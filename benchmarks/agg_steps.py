"""Cross-step aggregation benchmark: the FAC4DNN amortization curve.

For T in --steps-list, proves ONE aggregated session over T consecutive
batch updates (shared commitments, sumchecks, validity argument and IPA
openings; the step axis is log2(T) extra sumcheck variables) and reports
per-step proving time and per-step proof size.  The T=1 row doubles as
the "T independent proofs" baseline: independent proving costs exactly
T * row(1), so amortization = per_step(T) / per_step(1).

    PYTHONPATH=src python benchmarks/agg_steps.py \
        [--steps-list 1,2,4,8] [--width 4] [--batch 2] [--layers 2] \
        [--repeats 2] [--no-verify] [--out BENCH_agg_steps.json] \
        [--phases-out BENCH_prover_phases.json] \
        [--het-widths 16,8,4,2] [--smoke]

Emits BENCH_agg_steps.json with the full curve, the monotonicity
verdicts on the T=1..4 prefix, and a heterogeneous cell comparing a
pyramid MLP against a uniform MLP of (approximately) equal parameter
count in one aggregated session.  Both prove and verify run an untimed
warm-up first; the warm-up durations are recorded separately as
``prove_compile_s`` / ``verify_compile_s`` so jit compilation never
pollutes (or de-monotonizes) the reported numbers.

The parent process never touches JAX (a chip belongs to one process,
so a child could not reach it after the parent had): every cell runs in
a child of its own.  Per T a cold child proves and measures, populating
the serialized-executable cache (`repro.core.execache`) on disk, and
then a warm child reports ``prove_compile_warm_s``, the warm-start
cost: what a FRESH process pays on its first prove once that cache is
populated, first_prove - steady_prove, along with the executable-cache
hit/miss counters (a correct warm start shows ``misses == 0``).  The old
in-process ``jax.clear_caches()`` + re-prove measurement is gone — it
dropped executables a fresh process would load from disk while KEEPING
warm host state a fresh process wouldn't have, so it could read higher
than the cold path at small T and was neither cold nor warm.

Each row also carries the per-phase prover profile (commit / matmul /
anchor / openings wall clock plus the openings sub-phases, see
`repro.core.pipeline.profile`), emitted standalone as
BENCH_prover_phases.json.  ``--smoke`` is the CI guard: tiny shapes,
every cell must verify, the phase profile must account for ~all prove
time, serialized per-step bytes at T=8 must stay strictly below the
recorded v1 baseline, the zkReLU validity prep sub-phase must stay
under its share budget of T=8 prove time, and the warm start must be
genuinely warm: zero executable-cache misses in the probe subprocess,
T=8 warm overhead under WARM_COMPILE_MAX_S and within
WARM_T_INVARIANCE_MAX of the T=1 overhead (compile cost flat in T); no
JSON written.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

_CHILD_TAG = "AGG_STEPS_CHILD "


def _warm_probe(params: dict) -> dict:
    """Body of the warm child: starting from a populated executable-cache
    disk (the cold child wrote it), rebuild the keys, prove twice, and
    report first/steady timings plus the execache counters.  This IS the
    warm-start scenario: a fresh prover process for a config someone has
    proved before on this machine."""
    from repro.core import execache
    from repro.core.quantfc import (QuantConfig,
                                    synthetic_sgd_trajectory_widths)
    from repro.core.pipeline import PipelineConfig, ProofSession, make_keys

    widths = tuple(params["widths"])
    cfg = PipelineConfig(n_layers=len(widths) - 1, batch=params["batch"],
                         q_bits=params["q_bits"], r_bits=params["r_bits"],
                         n_steps=params["T"], widths=widths)
    qc = QuantConfig(q_bits=params["q_bits"], r_bits=params["r_bits"])
    t0 = time.perf_counter()
    keys = make_keys(cfg)
    setup_s = time.perf_counter() - t0
    wits = synthetic_sgd_trajectory_widths(params["T"], widths,
                                           params["batch"], qc,
                                           seed=params["T"])

    def prove_once(seed):
        session = ProofSession(keys, np.random.default_rng(seed))
        for w in wits:
            session.add_step(w)
        t0 = time.perf_counter()
        session.prove()
        return time.perf_counter() - t0

    execache.reset_stats()
    first = prove_once(0)
    stats = execache.stats()          # counters for the FIRST prove only
    steady = min(prove_once(s) for s in (1, 2))
    return {
        "setup_s": setup_s,
        "first_prove_s": first,
        "steady_prove_s": steady,
        "warm_overhead_s": max(0.0, first - steady),
        "exec_stats": stats,
        "exec_warm": execache.enabled() and execache.cache_dir() is not None,
    }


def _child_main(kind: str, params: dict) -> None:
    """Body of a ``--child`` process: run one cell and print its report
    as one tagged JSON line on stdout."""
    from repro.util import enable_compilation_cache

    enable_compilation_cache()        # mirror what a real prover enables
    if kind == "cold":
        report = bench_T(**params)
    elif kind == "warm":
        report = _warm_probe(params)
    elif kind == "het":
        report = bench_heterogeneous(argparse.Namespace(**params))
    else:
        raise SystemExit(f"unknown child kind {kind!r}")
    print(_CHILD_TAG + json.dumps(report), flush=True)


def _run_child(kind: str, params: dict) -> dict:
    """Run one cell in a fresh interpreter and return its report."""
    here = os.path.abspath(__file__)
    src = os.path.join(os.path.dirname(os.path.dirname(here)), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, here, "--child", kind, json.dumps(params)],
        capture_output=True, text=True, env=env, timeout=1800)
    for line in proc.stdout.splitlines():
        if line.startswith(_CHILD_TAG):
            return json.loads(line[len(_CHILD_TAG):])
    raise RuntimeError(
        f"{kind} child failed (rc={proc.returncode}):\n"
        f"{proc.stdout[-1000:]}\n{proc.stderr[-2000:]}")


def _measure_warm(T: int, batch: int, q_bits: int, r_bits: int, widths,
                  attempts: int = 2):
    """Run the warm-start probe in a FRESH child and return its report
    (best of ``attempts`` runs by warm overhead — the probe is pure wall
    clock, so background load can only inflate it).  A cold child must
    have proved this exact config already (so the executable-cache disk
    is populated)."""
    params = {"T": T, "batch": batch, "q_bits": q_bits, "r_bits": r_bits,
              "widths": list(widths)}
    best = None
    for _ in range(attempts):
        report = _run_child("warm", params)
        # a single re-traced program anywhere disqualifies the whole
        # warm start — never let a lucky fast attempt mask it
        if report["exec_stats"]["misses"] > 0:
            return report
        if best is None or (report["warm_overhead_s"]
                            < best["warm_overhead_s"]):
            best = report
    return best


def bench_cell(T: int, layers: int, batch: int, width: int, q_bits: int,
               r_bits: int, repeats: int, verify: bool,
               warm_probe: bool = True) -> dict:
    """One T row: the cold child's measurements, plus the warm child's
    warm-start cost when ``warm_probe``."""
    row = _run_child("cold", {
        "T": T, "layers": layers, "batch": batch, "width": width,
        "q_bits": q_bits, "r_bits": r_bits, "repeats": repeats,
        "verify": verify})
    warm = None
    if warm_probe:
        warm = _measure_warm(T, batch, q_bits, r_bits,
                             (width,) * (layers + 1))
    row.update({
        "prove_compile_warm_s": warm["warm_overhead_s"] if warm else None,
        "warm_first_prove_s": warm["first_prove_s"] if warm else None,
        "warm_steady_prove_s": warm["steady_prove_s"] if warm else None,
        "warm_setup_s": warm["setup_s"] if warm else None,
        "warm_exec_stats": warm["exec_stats"] if warm else None,
        "warm_exec_warm": warm["exec_warm"] if warm else None,
    })
    return row


def bench_T(T: int, layers: int, batch: int, width: int, q_bits: int,
            r_bits: int, repeats: int, verify: bool, widths=None):
    """Prove and verify one aggregated session in this process (a cold
    child's body): compile-inclusive first prove, then best-of-N."""
    from repro.core.quantfc import (QuantConfig,
                                    synthetic_sgd_trajectory_widths)
    from repro.core.pipeline import (PipelineConfig, ProofSession,
                                     encode_proof, make_keys,
                                     verify_session)

    if widths is None:
        widths = (width,) * (layers + 1)
    cfg = PipelineConfig(n_layers=len(widths) - 1, batch=batch,
                         q_bits=q_bits, r_bits=r_bits, n_steps=T,
                         widths=widths)
    qc = QuantConfig(q_bits=q_bits, r_bits=r_bits)
    keys = make_keys(cfg)
    wits = synthetic_sgd_trajectory_widths(T, widths, batch, qc, seed=T)

    def prove_once(seed):
        session = ProofSession(keys, np.random.default_rng(seed))
        for w in wits:
            session.add_step(w)
        t0 = time.perf_counter()
        proof = session.prove()
        return time.perf_counter() - t0, proof, session.last_profile

    # warmup run (jit compilation / caches), then best-of-N timed runs;
    # the warmup duration is recorded SEPARATELY so compile time never
    # leaks into (and never jitters) the reported prove/verify numbers
    prove_compile_s, proof, _ = prove_once(0)

    best, phases = float("inf"), None
    for rep in range(repeats):
        dt, proof, prof = prove_once(rep + 1)
        if dt < best:
            best, phases = dt, prof

    ok, verify_s, verify_compile_s = None, None, None
    if verify:
        t0 = time.perf_counter()
        ok = verify_session(keys, proof)          # untimed warm-up cell
        verify_compile_s = time.perf_counter() - t0
        assert ok, f"aggregated proof rejected at T={T}"
        verify_s = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            ok = verify_session(keys, proof)
            verify_s = min(verify_s, time.perf_counter() - t0)
        assert ok, f"aggregated proof rejected at T={T}"

    # proof size is the CANONICAL WIRE FORMAT (len(encode_proof)), not
    # an in-memory estimate: what actually crosses the network per window
    proof_bytes = len(encode_proof(proof))
    return {
        "T": T,
        "prove_s": best,
        "per_step_s": best / T,
        "proof_bytes": proof_bytes,
        "per_step_bytes": proof_bytes / T,
        "prove_compile_s": prove_compile_s,
        "verify_s": verify_s,
        "verify_compile_s": verify_compile_s,
        "verify_ok": ok,
        "phases": phases.as_dict() if phases is not None else None,
    }


def bench_heterogeneous(args, T: int = 2):
    """The heterogeneous cell: a pyramid MLP vs a uniform-width MLP at
    (approximately) equal parameter count, both aggregated over T steps
    in ONE ProofSession.  FAC4DNN's claim is that heterogeneous shapes
    aggregate as well as uniform ones; the acceptance bar is pyramid
    per-step prove time within 1.5x of uniform."""
    het_widths = tuple(int(w) for w in args.het_widths.split(","))
    uni = bench_T(T, args.het_uniform_layers, args.batch,
                  args.het_uniform_width, args.q_bits, args.r_bits,
                  args.repeats, verify=not args.no_verify)
    het = bench_T(T, 0, args.batch, 0, args.q_bits, args.r_bits,
                  args.repeats, verify=not args.no_verify,
                  widths=het_widths)
    p_het = sum(a * b for a, b in zip(het_widths, het_widths[1:]))
    p_uni = args.het_uniform_layers * args.het_uniform_width ** 2
    cell = {
        "T": T,
        "widths": list(het_widths),
        "uniform_width": args.het_uniform_width,
        "uniform_layers": args.het_uniform_layers,
        "param_count_het": p_het,
        "param_count_uniform": p_uni,
        "het_per_step_s": het["per_step_s"],
        "uniform_per_step_s": uni["per_step_s"],
        "het_per_step_bytes": het["per_step_bytes"],
        "uniform_per_step_bytes": uni["per_step_bytes"],
        "ratio_het_vs_uniform": het["per_step_s"] / uni["per_step_s"],
        "verify_ok": het["verify_ok"] and uni["verify_ok"],
    }
    print(f"agg_steps,het,widths={'x'.join(map(str, het_widths))},"
          f"params={p_het}v{p_uni},per_step_s="
          f"{het['per_step_s']:.2f}v{uni['per_step_s']:.2f},"
          f"ratio={cell['ratio_het_vs_uniform']:.2f}", flush=True)
    return cell


# serialized per-step proof bytes at T=8 under the v1 byte format
# (committed BENCH_agg_steps.json baseline before the one-IPA direct-sum
# opening); --smoke asserts the current format stays STRICTLY smaller,
# so an opening-layout regression can never ship silently through CI
V1_T8_PER_STEP_BYTES = 494.375

# ceiling on the zkrelu-validity share of T=8 prove wall clock (the
# sub-phase now covers statement/table prep only — the validity IPA
# itself rides the merged pair IPA); under the v2 host-side per-bit
# loops this phase consumed ~45% of prove, the kernel path keeps it
# comfortably below a third
VALIDITY_SHARE_MAX_T8 = 0.35

# warm-start gates (fresh-subprocess probe, executable cache populated):
# a warm prover must come up in seconds, and the cost must be flat in T
# — the scan-shaped sumcheck bodies and masked IPA ladder make the
# executable set depend only on shape buckets, not on depth or T, so
# T=8 pays (nearly) the same warm overhead as T=1.  The absolute slack
# absorbs disk/OS noise at toy shapes where the overheads are a few
# seconds and a 0.3s wobble would otherwise flip the ratio.  The
# absolute budget carries ~30% headroom over a loaded-container
# measurement (the 5.0s budget tripped at 5.2-5.5s on a machine where
# the unchanged seed measured the same — interpreter+jax import and
# disk-cache loads drift with host load; the warm CONTRACT is the
# zero-miss assert above, the seconds bound only catches a cold start's
# ~25-30s full re-trace).
WARM_COMPILE_MAX_S = 7.0
WARM_T_INVARIANCE_MAX = 1.3
WARM_T_INVARIANCE_SLACK_S = 0.5


def monotonic_prefix(rows, key, t_max=4):
    """Strictly-decreasing verdict over the measured T<=t_max prefix;
    None (json null) when T=1 wasn't measured or the prefix is trivial,
    so a partial --steps-list never yields a vacuous True."""
    vals = [r[key] for r in rows if r["T"] <= t_max]
    if len(vals) < 2 or not any(r["T"] == 1 for r in rows):
        return None
    return all(b < a for a, b in zip(vals, vals[1:]))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps-list", default="1,2,4,8")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--width", type=int, default=4)
    ap.add_argument("--q-bits", type=int, default=16)
    ap.add_argument("--r-bits", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--het-widths", default="16,8,4,2",
                    help="pyramid shape table for the heterogeneous cell")
    ap.add_argument("--het-uniform-width", type=int, default=8)
    ap.add_argument("--het-uniform-layers", type=int, default=3)
    ap.add_argument("--no-het", action="store_true",
                    help="skip the heterogeneous comparison cell")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny shapes, 1 repeat, asserts every "
                         "cell verifies AND the phase profile accounts "
                         "for ~all prove time, writes no JSON unless "
                         "--out/--phases-out are passed explicitly")
    ap.add_argument("--out", default=None)
    ap.add_argument("--phases-out", default=None,
                    help="per-phase prover profile JSON "
                         "(default BENCH_prover_phases.json)")
    ap.add_argument("--child", nargs=2, default=None,
                    metavar=("KIND", "JSON"),
                    help=argparse.SUPPRESS)   # internal: one cell's body
    ap.add_argument("--no-warm-probe", action="store_true",
                    help="skip the fresh-subprocess warm-start probe")
    args = ap.parse_args(argv)
    if args.child is not None:
        _child_main(args.child[0], json.loads(args.child[1]))
        return None
    if args.smoke:
        # T=8 rides along so CI can gate the serialized per-step size
        # against the recorded v1 baseline (see V1_T8_PER_STEP_BYTES)
        args.steps_list = "1,2,8"
        args.repeats = 1
        args.no_verify = False
        args.het_widths = "8,4,4,2"        # multi-bucket, but tiny
        args.het_uniform_width = 4
        args.het_uniform_layers = 2
    if args.out is None:
        args.out = None if args.smoke else "BENCH_agg_steps.json"
    if args.phases_out is None:
        args.phases_out = None if args.smoke else "BENCH_prover_phases.json"

    steps = sorted({int(s) for s in args.steps_list.split(",")})
    rows = []
    for T in steps:
        row = bench_cell(T, args.layers, args.batch, args.width,
                         args.q_bits, args.r_bits, args.repeats,
                         verify=not args.no_verify,
                         warm_probe=not args.no_warm_probe)
        base = rows[0] if rows else row
        row["amortization_vs_T1"] = (row["per_step_s"] / base["per_step_s"]
                                     if base["T"] == 1 else None)
        rows.append(row)
        amort = row["amortization_vs_T1"]
        print(f"agg_steps,T={T},prove_s={row['prove_s']:.2f},"
              f"per_step_s={row['per_step_s']:.2f},"
              f"proof_kB={row['proof_bytes'] / 1024:.1f},"
              f"per_step_kB={row['per_step_bytes'] / 1024:.2f},"
              f"amortization="
              f"{f'{amort:.2f}' if amort is not None else 'n/a'}",
              flush=True)

    result = {
        "config": {"layers": args.layers, "batch": args.batch,
                   "width": args.width, "q_bits": args.q_bits,
                   "r_bits": args.r_bits, "repeats": args.repeats},
        "rows": rows,
        "monotonic_per_step_time_1_to_4": monotonic_prefix(
            rows, "per_step_s"),
        "monotonic_per_step_size_1_to_4": monotonic_prefix(
            rows, "per_step_bytes"),
    }
    if not args.no_het:
        result["heterogeneous"] = _run_child("het", {
            k: getattr(args, k) for k in (
                "het_widths", "het_uniform_layers", "het_uniform_width",
                "batch", "q_bits", "r_bits", "repeats", "no_verify")})

    phases_result = {
        "config": result["config"],
        "rows": [{"T": r["T"], "prove_s": r["prove_s"],
                  **(r["phases"] or {})} for r in rows],
    }
    if args.smoke:
        assert all(r["verify_ok"] for r in rows), "smoke: a cell rejected"
        if not args.no_het:
            assert result["heterogeneous"]["verify_ok"], \
                "smoke: heterogeneous cell rejected"
        # the phase profiler must attribute (nearly) all of prove time
        for r in rows:
            ph = r["phases"]
            assert ph is not None, f"smoke: no phase profile at T={r['T']}"
            assert ph["accounted_s"] <= ph["total_s"] * 1.001 + 1e-6 and \
                ph["accounted_s"] >= ph["total_s"] * 0.85, \
                f"smoke: phases {ph['accounted_s']:.3f}s do not sum to " \
                f"prove total {ph['total_s']:.3f}s at T={r['T']}"
            sub = ph.get("sub_phases_s")
            assert sub and set(sub) >= {"claim-combine", "ipa-rounds",
                                        "sigma", "zkrelu-validity"}, \
                f"smoke: openings sub-phases missing at T={r['T']}: {sub}"
        # proof-size regression gate: the one-IPA opening must keep the
        # serialized per-step bytes strictly under the v1 baseline
        (t8,) = [r for r in rows if r["T"] == 8]
        assert t8["per_step_bytes"] < V1_T8_PER_STEP_BYTES, (
            f"smoke: serialized per-step proof at T=8 is "
            f"{t8['per_step_bytes']:.1f} B/step, not smaller than the v1 "
            f"baseline {V1_T8_PER_STEP_BYTES} B/step")
        # phase-share gate: with the kernel-built tables and the validity
        # claims folded into the merged IPA, zkReLU validity prep must
        # stay a MINORITY cost of the T=8 prove (it was ~45% under the
        # v2 host-loop path; regressions to per-bit python show up here)
        vshare = (t8["phases"]["sub_phases_s"]["zkrelu-validity"]
                  / t8["prove_s"])
        assert vshare <= VALIDITY_SHARE_MAX_T8, (
            f"smoke: zkReLU validity prep is {vshare:.0%} of T=8 prove "
            f"time, over the {VALIDITY_SHARE_MAX_T8:.0%} budget")
        # warm-start gates: a fresh process with the executable cache
        # populated must (a) never re-trace, (b) come up fast, (c) pay
        # the same compile overhead at T=8 as at T=1 (flat in T)
        warm_line = "warm probe skipped"
        if not args.no_warm_probe:
            (t1,) = [r for r in rows if r["T"] == 1]
            for r in rows:
                es = r["warm_exec_stats"]
                if r["warm_exec_warm"]:
                    assert es["misses"] == 0, (
                        f"smoke: warm-start subprocess at T={r['T']} "
                        f"re-compiled {es['misses']} programs (expected "
                        f"0 executable-cache misses): {es}")
            t8w, t1w = t8["prove_compile_warm_s"], \
                t1["prove_compile_warm_s"]
            assert t8w <= WARM_COMPILE_MAX_S, (
                f"smoke: T=8 warm-start overhead {t8w:.2f}s over the "
                f"{WARM_COMPILE_MAX_S}s budget")
            assert t8w <= (WARM_T_INVARIANCE_MAX * t1w
                           + WARM_T_INVARIANCE_SLACK_S), (
                f"smoke: warm-start overhead not flat in T: T=8 "
                f"{t8w:.2f}s vs T=1 {t1w:.2f}s (budget "
                f"{WARM_T_INVARIANCE_MAX}x + "
                f"{WARM_T_INVARIANCE_SLACK_S}s)")
            warm_line = (f"warm start {t8w:.2f}s at T=8 vs {t1w:.2f}s "
                         f"at T=1, 0 misses")
        print(f"agg_steps: smoke ok (all cells verified; phases account "
              f"for prove time; T=8 per-step {t8['per_step_bytes']:.1f} B "
              f"< v1 baseline {V1_T8_PER_STEP_BYTES} B; validity share "
              f"{vshare:.0%} <= {VALIDITY_SHARE_MAX_T8:.0%}; "
              f"{warm_line})", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"agg_steps: wrote {args.out}; "
              f"per-step time monotonic(1..4)="
              f"{result['monotonic_per_step_time_1_to_4']}, "
              f"per-step size monotonic(1..4)="
              f"{result['monotonic_per_step_size_1_to_4']}", flush=True)
    if args.phases_out:
        with open(args.phases_out, "w") as f:
            json.dump(phases_result, f, indent=1)
        print(f"agg_steps: wrote {args.phases_out}", flush=True)
    return result


if __name__ == "__main__":
    main()
