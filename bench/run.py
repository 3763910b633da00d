"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared beside its limit, which also end
standard error.  Readings that are not metrics (peak HBM, proof bytes,
executable-cache counters, per-window prove and verify seconds) go on
earlier lines.

Exits non-zero and prints no result line when JAX finds no TPU, or
fewer chips than the cell asks for: there is no CPU fallback.  Both
compile caches live at one fixed path inside the checkout, so only a
cell's first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, this directory heads sys.path; its modules are
# imported as the ``bench`` package instead
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


def _say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness, spec

    # the program keeps both compile caches under this directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ.pop("ZKDL_EXEC_CACHE", None)
    try:
        import repro.launch.serve  # noqa: F401  the system under test
    except ImportError as exc:
        print(f"bench: the program is not in this checkout: {exc}",
              file=sys.stderr)
        return 2
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind}); nothing was run", file=sys.stderr)
        return 1
    kind = devs[0].device_kind
    _say(f"{cell.name} seed {args.seed} on {len(devs)} x {kind}; caches "
         f"under {harness.CACHE_DIR}")

    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, say=_say)
    run = res["run"]
    metrics = spec.read_metrics(cell.per_layer if args.trace
                                else cell.end_to_end, run)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": res["peak"]}
    out = {"correct": harness.is_correct(res),
           "attempted": res["attempted"],
           "failed": res["attempted"] - res["committed"]
           + res["checks"]["proofs_rejected"]["value"],
           "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        from bench import devtrace

        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": devtrace.top(run.trace.program_s.items()),
            "idle_gaps": devtrace.top(run.trace.gaps)}
    out["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
