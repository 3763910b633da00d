"""The controls of the check that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--kinds bf16_f32 float32 int32]
    python3 bench/control.py --workload <cell> --seeds 1 2 3 --proof-faults

The first: the plain reference put in the program's place, with its
products in a lower precision, must fail the exact comparison that
every run makes.  For each seed it draws the weights and batches
exactly as a run of the cell does, trains as many steps as a run trains
(one window proved and the next window's steps), lets the reference and
each lower precision follow their own trajectories, and prints, per
seed and precision, the number a run compares (``step_max_abs_diff``).

The second (on the chip, at the cell's own size): each seed makes one
sound run of the cell, printed with its checks, and then each of
`PROOF_FAULTS` is planted in that run's committed proofs, re-encoded,
and read by the benchmark's own proof check (`bench.proofcheck`), which
has to fail.  Both print JSON lines; the benchmark's own runs run
neither.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

from bench import proofcheck  # noqa: E402

KINDS = ("bf16_f32", "float32", "int32")


def _drop_ipa_round(proof):
    """A shorter opening: the merged IPA's last L/R round left out."""
    proof.ipa_agg.ls.pop()
    proof.ipa_agg.rs.pop()


def _alter_sumcheck_round(proof):
    """A field error in the prover: one anchor round polynomial's value
    at 2 off by one (its sum over 0 and 1 still holds)."""
    msg = proof.sc_anchor.messages[0]
    msg[2] = (msg[2] + 1) % proofcheck.Q


def _commitment_off_group(proof):
    """A group error in the prover: a data commitment outside the
    order-q subgroup (-1 is a non-residue mod p = 2q + 1)."""
    proof.coms.x[0] = proofcheck.P - 1


#: faults planted in committed proofs: name -> (mutation, the number of
#: the benchmark's proof check that has to catch it)
PROOF_FAULTS = {
    "ipa_round_removed": (_drop_ipa_round, "proof_layout_mismatches"),
    "sumcheck_round_altered": (_alter_sumcheck_round,
                               "sumcheck_equations_failed"),
    "commitment_off_group": (_commitment_off_group,
                             "proof_elements_out_of_range"),
}


def plant(raw: bytes, fault: str) -> bytes:
    """``raw`` with ``fault`` planted and encoded again by the program."""
    from repro.core.pipeline.proofio import decode_proof, encode_proof

    proof = decode_proof(raw)
    PROOF_FAULTS[fault][0](proof)
    return encode_proof(proof)


def proof_fault_readings(raws, config: dict, steps_per_proof: int,
                         label: bytes) -> dict:
    """``fault -> the proof check's numbers`` on the planted proofs."""
    from bench import harness

    lay = harness.proof_layout(config, steps_per_proof)
    return {f: proofcheck.check([plant(r, f) for r in raws], lay, label,
                                config["q_bits"], config["r_bits"])
            for f in PROOF_FAULTS}


def readings(config: dict, traffic: dict, seed: int, kinds=KINDS) -> dict:
    """``kind -> step_max_abs_diff`` of that precision against the
    exact reference, over the steps one run trains."""
    from bench import harness, reference

    steps = 2 * int(traffic["steps_per_proof"])
    rng = harness.seed_rng(seed)
    ws0 = harness.initial_weights(rng, config)
    batches = [harness.draw_batch(rng, config, traffic)
               for _ in range(steps)]
    q, r, lr = config["q_bits"], config["r_bits"], config["lr_shift"]
    exact, ref_ws = [], ws0
    for b in batches:
        ref_ws, t = reference.train_step(b["x"], b["y"], ref_ws, q, r, lr)
        exact.append((ref_ws, t))
    out = {}
    for kind in kinds:
        mm = reference.control_matmul(kind)
        ws, worst = ws0, 0
        for b, (ref_ws, ref_t) in zip(batches, exact):
            try:
                ws, t = reference.train_step(b["x"], b["y"], ws, q, r, lr,
                                             matmul=mm)
            except reference.RangeError:
                worst = None          # crashed: fails, sets no reading
                break
            worst = max(worst, reference.max_abs_diff(ws, t, ref_ws, ref_t))
        out[kind] = worst
    return out


def main(argv=None) -> int:
    from bench import spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kinds", nargs="+", default=list(KINDS),
                    choices=KINDS)
    ap.add_argument("--proof-faults", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    from bench import harness

    # the compile caches of a run of the cell (`bench/run.py`)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ.pop("ZKDL_EXEC_CACHE", None)
    import jax
    dev = jax.devices()[0]
    for seed in args.seeds:
        line = {"workload": cell.name, "seed": seed,
                "device": dev.device_kind}
        if args.proof_faults:
            res = harness.run_cell(cell, seed, 1.0, False,
                                   time.perf_counter(), say=lambda m: None)
            line.update(correct=harness.is_correct(res),
                        checks={k: c["value"]
                                for k, c in res["checks"].items()},
                        faults=proof_fault_readings(
                            res["proofs"], cell.config,
                            int(cell.traffic["steps_per_proof"]),
                            res["label"]))
        else:
            line["step_max_abs_diff"] = readings(
                cell.config, cell.traffic, seed, args.kinds)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
