"""The check that decides ``correct``: a sound run passes it, the control
and each fault the cell can have fail it.  On the CPU at a small size;
the harness's look for a chip is skipped, the rest of a run is driven.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, spec
from bench.control import plant, readings
from bench.tests.layouts import program_layout

ROOT = spec.ROOT


def _tiny_cell():
    """The cell's configuration at 3-4-4-1, batch 4: the same graph
    builder, prover, service and check as the timed sizes."""
    cell = spec.load_cell("autompg-dnn.prove")
    cell.config = dict(cell.config, widths=[3, 4, 4, 1], batch=4)
    cell.config["proof_layout_by_steps_per_proof"] = {
        "1": program_layout(cell.config, 1)}
    return cell


def _run(cell=None):
    cell = cell or _tiny_cell()
    res = harness.run_cell(cell, 2**31 + 11, 0.5, False,
                           time.perf_counter(), say=lambda m: None)
    return res, harness.is_correct(res)


def _broken_step(monkeypatch, fault):
    from repro.launch import steps

    real = steps.build_zkdl_step

    def build(zk_cfg, lr_shift=8):
        step = real(zk_cfg, lr_shift)

        def broken(ws, batch):
            if fault == "state_unchanged":
                _, wit = step(ws, batch)
                return ws, wit
            half = batch["x"].shape[0] // 2      # second half replaced
            b = {k: np.concatenate([v[:half], v[:half]])
                 for k, v in batch.items()}
            return step(ws, b)
        return broken

    monkeypatch.setattr(steps, "build_zkdl_step", build)


def _altered_proof(monkeypatch, fault):
    """A proof altered where the service produces it: one byte flipped,
    or the merged opening one round short."""
    import repro.core.pipeline as pipeline

    real = pipeline.encode_proof

    def altered(proof):
        data = real(proof)
        if fault == "ipa_round_removed":
            return plant(data, fault)
        data = bytearray(data)
        data[-9] ^= 0x04
        return bytes(data)

    monkeypatch.setattr(pipeline, "encode_proof", altered)


def test_sound_run_is_correct():
    res, ok = _run()
    assert ok, res["checks"]
    assert res["committed"] >= 1 and res["run"].steps_proved >= 1


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_proof", "ipa_round_removed"])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    if fault in ("altered_proof", "ipa_round_removed"):
        _altered_proof(monkeypatch, fault)
    else:
        _broken_step(monkeypatch, fault)
    res, ok = _run()
    assert not ok
    failed = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    want = {"altered_proof": "proofs_rejected",
            "ipa_round_removed": "proof_layout_mismatches"}.get(
                fault, "step_max_abs_diff")
    assert want in failed, res["checks"]


@pytest.mark.parametrize("seed", [2, 3])
def test_control_fails_the_comparison(seed):
    """The reference in a lower precision (bfloat16 operands, float32
    accumulation: a float32 matmul at the TPU's default precision), in
    the program's place at the cell's own size, fails the exact
    comparison; int32 and float32 hold this step's values exactly."""
    cell = spec.load_cell("autompg-dnn.prove")
    got = readings(cell.config, cell.traffic, seed)
    assert got["bf16_f32"] > harness.LIMITS["step_max_abs_diff"]
    assert got["int32"] == 0 and got["float32"] == 0


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "autompg-dnn.prove", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "nothing was run" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """Without the program beside them, the benchmark's files alone
    make no result."""
    import shutil

    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "autompg-dnn.prove", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()
    assert "the program is not in this checkout" in p.stderr
