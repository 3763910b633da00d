"""Chip smoke run: prove-while-train on one TPU, 128 wide at the paper's
batch of 64, one step per proof, depth cut to 2 layers (the merged
opening is 2^20 long).

    python chip_smoke.py

Depth is cut so that a cold run, compilation included, ends well inside
20 minutes on one TPU v5e: the prover compiles ~190 programs whatever
the depth, and at the paper's 8 layers one window took 123 s to prove
there, so the three phases below ran past 20 minutes.

Phases, all in this one process (a chip belongs to one process):

1. train: ``repro.launch.train.main`` takes two integer-SGD steps and
   streams one aggregated proof per step through the warm prover
   service into ``.chip_smoke/`` (``vk.bin``, ``proof_NNNNNN.bin``);
2. verify: every written proof is checked from its bytes against the
   serialized vk and must accept; the first with one byte flipped must
   reject;
3. pallas: one window is proved twice from the same seed and witness,
   with the jnp fold/validity backends and then with the Pallas kernels
   compiled for the chip, and the two proofs' bytes must be identical.

Readings (set-up, prove and verify seconds, proof bytes, peak device
memory, executable-cache counters) are printed, each named with the
device.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero and prints no such line when the first device
is not a TPU, or when any phase fails.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PROOF_DIR = os.path.join(HERE, ".chip_smoke")
ARCH = "fcnn-zkdl-16l"
LAYERS, WIDTH, BATCH, STEPS = 2, 128, 64, 2
LABEL = b"zkdl/train"           # the prover service's transcript label


class _Tee(io.TextIOBase):
    """Writes through to ``sink`` and keeps a copy."""

    def __init__(self, sink):
        self.sink, self.buf = sink, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.sink.write(s)

    def flush(self):
        self.sink.flush()


def tpu_device() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (first device: "
                         f"{devs[0].platform} {devs[0].device_kind}); "
                         f"nothing was run")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run(dev: dict, layers: int, width: int, batch: int) -> None:
    """Run the three phases at ``layers`` x ``width``, batch ``batch``,
    printing the readings.  Raises on any failed check."""
    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.core import execache, mle, quantfc
    from repro.core.pipeline import (ProofSession, compile as zk_compile,
                                     encode_proof, verify_bytes)
    from repro.core.pipeline.proofio import decode_vk
    from repro.kernels.validity_tables import ops as vt_ops
    from repro.launch import serve, steps, train
    from repro.util import cache_root

    name = f"{dev['platform']} {dev['kind']}"

    def say(what, value):
        print(f"[chip_smoke] {name}: {what} = {value}", flush=True)

    widths = (width,) * (layers + 1)
    zk_cfg = steps.build_proof_pipeline_config(get_config(ARCH), batch=batch,
                                               n_steps=1, widths=widths)
    print(f"[chip_smoke] device {dev}; geometry {layers} layers x {width} "
          f"wide, batch {batch}, T=1, merged_len {zk_cfg.merged_len} "
          f"(2^{zk_cfg.merged_len.bit_length() - 1})", flush=True)
    print(f"[chip_smoke] compile cache {cache_root()}; executable cache "
          f"{execache.cache_dir()}", flush=True)

    # -- phase 1: the train entry point, prover service in this process --
    shutil.rmtree(PROOF_DIR, ignore_errors=True)
    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        rc = train.main([
            "--arch", ARCH, "--layers", str(layers), "--d-model", str(width),
            "--global-batch", str(batch), "--steps", str(STEPS),
            "--prove-window", "1", "--log-every", "1", "--no-verify",
            "--proof-dir", PROOF_DIR])
    train_wall = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"train entry point returned {rc}")
    m = re.search(r"prover service warm in ([0-9.]+)s", tee.buf.getvalue())
    if m is None:
        raise RuntimeError("train entry point printed no set-up time")
    manifest = serve.read_manifest(PROOF_DIR)
    if sorted(manifest) != list(range(STEPS)) or any(
            rec["status"] != serve.COMMITTED for rec in manifest.values()):
        raise RuntimeError(f"expected {STEPS} committed windows: {manifest}")
    say("setup_s (generators + compile)", float(m.group(1)))
    say("train_wall_s", train_wall)
    for w in sorted(manifest):
        say(f"window {w} prove_s", manifest[w]["prove_s"])

    # -- phase 2: every proof from bytes; a tampered one must reject -----
    with open(os.path.join(PROOF_DIR, "vk.bin"), "rb") as f:
        vk = decode_vk(f.read())
    raws = []
    for w in sorted(manifest):
        with open(os.path.join(PROOF_DIR, f"proof_{w:06d}.bin"), "rb") as f:
            raws.append(f.read())
        t0 = time.perf_counter()
        ok = verify_bytes(vk, raws[-1], label=LABEL)
        say(f"window {w} verify_s", time.perf_counter() - t0)
        say(f"window {w} proof_bytes", len(raws[-1]))
        if not ok:
            raise RuntimeError(f"window {w}: proof rejected from bytes")
    bad = bytearray(raws[0])
    bad[len(bad) // 2] ^= 0x01
    if verify_bytes(vk, bytes(bad), label=LABEL):
        raise RuntimeError("a proof with one byte flipped was accepted")
    print(f"[chip_smoke] {len(raws)} proofs verified from bytes; the "
          f"tampered proof rejected", flush=True)

    # -- phase 3: Pallas kernels against jnp, byte for byte --------------
    qc = quantfc.QuantConfig(q_bits=zk_cfg.q_bits, r_bits=zk_cfg.r_bits)
    pk, _ = zk_compile(zk_cfg.graph, qc, n_steps=1)
    (wit,) = quantfc.synthetic_sgd_trajectory_widths(1, widths, batch, qc,
                                                     seed=0)

    def prove_bytes():
        session = ProofSession(pk, np.random.default_rng(1), label=LABEL)
        session.add_step(wit)
        t0 = time.perf_counter()
        data = encode_proof(session.prove())
        return data, time.perf_counter() - t0

    jnp_bytes, jnp_s = prove_bytes()
    mle.set_fold_backend("pallas")
    vt_ops.set_backend("pallas")
    try:
        pallas_bytes, pallas_s = prove_bytes()
    finally:
        mle.set_fold_backend(None)
        vt_ops.set_backend(None)
    say("jnp prove_s", jnp_s)
    say("pallas prove_s (first, kernel compile included)", pallas_s)
    if pallas_bytes != jnp_bytes:
        raise RuntimeError("Pallas and jnp proof bytes differ")
    print("[chip_smoke] Pallas and jnp proof bytes identical", flush=True)

    stats = jax.devices()[0].memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use"))
    say("execache stats", execache.stats())


def main() -> int:
    dev = tpu_device()
    sys.path.insert(0, os.path.join(HERE, "src"))
    run(dev, LAYERS, WIDTH, BATCH)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
