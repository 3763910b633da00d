"""One run of a cell: set-up, the measured window, and the check.

The one general generator: a closed loop of one training stream.  From
``--seed`` the benchmark draws the network's initial weights and every
step's batch (fresh rows each step); the program's integer SGD step
(`repro.launch.steps.build_zkdl_step`) turns them into a witness, which
goes to a warm `repro.launch.serve.ProverService` (thread isolation,
journal on, no in-service verify), ``steps_per_proof`` steps to a proof
window.  While a window proves, the next window's steps are trained; a
window is submitted only while the measured window is open, and a
window in flight when it closes is finished, not cut.

After the window (untimed): every step's witness and updated weights
are compared with the plain reference (`bench.reference`), which
follows its own trajectory from the same initial weights and batches;
every committed proof is held to `bench.proofcheck`, which uses none of
the program's code, and verified from its bytes against ``vk.bin`` by
the program's verifier; and a proof with one byte flipped must be
rejected.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench import devtrace, proofcheck, reference, spec

ROOT = spec.ROOT
# fixed paths inside the checkout: the compile caches, and the last
# traced run's profile
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
COMMIT_TIMEOUT_S = 900.0
POLL_S = 0.002
# A traced run records the first seconds of the window: past about 6.2 M
# op events the TPU profiler drops every later device event, and a
# window of the prover's sequential scans holds more.
TRACE_SECONDS = 8.0

# Each number the check compares, with its limit.  All are exact
# comparisons (limit 0): integer SGD has one right answer, a proof keeps
# its layout and its equations or not, and it verifies or not.
LIMITS = {"step_max_abs_diff": 0, "uncommitted_windows": 0,
          "proof_layout_mismatches": 0, "proof_elements_out_of_range": 0,
          "sumcheck_equations_failed": 0, "proofs_rejected": 0,
          "tampered_accepted": 0}


def seed_rng(seed: int) -> np.random.Generator:
    """Any whole number, negative or past 64 bits, seeds the stream."""
    return np.random.default_rng(seed % (1 << 64))


def quantize(v: np.ndarray, q_bits: int, r_bits: int) -> np.ndarray:
    lim = 1 << (q_bits - 1)
    return np.clip(np.floor(v * (1 << r_bits)).astype(np.int64),
                   -lim, lim - 1)


def initial_weights(rng, config: dict) -> List[np.ndarray]:
    """Uniform in +-gain, shrunk as sqrt(fan_in_floor / fan_in) above
    that fan-in, quantized: (d_in, d_out) per layer."""
    init, w = config["init"], config["widths"]
    return [quantize(rng.uniform(-1, 1, (a, b)) * init["gain"]
                     * min(1.0, (init["fan_in_floor"] / a) ** 0.5),
                     config["q_bits"], config["r_bits"])
            for a, b in zip(w, w[1:])]


def draw_batch(rng, config: dict, traffic: dict) -> Dict[str, np.ndarray]:
    b, w = config["batch"], config["widths"]
    q, r = config["q_bits"], config["r_bits"]
    return {"x": quantize(rng.uniform(*traffic["x_range"], (b, w[0])), q, r),
            "y": quantize(rng.uniform(*traffic["y_range"], (b, w[-1])), q, r)}


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    config: dict
    traffic: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    windows: int = 0
    steps_proved: int = 0
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    trace: Optional[devtrace.Summary] = None


class Trainer:
    """The training stream: the program's step on the benchmark's data,
    every step kept for the check."""

    def __init__(self, step_fn, ws, rng, config, traffic, spans, annotate):
        self.step_fn, self.ws, self.rng = step_fn, ws, rng
        self.config, self.traffic = config, traffic
        self.spans, self.annotate = spans, annotate
        self.records = []          # (batch, new_ws, witness) per step

    def step(self):
        batch = draw_batch(self.rng, self.config, self.traffic)
        with self.annotate("train_step"):
            t = time.perf_counter()
            self.ws, wit = self.step_fn(self.ws, batch)
            self.spans.setdefault("train_step", []).append(
                time.perf_counter() - t)
        self.records.append((batch, self.ws, wit))
        return wit


def _annotator(on: bool):
    import jax

    def annotate(name):
        if on:
            return jax.profiler.TraceAnnotation(devtrace.SPAN_PREFIX + name)
        return contextlib.nullcontext()
    return annotate


class Profiler:
    """The device trace of a traced run: the first `TRACE_SECONDS` of
    the window, marked by a ``bench/traced`` span."""

    def __init__(self, on: bool):
        self.on = on
        self.active, self.span, self.t_stop = False, None, None

    def start(self) -> None:
        import jax

        if not self.on:
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans only, no Python calls
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.active = True

    def window_started(self, t0: float) -> None:
        import jax

        if self.active:
            self.t_stop = t0 + TRACE_SECONDS
            self.span = jax.profiler.TraceAnnotation(devtrace.TRACED_SPAN)
            self.span.__enter__()

    def poll(self) -> None:
        if self.t_stop is not None and time.perf_counter() >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        if self.span is not None:
            self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.t_stop = False, None


def _wait_commit(service, n: int, poll) -> bool:
    """Until ``n`` windows have committed; False if a window failed, the
    worker stopped, or nothing committed within the timeout."""
    t_end = time.perf_counter() + COMMIT_TIMEOUT_S
    while len(service.proofs) < n:
        if (service.stats["failed_windows"] or time.perf_counter() > t_end
                or not service._worker.is_alive()):
            return False
        poll()
        time.sleep(POLL_S)
    return True


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, say=print) -> dict:
    """Set up, measure, check.  Returns the result line's fields."""
    import jax
    from repro.core import execache
    from repro.core.pipeline import PipelineConfig, compile as zk_compile
    from repro.core.pipeline.graph import proof_graph_for_family
    from repro.core.quantfc import QuantConfig
    from repro.launch import steps as steps_mod
    from repro.launch.serve import COMMITTED, ProverService, read_manifest

    config, traffic = cell.config, cell.traffic
    T = int(traffic["steps_per_proof"])
    run = Run(config=config, traffic=traffic)
    annotate = _annotator(traced)

    # -- set-up: keys, the warm prover service, the first window's steps
    graph = proof_graph_for_family(config["family"],
                                   widths=tuple(config["widths"]),
                                   batch=config["batch"])
    zk_cfg = PipelineConfig.from_graph(graph, q_bits=config["q_bits"],
                                       r_bits=config["r_bits"], n_steps=T)
    qc = QuantConfig(q_bits=config["q_bits"], r_bits=config["r_bits"])
    say(f"merged_len = {zk_cfg.merged_len} (T={T})")
    t = time.perf_counter()
    zk_compile(graph, qc, n_steps=T)        # derives every generator
    run.spans["keygen"] = [time.perf_counter() - t]
    proof_dir = tempfile.mkdtemp(prefix="bench-proofs-")
    try:
        service = ProverService(graph, qc, n_steps=T, out_dir=proof_dir,
                                verify=False, journal=True)
        t = time.perf_counter()
        service.start(warm=True)
        say(f"service start (keys again, executables, warm-up prove) = "
            f"{time.perf_counter() - t} s; exec cache {service.warm_stats}")
        rng = seed_rng(seed)
        ws0 = initial_weights(rng, config)
        trainer = Trainer(steps_mod.build_zkdl_step(zk_cfg,
                                                    config["lr_shift"]),
                          ws0, rng, config, traffic, run.spans, annotate)
        queued = [trainer.step() for _ in range(T)]
        profiler = Profiler(traced)
        profiler.start()
        run.setup_s = time.perf_counter() - t_start

        # -- the measured window ------------------------------------------
        attempted, ok = 0, True
        with annotate("window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            profiler.window_started(t0)
            while True:
                with annotate("submit"):
                    for wit in queued:
                        service.submit(wit)
                attempted += 1
                queued = [trainer.step() for _ in range(T)]
                with annotate("wait_commit"):
                    ok = _wait_commit(service, attempted, profiler.poll)
                t_last = time.perf_counter()
                if not ok or t_last >= deadline:
                    break
        run.window_s = t_last - t0
        profiler.stop()
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        service.close(timeout=COMMIT_TIMEOUT_S)

        manifest = read_manifest(proof_dir)
        committed = sorted(w for w, rec in manifest.items()
                           if rec.get("status") == COMMITTED)
        run.windows = len(committed)
        run.steps_proved = run.windows * T
        raws = []
        for w in committed:
            with open(os.path.join(proof_dir, f"proof_{w:06d}.bin"),
                      "rb") as f:
                raws.append(f.read())
            say(f"window {w}: prove_s (manifest) = {manifest[w]['prove_s']}"
                f", proof bytes = {manifest[w]['bytes']}")
        say(f"peak_bytes_in_use = {peak}; exec cache now "
            f"{execache.stats()}")
        if traced:
            run.trace = devtrace.reduce(*devtrace.load(TRACE_DIR))
            if run.trace is not None:
                say(f"trace: {run.trace.busy_s} s busy of "
                    f"{run.trace.window_s} s traced, truncated="
                    f"{run.trace.truncated}")

        checks = check(trainer, ws0, config, T, proof_dir, raws, attempted,
                       service.label, say)
    finally:
        shutil.rmtree(proof_dir, ignore_errors=True)

    return {"run": run, "checks": checks, "attempted": attempted,
            "committed": run.windows, "peak": peak, "proofs": raws,
            "label": service.label}


def check(trainer, ws0, config, T, proof_dir, raws, attempted, label,
          say) -> Dict[str, dict]:
    """The numbers compared, each with its value and limit."""
    from repro.core.pipeline import verify_bytes
    from repro.core.pipeline.proofio import decode_vk

    # 1. the training stream against the reference's own trajectory
    ref_ws, worst = ws0, 0
    for batch, got_ws, wit in trainer.records:
        try:
            ref_ws, ref_t = reference.train_step(
                batch["x"], batch["y"], ref_ws, config["q_bits"],
                config["r_bits"], config["lr_shift"])
        except reference.RangeError:
            worst = np.iinfo(np.int64).max
            break
        got_t = {k: list(getattr(wit, k)) for k in reference.TENSORS}
        worst = max(worst, reference.max_abs_diff(got_ws, got_t,
                                                  ref_ws, ref_t))
    # 2. every committed proof, by the benchmark's own check and by the
    # program's verifier from its bytes; 3. one byte flipped
    t = time.perf_counter()
    independent = proofcheck.check(raws, proof_layout(config, T), label,
                                   config["q_bits"], config["r_bits"])
    say(f"independent proof check = {time.perf_counter() - t} s")
    with open(os.path.join(proof_dir, "vk.bin"), "rb") as f:
        vk = decode_vk(f.read())
    rejected = 0
    for w, raw in enumerate(raws):
        t = time.perf_counter()
        if not verify_bytes(vk, raw, label=label):
            rejected += 1
        say(f"proof {w}: verify from bytes = {time.perf_counter() - t} s")
    tampered = 0
    if raws:
        bad = bytearray(raws[0])
        bad[len(bad) // 2] ^= 0x01
        why: list = []
        t = time.perf_counter()
        tampered = int(verify_bytes(vk, bytes(bad), label=label, trace=why))
        say(f"tampered proof: accepted={bool(tampered)} in "
            f"{time.perf_counter() - t} s ({why})")
    values = {"step_max_abs_diff": worst,
              "uncommitted_windows": attempted - len(raws), **independent,
              "proofs_rejected": rejected, "tampered_accepted": tampered}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def proof_layout(config: dict, steps_per_proof: int) -> dict:
    """The protocol's proof layout at this configuration and window."""
    return config["proof_layout_by_steps_per_proof"][str(steps_per_proof)]


def is_correct(result: dict) -> bool:
    return result["committed"] >= 1 and all(
        c["value"] <= c["limit"] for c in result["checks"].values())
