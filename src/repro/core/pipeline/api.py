"""The graph-first prover lifecycle: compile -> prove -> verify.

    graph = (GraphBuilder(batch=4).input(16)
             .dense(16).relu().dense(16).relu()
             .residual(to=1).dense(16).relu().output())
    pk, vk = compile(graph, quant=QuantConfig(16, 8), n_steps=T)

    session = ProofSession(pk)
    for wit in witnesses:                  # T of them
        session.add_step(wit)
    proof_bytes = encode_proof(session.prove())

    # any other process, from bytes alone:
    vk = decode_vk(vk_bytes)
    assert verify_bytes(vk, proof_bytes)

`compile` is the one-time setup phase: it freezes the graph's bucket and
slot layout into a `PipelineConfig` and derives every Pedersen/zkReLU
generator table — reusable across sessions, trajectories and processes.
The `ProvingKey` carries the full generator tables (big, prover-side
only); the `VerifyingKey` carries just the graph + quantization geometry
and re-derives its generators deterministically on first use, so its
serialized form (`VerifyingKey.to_bytes`) is a few hundred bytes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

from repro.core.quantfc import QuantConfig
from repro.core.pipeline import profile
from repro.core.pipeline.config import (PipelineConfig, PipelineKeys,
                                        make_keys)
from repro.core.pipeline.graph import LayerGraph


@dataclasses.dataclass(frozen=True)
class ProvingKey:
    """Prover-side setup artifact: config + full generator tables.

    The compiled executables behind a key are cached process-wide AND
    on disk (`repro.core.execache`), keyed by the argument shapes every
    program sees — which fully encode (graph_spec, quant, T, backend).
    `warm()` populates that cache ahead of time; `exec_stats()` reports
    hit/miss/disk counters, so "a second ProofSession never re-traces"
    is an observable property, not a hope."""
    keys: PipelineKeys

    @property
    def cfg(self) -> PipelineConfig:
        return self.keys.cfg

    @property
    def graph(self) -> LayerGraph:
        return self.keys.cfg.graph

    def warm(self, seed: int = 0) -> dict:
        """AOT-compile every prover executable for this key's geometry.

        Proves one throwaway synthetic window end to end (program
        shapes — not values — determine what compiles, and the
        executable cache keys on shapes), serializing each executable
        to the disk cache as it builds.  Returns the executable-cache
        stats delta; after a warm (this process or a fresh one sharing
        the disk cache) a `ProofSession(pk).prove()` re-traces nothing.
        """
        import numpy as np

        from repro.core import execache
        from repro.core.quantfc import synthetic_sgd_trajectory_widths
        from repro.core.pipeline.graph import graph_skips, graph_widths
        from repro.core.pipeline.session import ProofSession

        before = execache.stats()
        cfg = self.cfg
        quant = QuantConfig(q_bits=cfg.q_bits, r_bits=cfg.r_bits)
        with profile.span("warm"):
            wits = synthetic_sgd_trajectory_widths(
                cfg.n_steps, graph_widths(cfg.graph), cfg.batch, quant,
                seed=seed, skips=graph_skips(cfg.graph))
            session = ProofSession(self, np.random.default_rng(seed))
            for wit in wits:
                session.add_step(wit)
            proof = session.prove()
            with profile.span("warm-verify"):
                ok = session.verify(proof)
        assert ok, "warm-up proof rejected"
        after = execache.stats()
        return {k: after[k] - before[k] for k in after}

    def exec_stats(self) -> dict:
        from repro.core import execache
        return execache.stats()


@dataclasses.dataclass(frozen=True)
class VerifyingKey:
    """Verifier-side setup artifact: graph + quantization geometry.

    Generator tables derive lazily (deterministic label-based
    derivation, identical to the prover's), so the key serializes to a
    few hundred bytes and `verify_bytes` needs no session state."""
    cfg: PipelineConfig

    @functools.cached_property
    def keys(self) -> PipelineKeys:
        return make_keys(self.cfg)

    @property
    def graph(self) -> LayerGraph:
        return self.cfg.graph

    def to_bytes(self) -> bytes:
        from repro.core.pipeline.proofio import encode_vk
        return encode_vk(self)

    @classmethod
    def from_bytes(cls, data: bytes) -> "VerifyingKey":
        from repro.core.pipeline.proofio import decode_vk
        return decode_vk(data)


def compile(graph: LayerGraph, quant: Optional[QuantConfig] = None,
            n_steps: int = 1,
            warm: bool = False) -> Tuple[ProvingKey, VerifyingKey]:
    """One-time setup for a proof graph: freeze the bucket/slot layout
    and derive the commitment generators.

    The graph is the single source of truth — shapes, slot maps, shape
    buckets and the challenge-schedule geometry all derive from it; only
    the quantization (`quant`) and the aggregation window (`n_steps`)
    are free parameters.  Returns ``(ProvingKey, VerifyingKey)``; both
    wrap the same deterministic generator derivation, so a vk
    reconstructed from bytes in another process verifies proofs made
    with this pk.

    ``warm=True`` additionally AOT-compiles every prover executable for
    this geometry (one throwaway synthetic window through the full
    prover; see `ProvingKey.warm`), so the first real `prove()` pays
    zero trace/compile time — and, via the serialized-executable disk
    cache, neither does any later process for the same config."""
    # setup is the natural choke point every prover/verifier process
    # passes through: enabling the persistent XLA compilation cache here
    # (idempotent config flips) turns the ~tens-of-seconds first-prove
    # jit cost into a disk-cache hit for every later process
    from repro.util import enable_compilation_cache
    enable_compilation_cache()
    quant = quant if quant is not None else QuantConfig()
    cfg = PipelineConfig.from_graph(graph, q_bits=quant.q_bits,
                                    r_bits=quant.r_bits, n_steps=n_steps)
    with profile.span("keygen"):
        keys = make_keys(cfg)
    pk = ProvingKey(keys=keys)
    if warm:
        pk.warm()
    return pk, VerifyingKey(cfg=cfg)
