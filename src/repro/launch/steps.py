"""pjit-able train / prefill / decode steps with full sharding annotations.

``lower_cell`` is the single entry point the dry-run, roofline, and real
launchers share: given (config, mesh, shape-name) it returns the lowered
computation for that cell.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import hints
from repro.distributed import sharding as shard_rules
from repro.launch import specs as specs_mod
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.train import optim


def build_proof_pipeline_config(model_cfg, batch: int, n_steps: int,
                                q_bits: int = 16, r_bits: int = 8,
                                widths=None):
    """ArchConfig -> graph-first `PipelineConfig`, gated by the
    proof-graph registry.

    Families without a registered layer-graph builder raise a clear
    LookupError instead of silently training unproven; ``widths``
    overrides the uniform d_0..d_L table derived from the model config
    (heterogeneous pyramids, reduced runs).  The registered graph is the
    config's single source of truth (`PipelineConfig.from_graph`);
    callers wanting the full setup artifacts should pass the same graph
    to `repro.core.pipeline.compile`."""
    from repro.core.pipeline import PipelineConfig
    from repro.core.pipeline.graph import proof_graph_for_family

    if widths is None:
        widths = (model_cfg.d_model,) * (model_cfg.n_layers + 1)
    widths = tuple(int(w) for w in widths)
    # registry gate: raises LookupError for unprovable families
    graph = proof_graph_for_family(model_cfg.family, widths=widths,
                                   batch=batch)
    return PipelineConfig.from_graph(graph, q_bits=q_bits, r_bits=r_bits,
                                     n_steps=n_steps)


def build_zkdl_step(zk_cfg, lr_shift: int = 8):
    """Train step for a provable integer-SGD family: exact integer SGD
    whose per-batch witness feeds the proof pipeline (any layer-graph
    shape table, uniform or pyramid).

    Returns ``step(ws, batch) -> (new_ws, StepWitness)`` with batch a
    dict of int64 arrays {"x": (B, d_0), "y": (B, d_L)} at scale 2^R."""
    from repro.core import quantfc
    from repro.core.pipeline import profile

    qc = quantfc.QuantConfig(q_bits=zk_cfg.q_bits, r_bits=zk_cfg.r_bits)

    def step(ws, batch):
        with profile.span("train-step"):
            wit = quantfc.train_step_witness(batch["x"], batch["y"], ws, qc)
            return quantfc.sgd_apply(ws, wit.gw, lr_shift, qc), wit

    return step


class ZkdlProveHook:
    """Prove-while-train: observe each step's witness; every
    ``keys.cfg.n_steps`` steps one aggregated proof covering the whole
    window is emitted (and optionally verified) via `ProofSession`.

    The trainer never blocks on a per-step proof: proofs are per-window,
    which is the FAC4DNN cross-step amortization."""

    def __init__(self, keys, rng, verify: bool = True, on_proof=None,
                 label: bytes = b"zkdl/train"):
        from repro.core.pipeline import ProofSession

        self._mk = lambda: ProofSession(keys, rng, label=label)
        self._session = self._mk()
        self.keys = keys
        self.verify = verify
        self.on_proof = on_proof
        self.proofs = []           # (last_step, proof, prove_seconds)

    @property
    def n_pending(self) -> int:
        return self._session.n_pending

    def observe(self, step: int, wit) -> None:
        import time

        self._session.add_step(wit)
        if not self._session.is_full:
            return
        t0 = time.perf_counter()
        proof = self._session.prove()
        dt = time.perf_counter() - t0
        if self.verify:
            ok = self._session.verify(proof)
            if not ok:
                raise RuntimeError(f"aggregated proof REJECTED at step {step}")
        self.proofs.append((step, proof, dt))
        if self.on_proof is not None:
            self.on_proof(step, proof, dt)
        self._session = self._mk()


def build_train_step(cfg: ModelConfig, opt_cfg: optim.AdamWConfig):
    def train_step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: transformer.loss_fn(cfg, p, batch))(state["params"])
        new_params, new_opt, metrics = optim.apply_updates(
            opt_cfg, state["params"], grads, state["opt"])
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics
    return train_step


def build_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        logits, caches = transformer.forward(cfg, params, batch,
                                             collect_cache=True,
                                             head_last_only=True)
        return logits[:, -1], caches
    return prefill_step


def build_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, token, pos, positions3=None):
        return transformer.decode_step(cfg, params, cache, token, pos,
                                       positions3=positions3)
    return decode_step


def default_opt_cfg(cfg: ModelConfig) -> optim.AdamWConfig:
    """bf16 Adam moments for >=100B-param models (fits 16 GB/chip)."""
    big = cfg.param_count() > 100e9
    return optim.AdamWConfig(state_dtype="bfloat16" if big else "float32")


def state_specs(cfg: ModelConfig, opt_cfg: optim.AdamWConfig):
    p = specs_mod.param_specs(cfg)
    opt = jax.eval_shape(functools.partial(optim.init_opt_state,
                                           cfg=opt_cfg), p)
    return {"params": p, "opt": opt}


def state_shardings(cfg: ModelConfig, mesh, state_tree):
    p_sh = shard_rules.param_shardings(cfg, mesh, state_tree["params"])
    mu_sh = shard_rules.param_shardings(cfg, mesh, state_tree["opt"]["mu"])
    nu_sh = shard_rules.param_shardings(cfg, mesh, state_tree["opt"]["nu"])
    return {"params": p_sh,
            "opt": {"mu": mu_sh, "nu": nu_sh,
                    "step": shard_rules.replicated(mesh)}}


def lower_cell(cfg: ModelConfig, mesh, shape_name: str,
               opt_cfg: optim.AdamWConfig | None = None):
    """Lower the computation for one (arch x shape x mesh) cell.

    Returns the jax.stages.Lowered object (call .compile() on it)."""
    if opt_cfg is None:
        opt_cfg = default_opt_cfg(cfg)
    spec = specs_mod.input_specs(cfg, shape_name)
    repl = shard_rules.replicated(mesh)
    from repro.launch.mesh import batch_axes
    bax = batch_axes(mesh)
    sizes = {"batch": 1, "model": mesh.shape.get("model", 1)}
    for a in bax:
        sizes["batch"] *= mesh.shape[a]
    hints.set_axes(bax, "model" if "model" in mesh.axis_names else None,
                   sizes, mesh=mesh)

    if spec["kind"] == "train":
        st_spec = state_specs(cfg, opt_cfg)
        st_shard = state_shardings(cfg, mesh, st_spec)
        b_shard = shard_rules.batch_shardings(cfg, mesh, spec["batch"])
        step = build_train_step(cfg, opt_cfg)
        jitted = jax.jit(step,
                         in_shardings=(st_shard, b_shard),
                         out_shardings=(st_shard, None),
                         donate_argnums=(0,))
        with mesh:
            return jitted.lower(st_spec, spec["batch"])

    if spec["kind"] == "prefill":
        p_spec = specs_mod.param_specs(cfg)
        p_shard = shard_rules.param_shardings(cfg, mesh, p_spec)
        b_shard = shard_rules.batch_shardings(cfg, mesh, spec["batch"])
        step = build_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                         out_shardings=None)
        with mesh:
            return jitted.lower(p_spec, spec["batch"])

    # decode
    p_spec = specs_mod.param_specs(cfg)
    p_shard = shard_rules.param_shardings(cfg, mesh, p_spec)
    c_shard = shard_rules.cache_shardings(cfg, mesh, spec["cache"])
    step = build_decode_step(cfg)
    if cfg.family == "vlm":
        tok_shard = shard_rules.batch_shardings(
            cfg, mesh, {"embeds": spec["token"]})["embeds"]
        pos3_shard = shard_rules.batch_shardings(
            cfg, mesh, {"positions3": spec["positions3"]})["positions3"]
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, c_shard, tok_shard, repl, pos3_shard),
            out_shardings=(None, c_shard), donate_argnums=(1,))
        with mesh:
            return jitted.lower(p_spec, spec["cache"], spec["token"],
                                spec["pos"], spec["positions3"])
    tok_shard = shard_rules.batch_shardings(
        cfg, mesh, {"tokens": spec["token"]})["tokens"]
    jitted = jax.jit(step,
                     in_shardings=(p_shard, c_shard, tok_shard, repl),
                     out_shardings=(None, c_shard), donate_argnums=(1,))
    with mesh:
        return jitted.lower(p_spec, spec["cache"], spec["token"], spec["pos"])
