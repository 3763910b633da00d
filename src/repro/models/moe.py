"""Mixture-of-experts with sorted capacity dispatch.

Tokens-choose-experts top-k routing; assignments are sorted by expert and
scattered into an (E, C, d) buffer, so expert FFN compute scales with
top_k (not E) and the buffer's expert axis shards cleanly over the mesh
'model' axis.  Overflow beyond capacity is dropped (standard;
capacity_factor controls head-room).

Distribution (GShard/Switch pattern): the token->slot gather/scatter has
data-dependent indices, so under plain SPMD it crosses the data axis and
XLA materializes an all-reduce of the full (n*k, d) dispatch tensor PER
LAYER (measured 5.2e10 B/layer on deepseek-v2-lite -- EXPERIMENTS.md
§Perf iter 2).  The fix is per-shard dispatch: a shard_map over the batch
axes routes each data shard's tokens into its own capacity slice
(C_local = C / n_shards), keeping every gather/scatter local; the only
cross-device movement left is the (E, C, d) buffer's expert all-to-all,
which is the irreducible MoE traffic.  Outside a configured mesh (unit
tests, 1 device) the unsharded path runs unchanged.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import hints
from repro.models.config import ModelConfig
from repro.models.layers import activation, dense_init


def init_moe(key, cfg: ModelConfig) -> Dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e)),
        "wi": dense_init(ks[1], (e, d, f), in_axis=1),
        "wg": dense_init(ks[2], (e, d, f), in_axis=1),
        "wo": dense_init(ks[3], (e, f, d), in_axis=1),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        km = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": dense_init(km[0], (d, fs)),
            "wg": dense_init(km[1], (d, fs)),
            "wo": dense_init(km[2], (fs, d)),
        }
    return p


def _route_and_dispatch(xf, router_w, e: int, k: int, cap: int):
    """Route xf (n, d) -> dispatch buffer (e, cap, d) + combine metadata.

    Pure function of LOCAL data; called once globally (fallback) or once
    per data shard inside shard_map (distributed path).
    """
    n, d = xf.shape
    logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)   # (n,k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    flat_e = idx.reshape(-1)                                   # (n*k,)
    flat_t = jnp.repeat(jnp.arange(n), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    ar = jnp.arange(n * k)
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")
    pos_in_e = ar - seg_start[se]
    keep = pos_in_e < cap
    slot = jnp.where(keep, se * cap + pos_in_e, e * cap)       # overflow slot

    buf = jnp.zeros((e * cap + 1, d), xf.dtype).at[slot].set(
        xf[st], mode="drop")
    buf = buf[:-1].reshape(e, cap, d)
    return buf, (st, sg, keep, slot)


def _combine(out_e, meta, n: int, cap: int, dtype):
    """Inverse of dispatch: (e, cap, d) expert outputs -> (n, d) tokens."""
    st, sg, keep, slot = meta
    e_cap = out_e.shape[0] * cap
    out_flat = out_e.reshape(e_cap, -1)
    contrib = jnp.where(keep[:, None],
                        out_flat[jnp.clip(slot, 0, e_cap - 1)]
                        * sg[:, None].astype(dtype), 0)
    return jnp.zeros((n, out_flat.shape[-1]), dtype).at[st].add(contrib)


def moe_ffn(params: Dict, x, cfg: ModelConfig):
    """x: (B, S, d) -> (B, S, d)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n = b * s
    cap = int(cfg.capacity_factor * n * k / e)
    cap = max(8, min(cap, n))
    act = activation(cfg.act)
    xf = x.reshape(n, d)

    mesh = hints.mesh()
    bax = hints.batch_axis_names()
    nshard = hints.axis_size("BATCH")
    use_shard_map = (mesh is not None and bax and nshard > 1
                     and n % nshard == 0 and (n // nshard) >= k)

    if use_shard_map:
        cap_loc = max(8, cap // nshard)
        n_loc = n // nshard

        def dispatch_shard(xf_l, rw):
            buf_l, (st, sg, keep, slot) = _route_and_dispatch(
                xf_l, rw, e, k, cap_loc)
            return buf_l, st, sg, keep, slot

        buf, st, sg, keep, slot = jax.shard_map(
            dispatch_shard, mesh=mesh,
            in_specs=(P(bax), P()),
            out_specs=(P(None, bax), P(bax), P(bax), P(bax), P(bax)),
        )(xf, params["router"])
        # buf: logical (e, nshard*cap_loc, d), capacity data-sharded.
        # Re-shard the expert axis onto 'model' => XLA's all-to-all, the
        # irreducible expert-parallel traffic.
        ep = e % hints.axis_size("MODEL") == 0
        e_ax = "MODEL" if ep else None
        c_ax = "BATCH"
        f_ax = None if ep else "MODEL"
        buf = hints.constrain(buf, (e_ax, c_ax, None))
        h = act(jnp.einsum("ecd,edf->ecf", buf, params["wg"].astype(x.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(x.dtype))
        h = hints.constrain(h, (e_ax, c_ax, f_ax))
        out_e = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x.dtype))
        out_e = hints.constrain(out_e, (e_ax, c_ax, None))

        model_ax = hints._AXES["model"]
        msize = hints.axis_size("MODEL")
        if ep and model_ax:
            # Combine WITHOUT replicating the expert axis: each model
            # shard combines its e_loc experts' outputs into a partial
            # (n_loc, d) and psums over 'model' -- wire bytes n_loc*d vs
            # e*cap_loc*d for the all-gather alternative (~9x less at
            # top-6; EXPERIMENTS.md §Perf iter 3).
            e_loc = e // msize
            span = e_loc * cap_loc

            def combine_shard(out_l, st_l, sg_l, keep_l, slot_l):
                m_idx = jax.lax.axis_index(model_ax)
                base = m_idx * span
                mine = keep_l & (slot_l >= base) & (slot_l < base + span)
                out_flat = out_l.reshape(span, d)
                contrib = jnp.where(
                    mine[:, None],
                    out_flat[jnp.clip(slot_l - base, 0, span - 1)]
                    * sg_l[:, None].astype(x.dtype), 0)
                y_l = jnp.zeros((n_loc, d), x.dtype).at[st_l].add(contrib)
                return jax.lax.psum(y_l, model_ax)

            y = jax.shard_map(
                combine_shard, mesh=mesh,
                in_specs=(P(model_ax, bax, None), P(bax), P(bax), P(bax),
                          P(bax)),
                out_specs=P(bax),
            )(out_e, st, sg, keep, slot)
        else:
            def combine_shard(out_l, st_l, sg_l, keep_l, slot_l):
                return _combine(out_l, (st_l, sg_l, keep_l, slot_l), n_loc,
                                cap_loc, x.dtype)

            y = jax.shard_map(
                combine_shard, mesh=mesh,
                in_specs=(P(None, bax), P(bax), P(bax), P(bax), P(bax)),
                out_specs=P(bax),
            )(out_e, st, sg, keep, slot)
        y = hints.constrain(y, ("BATCH", None))
    else:
        buf, meta = _route_and_dispatch(xf, params["router"], e, k, cap)
        # expert-parallel layout: E over 'model' when divisible, else
        # capacity over batch axes + FFN hidden over 'model' (TP experts)
        ep = e % hints.axis_size("MODEL") == 0
        e_ax = "MODEL" if ep else None
        c_ax = None if ep else "BATCH"
        f_ax = None if ep else "MODEL"
        buf = hints.constrain(buf, (e_ax, c_ax, None))
        h = act(jnp.einsum("ecd,edf->ecf", buf, params["wg"].astype(x.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(x.dtype))
        h = hints.constrain(h, (e_ax, c_ax, f_ax))
        out_e = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x.dtype))
        out_e = hints.constrain(out_e, (e_ax, c_ax, None))
        y = _combine(out_e, meta, n, cap, x.dtype)
        y = hints.constrain(y, ("BATCH", None))

    if cfg.n_shared_experts:
        sh = params["shared"]
        hs = act(xf @ sh["wg"].astype(x.dtype)) * (xf @ sh["wi"].astype(x.dtype))
        y = y + hs @ sh["wo"].astype(x.dtype)
    return y.reshape(b, s, d)
