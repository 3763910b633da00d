"""Pallas TPU kernel: fused sumcheck MLE fold.

One sumcheck round replaces the table T (n elements) by

    T'[i] = T[2i] + (T[2i+1] - T[2i]) * r        (fix variable 0 at r)

The unfused jnp path (`repro.core.mle.fold`) materializes `diff = odd -
even` and `diff * r` separately: ~3 reads + 3 writes of n/2 elements each
(9n/2 element-moves of HBM traffic).  This kernel streams even/odd tiles
through VMEM once and writes the folded tile: 2 reads + 1 write (3n/2
moves), a 3x reduction on the dominant memory term of the proving loop --
the fold is memory-bound (the CIOS multiply is ~152 lane-ops per 48 B,
but sub+mul+add per element is cheap next to the HBM round-trips the
unfused form makes).

The scalar ``r`` is passed as a (4, 1, 128) broadcast tile (each lane of
plane j holds limb j of r) so the kernel needs no scalar-prefetch plumbing
and the same body runs in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.field.modarith import NLIMB, FieldSpec
from repro.kernels.limb_planes import (LANE, add_planes, mont_mul_planes,
                                       sub_planes)

DEFAULT_BLOCK_ROWS = 256


def _fold_body(even_ref, odd_ref, r_ref, o_ref, *, spec: FieldSpec):
    ev = [even_ref[j] for j in range(NLIMB)]
    od = [odd_ref[j] for j in range(NLIMB)]
    rl = [r_ref[j] for j in range(NLIMB)]          # (1, 128), broadcasts
    diff = sub_planes(spec, od, ev)
    out = add_planes(spec, ev, mont_mul_planes(spec, diff, rl))
    for j in range(NLIMB):
        o_ref[j] = out[j]


def _fold_halves_body(lo_ref, hi_ref, clo_ref, chi_ref, o_ref, *,
                      spec: FieldSpec):
    """out = c_lo * lo + c_hi * hi — the IPA halves fold (top-variable
    fold with two independent coefficients, unlike the sumcheck fold's
    even + (odd - even) * r form)."""
    lo = [lo_ref[j] for j in range(NLIMB)]
    hi = [hi_ref[j] for j in range(NLIMB)]
    clo = [clo_ref[j] for j in range(NLIMB)]
    chi = [chi_ref[j] for j in range(NLIMB)]
    out = add_planes(spec, mont_mul_planes(spec, lo, clo),
                     mont_mul_planes(spec, hi, chi))
    for j in range(NLIMB):
        o_ref[j] = out[j]


@functools.partial(jax.jit,
                   static_argnames=("spec", "block_rows", "interpret"))
def fold_halves_planes(lo_planes, hi_planes, clo_tile, chi_tile, *,
                       spec: FieldSpec,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       interpret: bool):
    """(4,R,128) lo/hi planes + (4,1,128) coefficient tiles -> folded."""
    nl, rows, lane = lo_planes.shape
    assert nl == NLIMB and lane == LANE
    assert hi_planes.shape == lo_planes.shape
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    blk = pl.BlockSpec((NLIMB, br, LANE), lambda i: (0, i, 0))
    cblk = pl.BlockSpec((NLIMB, 1, LANE), lambda i: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_fold_halves_body, spec=spec),
        grid=(rows // br,),
        in_specs=[blk, blk, cblk, cblk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(lo_planes.shape, jnp.uint32),
        interpret=interpret,
    )(lo_planes, hi_planes, clo_tile, chi_tile)


def _pow_mul_body(lo_ref, hi_ref, elo_ref, ehi_ref, o_ref, *,
                  spec: FieldSpec, nbits: int):
    """out = lo^{e_lo} * hi^{e_hi} — the IPA generator fold, fused.

    Square-and-multiply over the shared scalar exponents as a rolled
    ``fori_loop`` (one squaring + one conditional multiply per half per
    bit); the exponents arrive as (4, 1, 128) broadcast limb tiles in
    STANDARD (non-Montgomery) form, and bit j selects its limb with a
    where-chain so the body needs no dynamic ref indexing."""
    lo = [lo_ref[j] for j in range(NLIMB)]
    hi = [hi_ref[j] for j in range(NLIMB)]
    elo = [elo_ref[j] for j in range(NLIMB)]
    ehi = [ehi_ref[j] for j in range(NLIMB)]
    ones = [jnp.full_like(lo[j], jnp.uint32(spec.one[j]))
            for j in range(NLIMB)]

    def bit_at(e, j):
        limb_i, sh = j >> jnp.uint32(4), j & jnp.uint32(15)
        limb = e[NLIMB - 1]
        for k in range(NLIMB - 2, -1, -1):
            limb = jnp.where(limb_i == k, e[k], limb)
        return (((limb >> sh) & 1) != 0)

    def step(i, carry):
        res_lo, acc_lo, res_hi, acc_hi = carry
        j = jnp.uint32(i)
        b_lo, b_hi = bit_at(elo, j), bit_at(ehi, j)
        mul_lo = mont_mul_planes(spec, res_lo, acc_lo)
        mul_hi = mont_mul_planes(spec, res_hi, acc_hi)
        res_lo = [jnp.where(b_lo, mul_lo[k], res_lo[k])
                  for k in range(NLIMB)]
        res_hi = [jnp.where(b_hi, mul_hi[k], res_hi[k])
                  for k in range(NLIMB)]
        acc_lo = mont_mul_planes(spec, acc_lo, acc_lo)
        acc_hi = mont_mul_planes(spec, acc_hi, acc_hi)
        return res_lo, acc_lo, res_hi, acc_hi

    res_lo, _, res_hi, _ = jax.lax.fori_loop(
        0, nbits, step, (ones, lo, list(ones), hi))
    out = mont_mul_planes(spec, res_lo, res_hi)
    for j in range(NLIMB):
        o_ref[j] = out[j]


@functools.partial(jax.jit,
                   static_argnames=("spec", "nbits", "block_rows",
                                    "interpret"))
def pow_mul_planes(lo_planes, hi_planes, elo_tile, ehi_tile, *,
                   spec: FieldSpec, nbits: int = 61,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool):
    """(4,R,128) lo/hi group-element planes + (4,1,128) standard-form
    exponent tiles -> (4,R,128) lo^{e_lo} * hi^{e_hi}."""
    nl, rows, lane = lo_planes.shape
    assert nl == NLIMB and lane == LANE
    assert hi_planes.shape == lo_planes.shape
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    blk = pl.BlockSpec((NLIMB, br, LANE), lambda i: (0, i, 0))
    eblk = pl.BlockSpec((NLIMB, 1, LANE), lambda i: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_pow_mul_body, spec=spec, nbits=nbits),
        grid=(rows // br,),
        in_specs=[blk, blk, eblk, eblk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(lo_planes.shape, jnp.uint32),
        interpret=interpret,
    )(lo_planes, hi_planes, elo_tile, ehi_tile)


@functools.partial(jax.jit,
                   static_argnames=("spec", "block_rows", "interpret"))
def fold_planes(even_planes, odd_planes, r_tile, *, spec: FieldSpec,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool):
    """(4,R,128) even/odd planes + (4,1,128) r tile -> (4,R,128) folded."""
    nl, rows, lane = even_planes.shape
    assert nl == NLIMB and lane == LANE
    assert odd_planes.shape == even_planes.shape
    assert r_tile.shape == (NLIMB, 1, LANE)
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    grid = (rows // br,)
    blk = pl.BlockSpec((NLIMB, br, LANE), lambda i: (0, i, 0))
    rblk = pl.BlockSpec((NLIMB, 1, LANE), lambda i: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_fold_body, spec=spec),
        grid=grid,
        in_specs=[blk, blk, rblk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(even_planes.shape, jnp.uint32),
        interpret=interpret,
    )(even_planes, odd_planes, r_tile)
