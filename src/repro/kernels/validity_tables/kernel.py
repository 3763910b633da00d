"""Pallas TPU kernel: zkReLU validity-table construction.

The validity argument's hot path turns the stacked aux tensors into the
two vectors of the combined inner-product relation (eq. 19):

    a = B_k - z 1                       (B_k = B + k \\bar{B}_{Q-1})
    b = z^2 (e_relu (x) s) + (z 1 + B'_k) . (e_relu (x) e_bit)

The former host path decomposed bits in a Python loop and pushed the
matrices through object-dtype ``encode_ints`` -- a per-element CPU walk
over 2 Ds (Q + R) positions.  Here the bit decomposition IS the kernel:
each lane owns one (row, bit) position, reads its packed source value
and bit index from uint32 planes, shifts/masks the bit out and assembles
BOTH tables in a single dispatch.  The main and remainder statements
ride the same grid, distinguished per-lane by a region mask that selects
between the two z challenges.

Because every bit value, the forced B_{Q-1} column and the two masks are
0/1 integers, the field encode is a masked select of pre-encoded scalar
tiles (``ONE``, ``k``) -- no Montgomery multiply is needed to lift the
bits, only to apply ``(z - (-B'_k)) * e`` on the b side.  Scalars arrive
as (4, 1, 128) broadcast limb tiles like `sumcheck_fold`, so the same
body runs in interpret mode off-TPU.
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


def _tables_body(vals_ref, shift_ref, kmask_ref, kpmask_ref, colmask_ref,
                 region_ref, efull_ref, es_ref, one_ref, k_ref, zm_ref,
                 zr_ref, a_ref, b_ref, *, spec: FieldSpec):
    """One block of (row, bit) positions -> (a, b) table planes.

    Per position p:  bit = (vals >> shift) & 1,
      a = [bit] + kmask * k - z_sel
      b = es + (z_sel - ([(1-bit)(1-colmask)] + kpmask * k)) * e_full
    where [x] selects the Montgomery ONE tile when the 0/1 integer x is
    set, ``es`` arrives pre-scaled by z^2 (and kron'd with s), and z_sel
    picks the main/remainder challenge by the region mask.
    """
    bit = (vals_ref[...] >> shift_ref[...]) & jnp.uint32(1)
    km = kmask_ref[...]
    kpm = kpmask_ref[...]
    colm = colmask_ref[...]
    reg = region_ref[...].astype(bool)

    one_t = [one_ref[j] for j in range(NLIMB)]
    k_t = [k_ref[j] for j in range(NLIMB)]

    def sel(mask01, tile):
        m = mask01.astype(bool)
        return [jnp.where(m, t, jnp.uint32(0)) for t in tile]

    # z_sel: the statement's own z challenge, chosen per lane
    zsel = [jnp.where(reg, zm_ref[j], zr_ref[j]) for j in range(NLIMB)]

    # a = B_k - z 1  (bit + k on the forced column, minus z everywhere)
    a = add_planes(spec, sel(bit, one_t), sel(km, k_t))
    a = sub_planes(spec, a, zsel)

    # -B'_k = (1 - bit) off the forced column, + k (1 - B_{Q-1}) on it
    negbp = add_planes(spec, sel((1 - bit) * (1 - colm), one_t),
                       sel(kpm, k_t))
    e_full = [efull_ref[j] for j in range(NLIMB)]
    es = [es_ref[j] for j in range(NLIMB)]
    b = add_planes(spec, es,
                   mont_mul_planes(spec, sub_planes(spec, zsel, negbp),
                                   e_full))
    for j in range(NLIMB):
        a_ref[j] = a[j]
        b_ref[j] = b[j]


@functools.partial(jax.jit,
                   static_argnames=("spec", "block_rows", "interpret"))
def validity_tables_planes(vals, shift, kmask, kpmask, colmask, region,
                           efull_planes, es_planes, one_tile, k_tile,
                           zm_tile, zr_tile, *, spec: FieldSpec,
                           block_rows: int = DEFAULT_BLOCK_ROWS,
                           interpret: bool):
    """(R,128) uint32 position planes + (4,R,128) field planes +
    (4,1,128) scalar tiles -> ((4,R,128) a, (4,R,128) b)."""
    rows, lane = vals.shape
    assert lane == LANE
    for m in (shift, kmask, kpmask, colmask, region):
        assert m.shape == vals.shape
    assert efull_planes.shape == (NLIMB, rows, LANE)
    assert es_planes.shape == (NLIMB, rows, LANE)
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    mblk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    blk = pl.BlockSpec((NLIMB, br, LANE), lambda i: (0, i, 0))
    cblk = pl.BlockSpec((NLIMB, 1, LANE), lambda i: (0, 0, 0))
    out = jax.ShapeDtypeStruct((NLIMB, rows, LANE), jnp.uint32)
    return pl.pallas_call(
        functools.partial(_tables_body, spec=spec),
        grid=(rows // br,),
        in_specs=[mblk, mblk, mblk, mblk, mblk, mblk, blk, blk,
                  cblk, cblk, cblk, cblk],
        out_specs=(blk, blk),
        out_shape=(out, out),
        interpret=interpret,
    )(vals, shift, kmask, kpmask, colmask, region, efull_planes, es_planes,
      one_tile, k_tile, zm_tile, zr_tile)
