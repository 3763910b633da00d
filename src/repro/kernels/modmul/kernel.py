"""Pallas TPU kernel: elementwise Montgomery modular multiply.

This is the inner loop of every proof-side hot spot (MSM bucket products,
sumcheck round evaluation, MLE folds).  One grid step loads a
``(4, BLOCK_ROWS, 128)`` tile of each operand into VMEM, runs the fully
unrolled 16-bit-limb CIOS sequence in int32 VPU lanes, and writes the
canonical product tile.

VMEM budget per step (uint32, BLOCK_ROWS=512):
    2 operands + 1 output tile : 3 * 4 * 512 * 128 * 4 B = 3.0 MiB
    CIOS temporaries (~10 planes): 10 * 512 * 128 * 4 B  = 2.5 MiB
well under the ~16 MiB/core VMEM of TPU v5e.  The multiply is
compute-bound at ~152 int32 lane-ops per element per operand-pair
(arithmetic intensity ~= 152 ops / 48 B ~ 3.2 op/B), so larger tiles only
need to cover DMA latency, not bandwidth.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.field.modarith import NLIMB, FieldSpec
from repro.kernels.limb_planes import LANE, mont_mul_planes

DEFAULT_BLOCK_ROWS = 512


def _modmul_body(a_ref, b_ref, o_ref, *, spec: FieldSpec):
    al = [a_ref[j] for j in range(NLIMB)]
    bl = [b_ref[j] for j in range(NLIMB)]
    ol = mont_mul_planes(spec, al, bl)
    for j in range(NLIMB):
        o_ref[j] = ol[j]


@functools.partial(jax.jit,
                   static_argnames=("spec", "block_rows", "interpret"))
def modmul_planes(a_planes, b_planes, *, spec: FieldSpec,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool):
    """(4, R, 128) x (4, R, 128) -> (4, R, 128) Montgomery product."""
    nl, rows, lane = a_planes.shape
    assert nl == NLIMB and lane == LANE and b_planes.shape == a_planes.shape
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    grid = (rows // br,)
    blk = pl.BlockSpec((NLIMB, br, LANE), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_modmul_body, spec=spec),
        grid=grid,
        in_specs=[blk, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(a_planes.shape, a_planes.dtype),
        interpret=interpret,
    )(a_planes, b_planes)
