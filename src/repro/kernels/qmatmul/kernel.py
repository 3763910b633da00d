"""Pallas TPU kernel: exact int16 matmul as four int8 MXU passes.

zkDL's quantized training step (Example 4.5) is built on *exact* integer
matmuls: Z = A @ W with A, W holding Q-bit (Q<=16) signed fixed-point
values and products accumulated without rounding (the witness relations
(30)/(33)/(34) must hold bit-exactly or the proof fails).  GPUs do this
with dp4a/int64 units; the TPU MXU multiplies int8 x int8 -> int32, so the
TPU-native scheme decomposes each int16 operand into two int8 digits and
recombines four MXU passes.

Digit split (both digits genuinely int8):

    x = 256 * x_hi + x_lo,  x_lo in [0,256)         (x_hi = x >> 8)
    x_lo = x_c + 128,       x_c  in [-128,128)      (x_c = x_lo - 128)

so with J the all-ones matrix:

    A @ B = 2^16 (Ah@Bh) + 2^8 (Ah@Bc + Ac@Bh) + (Ac@Bc)
          + 2^15 rowsum(Ah) + 2^7 rowsum(Ac)            [broadcast col]
          + 2^15 colsum(Bh) + 2^7 colsum(Bc)            [broadcast row]
          + 2^14 * K

The kernel computes the four int8 MXU products (exact int32 accumulation:
|prod| <= 2^14, so K <= 2^17 cannot overflow int32); the rank-1
corrections and the power-of-two recombination are cheap vector work done
in the wrapper (`ops.py`), where the final value is assembled at int64 --
on host for witness generation, or kept as digit planes on device.

Grid is (M/BM, N/BN, K/BK) with K innermost; all four accumulators live
in VMEM for the whole K loop.  VMEM at (BM,BN,BK)=(256,256,512):
    A tiles 2*256*512 B = 0.25 MiB, B tiles 0.25 MiB,
    4 int32 accumulators 4*256*256*4 B = 1.0 MiB      -- comfortably VMEM.
MXU utilization: operands are int8 so the 128x128 MXU runs at rate; the
4x pass count is the exactness price (vs. 1 bf16 pass that would round).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _qmatmul_body(ah_ref, ac_ref, bh_ref, bc_ref,
                  hh_ref, hc_ref, ch_ref, cc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        hh_ref[...] = jnp.zeros_like(hh_ref)
        hc_ref[...] = jnp.zeros_like(hc_ref)
        ch_ref[...] = jnp.zeros_like(ch_ref)
        cc_ref[...] = jnp.zeros_like(cc_ref)

    ah = ah_ref[...]
    ac = ac_ref[...]
    bh = bh_ref[...]
    bc = bc_ref[...]
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.int32)
    hh_ref[...] += dot(ah, bh)
    hc_ref[...] += dot(ah, bc)
    ch_ref[...] += dot(ac, bh)
    cc_ref[...] += dot(ac, bc)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def qmatmul_digits(a_hi, a_c, b_hi, b_c, *,
                   bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                   bk: int = DEFAULT_BK, interpret: bool):
    """Four int8 digit matrices -> four exact int32 product matrices.

    a_hi/a_c: (M, K) int8;  b_hi/b_c: (K, N) int8.
    Returns (hh, hc, ch, cc), each (M, N) int32.
    """
    m, kdim = a_hi.shape
    _, n = b_hi.shape
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (m, n, kdim)
    grid = (m // bm, n // bn, kdim // bk)
    a_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    b_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    o_shape = jax.ShapeDtypeStruct((m, n), jnp.int32)
    return pl.pallas_call(
        _qmatmul_body,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=(o_spec, o_spec, o_spec, o_spec),
        out_shape=(o_shape, o_shape, o_shape, o_shape),
        interpret=interpret,
    )(a_hi, a_c, b_hi, b_c)
