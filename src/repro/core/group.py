"""The Pedersen commitment group and multi-scalar multiplication.

The group is the order-q subgroup of quadratic residues of F_p^*, with
p = 2q + 1 a Sophie-Germain pair (q is the proof field FQ).  A group
element is an FP limb array in Montgomery form; the group operation is
``mont_mul(FP, ., .)`` and exponents live in FQ.

TPU adaptation note (DESIGN.md): zkDL's CUDA prover leans on atomic bucket
accumulation for Pippenger MSM.  Atomics do not exist on the TPU vector
unit, so the MSM here is re-expressed as sort -> segmented associative
scan -> scatter of segment tails, which XLA maps onto parallel hardware
(and mirrors how production TPU kernels express histogram-like reductions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import execache
from repro.field import (
    FP, FQ, GROUP_GEN, mont_mul, from_mont, encode_ints, int_to_limbs,
    limbs_to_ints, hash_to_int, pow_const,
)

P = FP.modulus
Q = FQ.modulus

WINDOW = 8            # legacy fixed window (still the large-n optimum)
NBUCKET = 1 << WINDOW


@functools.lru_cache(maxsize=None)
def best_window(n: int, nbits: int = 61) -> int:
    """Pippenger window adapted to vector length.

    Each window pass costs O(n) sort/scan work plus a 2^w-bucket
    aggregation and w squarings; small n with the fixed WINDOW=8 paid
    the full 256-bucket scatter for a handful of points.  Minimizing
    ceil(nbits/w) * (n + 2^w + w) over w picks ~log2(n), matching the
    classic analysis; the IPA's halving fold lengths are exactly the
    small-n callers that win.
    """
    best_cost, best_w = None, WINDOW
    for w in (2, 4, 8):        # divisors of 16: digits never straddle limbs
        nwin = -(-nbits // w)
        cost = nwin * (n + (1 << w) + w)
        if best_cost is None or cost < best_cost:
            best_cost, best_w = cost, w
    return best_w


def identity():
    return jnp.asarray(np.array(FP.one))


def g_mul(a, b):
    """Group operation."""
    return mont_mul(FP, a, b)


def g_inv(a):
    """Group inverse (p is prime, so a^{p-2})."""
    return pow_const(FP, a, P - 2)


def g_pow_int(base, e: int):
    """base^e for python-int exponent (e taken mod q).

    Routed through the jitted vectorized ``g_pow`` so repeated calls with
    different exponents reuse one compiled executable.
    """
    e = int(e) % Q
    exps = jnp.asarray(int_to_limbs(e))[None]
    return g_pow(base[None], exps)[0]


def g_pow(bases, exps_std, nbits: int = 61):
    """Elementwise bases^exps. exps in standard (non-Montgomery) limb form.

    Square-and-multiply as a lax.scan over the bit index so the compiled
    body is one mont_mul pair (XLA-CPU chokes on a 61x unrolled graph).
    """
    result = jnp.broadcast_to(identity(), bases.shape).astype(jnp.uint32)

    def step(carry, j):
        res, acc = carry
        limb = jnp.take(exps_std, j >> 4, axis=-1)
        bit = ((limb >> (j & 15)) & 1).astype(bool)
        res = jnp.where(bit[..., None], g_mul(res, acc), res)
        acc = g_mul(acc, acc)
        return (res, acc), None

    (result, _), _ = jax.lax.scan(step, (result, bases), jnp.arange(nbits, dtype=jnp.uint32))
    return result


g_pow = execache.wrap("g_pow", g_pow, static_argnames=("nbits",))


def _seg_combine(x, y):
    v1, f1 = x
    v2, f2 = y
    v = jnp.where(f2[..., None].astype(bool), v2, g_mul(v1, v2))
    return v, f1 | f2


def _seg_products(sp, starts, chunk: int = 32):
    """Inclusive segmented running product of (n,4) group elements with
    segment-start flags, used for the per-bucket products of the sorted
    Pippenger digits.

    For large n a flat `associative_scan` does O(n log n) group muls;
    instead the array is cut into `chunk`-length pieces: a sequential
    scan WITHIN chunks (vectorized across chunks, O(n) muls), one tiny
    associative scan over the per-chunk open-segment tails, then one
    vectorized carry-in multiply for elements before their chunk's
    first segment start.  Pure reassociation of the same products, so
    every output element is bit-identical to the flat scan."""
    n = sp.shape[0]
    one = identity()
    if n < 4 * chunk or n % chunk:
        vals, _ = jax.lax.associative_scan(_seg_combine, (sp, starts))
        return vals
    c = n // chunk
    p2 = sp.reshape(c, chunk, 4)
    f2 = starts.reshape(c, chunk)

    def step(carry, xs):
        nv, nf = _seg_combine(carry, xs)
        return (nv, nf), nv

    init = (jnp.broadcast_to(one, (c, 4)).astype(jnp.uint32),
            jnp.zeros((c,), jnp.uint32))
    (tail_v, _), vals_seq = jax.lax.scan(
        step, init, (p2.transpose(1, 0, 2), f2.T))
    vals2 = vals_seq.transpose(1, 0, 2)               # (c, chunk, 4)
    has_start = (f2.max(axis=1) > 0).astype(jnp.uint32)
    s_v, _ = jax.lax.associative_scan(_seg_combine, (tail_v, has_start))
    carry_in = jnp.concatenate(
        [jnp.broadcast_to(one, (1, 4)).astype(jnp.uint32), s_v[:-1]])
    seen = jnp.cumsum(f2, axis=1) > 0                 # start at index <= l
    fixed = jnp.where(
        seen[..., None], vals2,
        g_mul(jnp.broadcast_to(carry_in[:, None], (c, chunk, 4)), vals2))
    return fixed.reshape(n, 4)


def _msm_core(points, exps_std, nwin: int, window: int = WINDOW):
    """Pippenger MSM body; windows processed high->low inside one lax.scan
    so the compiled program contains a single window body.  ``window`` is a
    static length-adapted digit width (see `best_window`).  Pure traced
    code (no jit wrapper) so `_msm_impl` can inline it and `_msm_many_impl`
    can vmap it over a batch of independent MSMs."""
    one = identity()
    nbucket = 1 << window

    def window_body(total, w):
        bitpos = jnp.uint32(window) * w
        limb = jnp.take(exps_std, bitpos >> 4, axis=1)
        shift = bitpos & 15
        digit = (limb >> shift) & (nbucket - 1)
        if 16 % window != 0:
            # a digit may straddle the 16-bit limb boundary; the top
            # window may also run past the last limb (high bits = 0)
            nxt_idx = (bitpos >> 4) + 1
            nxt = jnp.take(exps_std, jnp.minimum(nxt_idx, 3), axis=1)
            nxt = jnp.where(nxt_idx > 3, jnp.uint32(0), nxt)
            digit = jnp.where(
                shift + window > 16,
                (digit | (nxt << (16 - shift))) & (nbucket - 1), digit)
        pts = jnp.where((digit == 0)[:, None], one[None], points)
        if points.shape[0] <= (1 << 16):
            # pack digit (< 2^window <= 2^8) and element index into one
            # uint32 key: a single flat sort + one gather replaces
            # argsort + two gathers (~4x cheaper per window on XLA-CPU,
            # and the sort runs once per window).  Equal digits keep
            # index order, but any order would do: bucket products
            # commute, so the reduction is exact either way.
            idx = jnp.arange(points.shape[0], dtype=jnp.uint32)
            skey = jnp.sort((digit << 16) | idx)
            order = skey & jnp.uint32(0xFFFF)
            sd = skey >> 16
        else:
            order = jnp.argsort(digit)
            sd = digit[order]
        sp = pts[order]
        starts = jnp.concatenate([jnp.ones((1,), jnp.uint32),
                                  (sd[1:] != sd[:-1]).astype(jnp.uint32)])
        vals = _seg_products(sp, starts)
        is_end = jnp.concatenate([(sd[1:] != sd[:-1]), jnp.ones((1,), bool)])
        idx = jnp.where(is_end, sd, jnp.uint32(nbucket))
        buckets = jnp.broadcast_to(one, (nbucket + 1, 4)).astype(jnp.uint32)
        buckets = buckets.at[idx].set(vals, mode="drop")

        # sum_j j * bucket_j via double running product, j = nbucket-1 .. 1
        def agg(carry, b):
            running, acc = carry
            running = g_mul(running, b)
            acc = g_mul(acc, running)
            return (running, acc), None

        rev = buckets[1:nbucket][::-1]
        (_, win_acc), _ = jax.lax.scan(agg, (one, one), rev)

        # total = total^(2^window) * win_acc
        def sq(t, _):
            return g_mul(t, t), None

        total, _ = jax.lax.scan(sq, total, None, length=window)
        total = g_mul(total, win_acc)
        return total, None

    ws = jnp.arange(nwin - 1, -1, -1, dtype=jnp.uint32)
    total, _ = jax.lax.scan(window_body, jnp.broadcast_to(one, (4,)).astype(jnp.uint32), ws)
    return total


def _msm_impl(points, exps_std, nwin: int, window: int = WINDOW):
    return _msm_core(points, exps_std, nwin, window)


_msm_impl = execache.wrap("msm", _msm_impl,
                          static_argnames=("nwin", "window"))


def _msm_many_impl(points, exps_std, nwin: int, window: int):
    """R independent MSMs over a shared window schedule, ONE executable.

    ``points``/``exps_std`` are (R, n, 4); the sort -> segmented-scan ->
    scatter Pippenger body is vmapped over the leading batch axis, so all
    R reductions run inside a single XLA program instead of R dispatches."""
    return jax.vmap(lambda p, e: _msm_core(p, e, nwin, window))(
        points, exps_std)


_msm_many_impl = execache.wrap("msm_many", _msm_many_impl,
                               static_argnames=("nwin", "window"))


def _pad4(n: int) -> int:
    """Next power of four >= n (fewer distinct compiled MSM shapes)."""
    m = 1
    while m < n:
        m *= 4
    return m


def msm(points, exps_std, nbits: int = 61, window: int | None = None):
    """prod_i points[i]^exps[i]; exps as (n,4) standard-form limbs.

    Power-of-two lengths run as-is; anything else pads to a power-of-four
    length with zero exponents so odd sizes reuse a handful of compiled
    executables (a pow-4 pad of an exact pow-2 input would DOUBLE the
    reduction width, and the committed tensors are all powers of two).
    The Pippenger window adapts to the (padded) length via `best_window`
    unless pinned explicitly (benchmarks compare against window=8).
    """
    n = points.shape[0]
    assert n == exps_std.shape[0]
    m = n if n & (n - 1) == 0 else _pad4(n)
    if m != n:
        points = jnp.concatenate(
            [points, jnp.broadcast_to(identity(), (m - n, 4)).astype(jnp.uint32)])
        exps_std = jnp.concatenate(
            [exps_std, jnp.zeros((m - n, 4), jnp.uint32)])
    if window is None:
        window = best_window(m, nbits)
    nwin = (nbits + window - 1) // window
    return _msm_impl(points, exps_std, nwin=nwin, window=window)


def msm_many(points, exps_std, nbits: int = 61, window: int | None = None):
    """R independent MSMs sharing one window schedule: (R, n, 4) points
    and standard-form exponents -> (R, 4) group elements.

    ``points`` may also be a single (n, 4) generator vector shared by all
    rows (the Pedersen commit-many case); it is broadcast across R.  Rows
    are padded with zero exponents to a power of TWO (the fused IPA
    rounds feed exact powers of two; `msm`'s power-of-four pad would
    double their sort width), so each row equals the sequential
    ``msm(points[r], exps[r])`` bit-for-bit while the whole batch costs
    ONE dispatch."""
    exps_std = jnp.asarray(exps_std)
    assert exps_std.ndim == 3
    r, n = exps_std.shape[0], exps_std.shape[1]
    points = jnp.asarray(points)
    if points.ndim == 2:
        points = jnp.broadcast_to(points[None], (r, n, 4))
    assert points.shape == (r, n, 4), (points.shape, exps_std.shape)
    m = max(2, 1 << (n - 1).bit_length())
    if m != n:
        points = jnp.concatenate(
            [points, jnp.broadcast_to(identity(), (r, m - n, 4)).astype(jnp.uint32)],
            axis=1)
        exps_std = jnp.concatenate(
            [exps_std, jnp.zeros((r, m - n, 4), jnp.uint32)], axis=1)
    if window is None:
        window = best_window(m, nbits)
    nwin = (nbits + window - 1) // window
    return _msm_many_impl(points, exps_std, nwin=nwin, window=window)


def msm_field(points, scalars_mont, nbits: int = 61):
    """MSM where scalars are FQ elements in Montgomery form."""
    return msm(points, from_mont(FQ, scalars_mont), nbits)


def pow_table(bases, nbits: int = 61):
    """Precomputed squaring chains: (n,4) bases -> (nbits,n,4) with
    table[j] = bases^{2^j}.  For a FIXED basis (commitment generators),
    building this once at key setup halves every later exponentiation:
    `g_pow_table` needs only the conditional multiplies, no runtime
    squarings."""
    def step(acc, _):
        return g_mul(acc, acc), acc
    _, tab = jax.lax.scan(step, bases, None, length=nbits)
    return tab


pow_table = execache.wrap("pow_table", pow_table,
                          static_argnames=("nbits",))


def g_pow_table(table, exps_std, nbits: int = 61):
    """Elementwise bases^exps via a `pow_table`: one conditional multiply
    per bit (half the work of `g_pow`'s square-and-multiply).  Exponents
    in standard limb form; bit-identical to `g_pow` on the same bases."""
    result = jnp.broadcast_to(identity(),
                              table.shape[1:]).astype(jnp.uint32)

    def step(res, xs):
        j, tab_j = xs
        limb = jnp.take(exps_std, j >> 4, axis=-1)
        bit = ((limb >> (j & 15)) & 1).astype(bool)
        return jnp.where(bit[..., None], g_mul(res, tab_j), res), None

    result, _ = jax.lax.scan(
        step, result, (jnp.arange(nbits, dtype=jnp.uint32), table))
    return result


g_pow_table = execache.wrap("g_pow_table", g_pow_table,
                            static_argnames=("nbits",))


def tree_prod(elems):
    """Product of all group elements in (n,4)."""
    one = identity()
    while elems.shape[0] > 1:
        if elems.shape[0] % 2 == 1:
            elems = jnp.concatenate([elems, one[None]], axis=0)
        elems = g_mul(elems[0::2], elems[1::2])
    return elems[0]


tree_prod = execache.wrap("tree_prod", tree_prod)


def msm_bits(points, bits):
    """prod points[i]^{bits[i]} for a 0/1 vector: pure selection product."""
    bits = jnp.asarray(bits).astype(bool)
    n = bits.shape[0]
    m = _pad4(n)
    sel = jnp.where(bits[:, None], points[:n], identity()[None])
    if m != n:
        sel = jnp.concatenate(
            [sel, jnp.broadcast_to(identity(), (m - n, 4)).astype(jnp.uint32)])
    return tree_prod(sel)


# ---------------------------------------------------------------------------
# Generators (nothing-up-my-sleeve, unknown discrete logs).
# ---------------------------------------------------------------------------

_GEN_CACHE: dict = {}


def derive_generators(label: bytes, n: int):
    """n independent subgroup generators; hash-to-group (t -> t^2 mod p).

    The per-generator hash is inherently sequential (one SHA-256 each),
    but the square / Montgomery-lift / limb-packing all run as batched
    numpy object-array ops instead of a per-generator Python loop."""
    cached = _GEN_CACHE.get(label)
    if cached is not None and cached.shape[0] >= n:
        return jnp.asarray(cached[:n])
    ts = np.array([max(hash_to_int(label + i.to_bytes(8, "little"), P), 2)
                   for i in range(n)], dtype=object)
    gm = (ts * ts % P) * pow(2, 64, P) % P   # square -> QR, then Montgomery
    out = ints_to_limbs_np(gm)
    _GEN_CACHE[label] = out
    return jnp.asarray(out)


def group_gen():
    """The canonical subgroup generator h=4 in Montgomery form."""
    g = (GROUP_GEN * pow(2, 64, P)) % P
    return jnp.asarray(int_to_limbs(g))


def decode_group(a) -> int:
    """Group element -> canonical python int (for transcripts/serialization)."""
    from repro.core.pipeline import profile   # the pipeline imports us
    profile.host_read()
    std = np.asarray(from_mont(FP, jnp.asarray(a)))
    return int(limbs_to_ints(std)[()])


def decode_group_many(a) -> list:
    """(R, 4) group elements -> list of R python ints, ONE host transfer."""
    from repro.core.pipeline import profile   # the pipeline imports us
    profile.host_read()
    std = np.asarray(from_mont(FP, jnp.asarray(a)))
    return [int(v) for v in limbs_to_ints(std)]


def encode_group(x: int):
    gm = (x % P) * pow(2, 64, P) % P
    return jnp.asarray(int_to_limbs(gm))


def exps_from_ints(vals) -> jnp.ndarray:
    """Python ints (mod q) -> standard-form limb array for msm/g_pow.

    Values already reduced into int64 range (the common case: transcript
    challenges and fold coefficients are canonical field elements) skip
    the mod; everything routes through the field's vectorized
    `ints_to_limbs` packer."""
    arr = np.asarray(list(vals), dtype=object)
    try:
        a64 = arr.astype(np.int64)
        if (a64 >= 0).all() and (a64 < Q).all():
            return jnp.asarray(ints_to_limbs_np(a64))
    except (OverflowError, TypeError):
        pass
    return jnp.asarray(ints_to_limbs_np(arr % Q))


def ints_to_limbs_np(arr: np.ndarray) -> np.ndarray:
    from repro.field import ints_to_limbs
    return ints_to_limbs(arr)
