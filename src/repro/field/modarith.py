"""Prime-field arithmetic in 16-bit-limb form, pure jnp uint32.

This is the TPU-native adaptation layer of zkDL: the reference CUDA
implementation relies on 64-bit integer units; TPUs expose 32-bit integer
lanes only, so every field element is held as four 16-bit limbs packed in a
trailing ``(..., 4)`` uint32 axis and multiplied with CIOS Montgomery
reduction (radix 2^16).  Products of 16-bit limbs and all CIOS accumulators
provably fit in uint32, so the same code runs bit-exactly on CPU (used for
validation here) and inside Pallas TPU kernels.

Two fields are instantiated:

* ``FQ`` -- the proof/scalar field, q = 2^61 - 5283 (prime).  All sumcheck,
  MLE, and quantized-training arithmetic of zkDL lives here (the paper's
  |F| with 2^{Q+R} << |F|).
* ``FP`` -- the group field, p = 2q + 1 (prime, Sophie-Germain pair).  The
  Pedersen commitment group is the order-q subgroup of quadratic residues
  of F_p^*; "group add" is modmul in FP and scalars live in FQ.

Elements are kept in Montgomery form (x * 2^64 mod m) between operations.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

WORD = 16
WMASK = 0xFFFF
NLIMB = 4

U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class FieldSpec:
    """Constants describing one prime field in 16-bit limb Montgomery form."""

    name: str
    modulus: int
    nprime16: int          # -modulus^{-1} mod 2^16
    r1: int                # 2^64 mod modulus  (Montgomery form of 1)
    r2: int                # 2^128 mod modulus (to_mont multiplier)

    @property
    def bits(self) -> int:
        return self.modulus.bit_length()

    @functools.cached_property
    def mod_limbs(self):
        return tuple((self.modulus >> (WORD * i)) & WMASK for i in range(NLIMB))

    @functools.cached_property
    def one(self) -> np.ndarray:
        """Montgomery form of 1, as a (4,) uint32 numpy array."""
        return int_to_limbs(self.r1)

    @functools.cached_property
    def zero(self) -> np.ndarray:
        return np.zeros(NLIMB, dtype=np.uint32)

    @functools.cached_property
    def r2_limbs(self) -> np.ndarray:
        return int_to_limbs(self.r2)


FQ = FieldSpec(
    name="Fq", modulus=2305843009213688669, nprime16=16139,
    r1=42264, r2=1786245696,
)
FP = FieldSpec(
    name="Fp", modulus=4611686018427377339, nprime16=397,
    r1=42260, r2=1785907600,
)
# Generator of the order-q subgroup (quadratic residues) of F_p^*.
GROUP_GEN = 4


# ---------------------------------------------------------------------------
# Host-side converters (numpy / python int <-> limb arrays).
# ---------------------------------------------------------------------------

def int_to_limbs(x: int) -> np.ndarray:
    return np.array([(x >> (WORD * i)) & WMASK for i in range(NLIMB)],
                    dtype=np.uint32)


def ints_to_limbs(xs) -> np.ndarray:
    """Vectorized python-int array -> (..., 4) uint32 limb array.

    Non-negative values below 2^64 (every canonical field element) pack
    via pure-numpy uint64 shifts; arbitrary python ints fall back to
    batched object-array shifts (still no per-element Python loop)."""
    arr = np.asarray(xs, dtype=object)
    flat = arr.reshape(-1)
    out = np.empty(flat.shape + (NLIMB,), dtype=np.uint32)
    try:
        u = flat.astype(np.uint64)
    except (OverflowError, TypeError):
        u = None
    if u is None:
        for j in range(NLIMB):
            out[:, j] = ((flat >> (WORD * j)) & WMASK).astype(np.uint32)
    else:
        for j in range(NLIMB):
            out[:, j] = ((u >> np.uint64(WORD * j))
                         & np.uint64(WMASK)).astype(np.uint32)
    return out.reshape(arr.shape + (NLIMB,))


def limbs_to_ints(limbs) -> np.ndarray:
    """(..., 4) uint32 limb array -> object array of python ints."""
    limbs = np.asarray(limbs)
    flat = limbs.reshape(-1, NLIMB)
    out = np.empty(flat.shape[0], dtype=object)
    for i in range(flat.shape[0]):
        v = 0
        for j in range(NLIMB):
            v |= int(flat[i, j]) << (WORD * j)
        out[i] = v
    return out.reshape(limbs.shape[:-1])


# ---------------------------------------------------------------------------
# Core limb primitives (shape (..., 4) uint32, each limb < 2^16).
# All arithmetic stays inside uint32; see module docstring for bounds.
# ---------------------------------------------------------------------------

def _split(t):
    return t & WMASK, t >> WORD


def _stack_limbs(limbs):
    """The (..., 4) result of one field op.

    On the CPU an optimization barrier keeps XLA from fusing this op into
    the next one: every output limb reads every input limb, and XLA:CPU
    re-derives a fused chain's inputs per limb, so the work grows ~4x
    with each chained op (4 chained mont_muls ran for minutes; the prover
    chains hundreds).  The TPU compiler's cost stays linear in the chain
    length, and a barrier there would only add HBM traffic."""
    out = jnp.stack(limbs, axis=-1)
    if jax.default_backend() == "cpu":
        out = jax.lax.optimization_barrier(out)
    return out


def mont_mul(spec: FieldSpec, a, b):
    """CIOS Montgomery multiplication: returns a*b*2^-64 mod m (canonical).

    jit'd with the field spec static: eager call sites (the prover's
    per-round host loops) pay ONE dispatch instead of ~150 tiny-op
    dispatches; inside other jitted code it inlines as before.
    """
    al = [a[..., j] for j in range(NLIMB)]
    bl = [b[..., j] for j in range(NLIMB)]
    pl = [jnp.uint32(x) for x in spec.mod_limbs]
    npr = jnp.uint32(spec.nprime16)

    zero = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), U32)
    t = [zero] * (NLIMB + 2)
    for i in range(NLIMB):
        # t += a * b[i]
        c = zero
        for j in range(NLIMB):
            acc = t[j] + al[j] * bl[i] + c
            t[j], c = _split(acc)
        acc = t[NLIMB] + c
        t[NLIMB], t[NLIMB + 1] = _split(acc)
        # Montgomery reduction step
        m = (t[0] * npr) & WMASK
        acc = t[0] + m * pl[0]
        _, c = _split(acc)
        for j in range(1, NLIMB):
            acc = t[j] + m * pl[j] + c
            t[j - 1], c = _split(acc)
        acc = t[NLIMB] + c
        t[NLIMB - 1], c = _split(acc)
        t[NLIMB] = t[NLIMB + 1] + c
        t[NLIMB + 1] = zero
    return _cond_sub_mod(spec, t[:NLIMB + 1])


def _cond_sub_mod(spec: FieldSpec, t):
    """t (5 words, value < 2m) -> canonical t mod m as (..., 4) stack."""
    pl = list(spec.mod_limbs) + [0]
    borrow = jnp.zeros_like(t[0])
    u = []
    for j in range(NLIMB + 1):
        d = t[j] - jnp.uint32(pl[j]) - borrow
        u.append(d & WMASK)
        borrow = (d >> 31)  # top bit set iff wrapped below zero
    keep_t = borrow.astype(bool)  # borrow out => t < m
    return _stack_limbs([jnp.where(keep_t, t[j], u[j])
                         for j in range(NLIMB)])


def add(spec: FieldSpec, a, b):
    c = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), U32)
    t = []
    for j in range(NLIMB):
        acc = a[..., j] + b[..., j] + c
        s, c = _split(acc)
        t.append(s)
    t.append(c)
    return _cond_sub_mod(spec, t)


def sub(spec: FieldSpec, a, b):
    pl = spec.mod_limbs
    borrow = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), U32)
    d = []
    for j in range(NLIMB):
        x = a[..., j] - b[..., j] - borrow
        d.append(x & WMASK)
        borrow = x >> 31
    # if borrow: add modulus back
    wrapped = borrow.astype(bool)
    c = jnp.zeros_like(borrow)
    e = []
    for j in range(NLIMB):
        acc = d[j] + jnp.uint32(pl[j]) + c
        s, c = _split(acc)
        e.append(s)
    return _stack_limbs([jnp.where(wrapped, e[j], d[j])
                         for j in range(NLIMB)])


def neg(spec: FieldSpec, a):
    z = jnp.zeros_like(a)
    return sub(spec, z, a)


def is_zero(a):
    return jnp.all(a == 0, axis=-1)


def eq(a, b):
    return jnp.all(a == b, axis=-1)


def pow_const(spec: FieldSpec, a, e: int):
    """a^e for a python-int exponent (unrolled square & multiply)."""
    if e == 0:
        return jnp.broadcast_to(jnp.asarray(spec.one), a.shape)
    result = None
    base = a
    while e:
        if e & 1:
            result = base if result is None else mont_mul(spec, result, base)
        e >>= 1
        if e:
            base = mont_mul(spec, base, base)
    return result


def inv(spec: FieldSpec, a):
    """Field inverse via Fermat (a^(m-2)); a must be nonzero."""
    return pow_const(spec, a, spec.modulus - 2)


#: most lanes of `batch_inv`'s blocked layout (a power of two)
_INV_LANES = 1 << 13


def batch_inv(spec: FieldSpec, a):
    """Montgomery batch inversion of a flat (n, 4) array, lane-blocked.

    The input, padded with ones to m * L elements, is read as m rows of
    L independent lanes (element i at row i // L, lane i % L), with
    L = min(next_pow2(n), `_INV_LANES`).  A scan over the rows keeps
    each lane's exclusive prefix products, one vectorised Fermat `inv`
    inverts the L lane totals, and a reverse scan gives every inverse:
    3n muls plus one (L, 4) inversion, on a dependent chain of m steps
    instead of n.  Every input must be nonzero; inverses are unique, so
    the result is the same for any block shape."""
    n = a.shape[0]
    if n == 0:
        return a
    lanes = min(1 << (n - 1).bit_length(), _INV_LANES)
    rows = -(-n // lanes)
    one = jnp.asarray(spec.one)
    pad = jnp.broadcast_to(one, (rows * lanes - n, NLIMB))
    x = jnp.concatenate([a, pad]).reshape(rows, lanes, NLIMB)

    def fwd(carry, row):
        return mont_mul(spec, carry, row), carry  # prefix *excluding* row

    start = jnp.broadcast_to(one, (lanes, NLIMB))
    total, prefix_ex = jax.lax.scan(fwd, start, x)

    def bwd(carry, xs):
        row, pre = xs
        return mont_mul(spec, carry, row), mont_mul(spec, carry, pre)

    _, outs = jax.lax.scan(bwd, inv(spec, total), (x, prefix_ex),
                           reverse=True)
    return outs.reshape(-1, NLIMB)[:n]


def to_mont(spec: FieldSpec, x_limbs):
    return mont_mul(spec, x_limbs, jnp.asarray(spec.r2_limbs))


def from_mont(spec: FieldSpec, a):
    one_std = jnp.zeros((1,) * (a.ndim - 1) + (NLIMB,), U32).at[..., 0].set(1)
    return mont_mul(spec, a, one_std)


# Executable-cache wrapping of the eager-callable primitives: the spec
# is a positional static (frozen dataclass, deterministic repr), so a
# fresh process replays mont_mul/add/sub dispatches from serialized
# executables instead of re-tracing each (spec, shape) signature.
# Deferred import: repro.core.execache is stdlib-only at module level.
from repro.core import execache as _execache

mont_mul = _execache.wrap("f_mont_mul", mont_mul, static_argnums=(0,))
add = _execache.wrap("f_add", add, static_argnums=(0,))
sub = _execache.wrap("f_sub", sub, static_argnums=(0,))
# batch_inv's cache name changes with its algorithm: the key holds no
# fingerprint of the code, so a disk cache kept from an earlier
# algorithm would otherwise replay it
batch_inv = _execache.wrap("f_batch_inv_blocked", batch_inv,
                           static_argnums=(0,))
to_mont = _execache.wrap("f_to_mont", to_mont, static_argnums=(0,))
from_mont = _execache.wrap("f_from_mont", from_mont, static_argnums=(0,))


# ---------------------------------------------------------------------------
# Host helpers: encoding integers / arrays into Montgomery limb form.
# ---------------------------------------------------------------------------

def encode_int(spec: FieldSpec, x: int) -> np.ndarray:
    """Python int (possibly negative) -> Montgomery limb form (4,) uint32."""
    v = (x * pow(2, 64, spec.modulus)) % spec.modulus
    return int_to_limbs(v)


def encode_ints(spec: FieldSpec, xs) -> np.ndarray:
    """Array of python/np ints -> (..., 4) uint32 Montgomery form (host).

    int64-range inputs (bit matrices, reduced challenge products, witness
    tensors) take the vectorized `encode_i64` path; arbitrary-precision
    inputs run the same computation as batched object-array ops."""
    arr = np.asarray(xs, dtype=object)
    try:
        return encode_i64(spec, arr.astype(np.int64)).reshape(
            arr.shape + (NLIMB,))
    except (OverflowError, TypeError):
        pass
    r = pow(2, 64, spec.modulus)
    return ints_to_limbs(arr * r % spec.modulus)


def decode(spec: FieldSpec, a) -> np.ndarray:
    """Montgomery limb array -> object array of canonical python ints (host)."""
    from repro.core.pipeline import profile   # the pipeline imports us
    profile.host_read()
    std = np.asarray(from_mont(spec, jnp.asarray(a)))
    return limbs_to_ints(std)


def decode_centered(spec: FieldSpec, a) -> np.ndarray:
    """Decode to signed representatives in (-m/2, m/2]."""
    vals = decode(spec, a)
    m = spec.modulus
    flat = vals.reshape(-1)
    for i in range(flat.shape[0]):
        if flat[i] > m // 2:
            flat[i] -= m
    return vals


def encode_i64(spec: FieldSpec, xs: np.ndarray) -> np.ndarray:
    """Fast path: int64 numpy array -> Montgomery limbs (vectorized host)."""
    xs = np.asarray(xs, dtype=np.int64)
    m = spec.modulus
    r = pow(2, 64, m)
    # int64 values are < 2^63 in magnitude; do the modmul in python-object
    # space only when needed.  (m * r fits in object ints.)
    vals = (xs.astype(object) * r) % m
    return ints_to_limbs(vals)


def rand_elements(spec: FieldSpec, rng: np.random.Generator, shape) -> np.ndarray:
    """Uniform field elements in Montgomery form (host-side sampling)."""
    n = int(np.prod(shape)) if shape else 1
    vals = [int(rng.integers(0, spec.modulus, dtype=np.uint64)) % spec.modulus
            for _ in range(n)]
    out = encode_ints(spec, np.array(vals, dtype=object).reshape(shape))
    return out


def hash_to_int(data: bytes, modulus: int) -> int:
    h = hashlib.sha256(data).digest()
    return int.from_bytes(h, "little") % modulus
