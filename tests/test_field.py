"""Field layer tests: limb Montgomery arithmetic vs python-int oracle.

Property-based (hypothesis) variants live in test_property_based.py so
this module collects in environments without dev extras installed."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.field import (
    FQ, FP, add, sub, neg, mont_mul, inv, batch_inv, pow_const,
    encode_ints, decode, encode_int, from_mont, to_mont, ints_to_limbs,
    limbs_to_ints,
)
from repro.field import modarith

SPECS = [FQ, FP]


def enc(spec, xs):
    return jnp.asarray(encode_ints(spec, np.array(xs, dtype=object)))


def dec(spec, a):
    return decode(spec, np.asarray(a))


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_roundtrip(spec):
    vals = [0, 1, 2, spec.modulus - 1, 123456789, 2**60]
    a = enc(spec, vals)
    back = dec(spec, a)
    assert [int(x) for x in back] == [v % spec.modulus for v in vals]


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_add_sub_mul_known(spec):
    rng = np.random.default_rng(0)
    m = spec.modulus
    xs = [int(rng.integers(0, 2**61)) % m for _ in range(64)]
    ys = [int(rng.integers(0, 2**61)) % m for _ in range(64)]
    a, b = enc(spec, xs), enc(spec, ys)
    assert [int(v) for v in dec(spec, add(spec, a, b))] == [(x + y) % m for x, y in zip(xs, ys)]
    assert [int(v) for v in dec(spec, sub(spec, a, b))] == [(x - y) % m for x, y in zip(xs, ys)]
    assert [int(v) for v in dec(spec, mont_mul(spec, a, b))] == [(x * y) % m for x, y in zip(xs, ys)]
    assert [int(v) for v in dec(spec, neg(spec, a))] == [(-x) % m for x in xs]


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_edge_values(spec):
    m = spec.modulus
    edge = [0, 1, m - 1, m - 2, 2**16 - 1, 2**32 - 1, 2**48 - 1, m // 2]
    a = enc(spec, edge)
    for i, x in enumerate(edge):
        for j, y in enumerate(edge):
            got = int(dec(spec, mont_mul(spec, a[i], a[j]))[()])
            assert got == (x * y) % m, (x, y)
    s = int(dec(spec, add(spec, a[2], a[2]))[()])
    assert s == (2 * (m - 1)) % m


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_inv_and_pow(spec):
    rng = np.random.default_rng(1)
    m = spec.modulus
    xs = [int(rng.integers(1, 2**60)) for _ in range(8)]
    a = enc(spec, xs)
    ia = inv(spec, a)
    prod = mont_mul(spec, a, ia)
    assert all(int(v) == 1 for v in dec(spec, prod))
    p5 = pow_const(spec, a, 5)
    assert [int(v) for v in dec(spec, p5)] == [pow(x, 5, m) for x in xs]
    p0 = pow_const(spec, a, 0)
    assert all(int(v) == 1 for v in dec(spec, p0))


def _py_batch_inv(xs, m):
    """Montgomery's trick on python ints: the reference `batch_inv` must
    match, independent of its block layout."""
    prefix, acc = [], 1
    for x in xs:
        prefix.append(acc)
        acc = acc * x % m
    inv_acc, out = pow(acc, m - 2, m), [0] * len(xs)
    for i in reversed(range(len(xs))):
        out[i] = inv_acc * prefix[i] % m
        inv_acc = inv_acc * xs[i] % m
    return out


_LANES = modarith._INV_LANES
# one element; one partial row; a power of two above the lane count
# (several full rows); padded rows; and past 2^15 (FQ only: each size
# compiles its own program)
_BATCH_INV_CASES = ([(s, n) for s in SPECS for n in (1, 33, 2 * _LANES)]
                    + [(FQ, 3 * _LANES + 5), (FQ, 5 * _LANES + 1)])


@pytest.mark.parametrize("spec,n", _BATCH_INV_CASES,
                         ids=lambda v: getattr(v, "name", str(v)))
def test_batch_inv(spec, n):
    assert 5 * _LANES + 1 > 1 << 15
    m = spec.modulus
    rng = np.random.default_rng(n)
    xs = rng.integers(1, m, size=n, dtype=np.uint64).tolist()
    b = np.asarray(batch_inv(spec, enc(spec, xs)))
    want = [pow(x, m - 2, m) for x in xs]
    assert _py_batch_inv(xs, m) == want
    # bit for bit, in Montgomery limbs
    np.testing.assert_array_equal(
        b, encode_ints(spec, np.array(want, dtype=object)))


def test_limb_roundtrip_multidim():
    rng = np.random.default_rng(3)
    vals = np.array([[int(rng.integers(0, 2**61)) for _ in range(3)]
                     for _ in range(2)], dtype=object)
    limbs = ints_to_limbs(vals)
    assert limbs.shape == (2, 3, 4)
    back = limbs_to_ints(limbs)
    assert (back == vals).all()


def test_mont_form_identity():
    a = enc(FQ, [7])
    std = from_mont(FQ, a)
    again = to_mont(FQ, std)
    assert (np.asarray(a) == np.asarray(again)).all()
