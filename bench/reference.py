"""Plain reference of one integer SGD step of a dense+ReLU network.

Written from the zkDL paper's equations (arXiv 2307.16273, Example 4.5
and Section 4), in numpy int64, and independent of the program: it
imports nothing from ``repro``.  Values are fixed point at scale 2^R,
rescaled values are Q-bit signed, rescaling is floor division:

    Z^l    = A^{l-1} W^l                          scale 2^{2R}
    Z^l    = 2^R Z'^l + R_Z^l,  0 <= R_Z^l < 2^R
    B^l    = [Z'^l < 0],  Z''^l = Z'^l + 2^{Q-1} B^l
    A^l    = (1 - B^l) Z''^l                      (ReLU, l < L)
    G_Z^L  = Z'^L - Y                             (square loss)
    G_A^l  = G_Z^{l+1} W^{l+1 T} = 2^R G_A'^l + R_GA^l
    G_Z^l  = (1 - B^l) G_A'^l
    G_W^l  = G_Z^{l T} A^{l-1}
    W^l   <- clip(W^l - floor(G_W^l / 2^{lr_shift + R})^T)

``matmul`` is the one place precision enters: the reference passes
exact int64 products; the control (`control_matmul`) passes the same
products computed in a lower precision on the device.
"""
from __future__ import annotations

import numpy as np

#: witness tensors compared, in the order they are reported
TENSORS = ("z", "zpp", "b", "rz", "a", "gz", "ga", "gap", "rga", "gw")


class RangeError(ValueError):
    """A rescaled value left the Q-bit signed range."""


def exact_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.matmul(a.astype(np.int64), b.astype(np.int64))


def _rescale(v, r_bits, q_bits, what):
    vp = np.floor_divide(v, 1 << r_bits)
    lim = 1 << (q_bits - 1)
    if (vp < -lim).any() or (vp >= lim).any():
        raise RangeError(f"{what} exceeds the {q_bits}-bit signed range")
    return vp, v - vp * (1 << r_bits)


def train_step(x, y, ws, q_bits: int, r_bits: int, lr_shift: int,
               matmul=exact_matmul):
    """One step: returns ``(new_ws, tensors)`` with ``tensors[name]`` a
    list over layers (``a`` starts with the input ``x``)."""
    lim = 1 << (q_bits - 1)
    n = len(ws)
    t = {k: [] for k in TENSORS}
    t["a"].append(np.asarray(x, np.int64))
    for l, w in enumerate(ws):
        z = matmul(t["a"][-1], w)
        zp, rz = _rescale(z, r_bits, q_bits, "Z'")
        b = (zp < 0).astype(np.int64)
        zpp = zp + lim * b
        t["z"].append(z)
        t["zpp"].append(zpp)
        t["b"].append(b)
        t["rz"].append(rz)
        if l < n - 1:
            t["a"].append((1 - b) * zpp)
    gz = [None] * n
    gz[-1] = (t["zpp"][-1] - lim * t["b"][-1]) - np.asarray(y, np.int64)
    ga, gap, rga = [None] * (n - 1), [None] * (n - 1), [None] * (n - 1)
    for m in range(n - 1, 0, -1):
        ga[m - 1] = matmul(gz[m], ws[m].T)
        gap[m - 1], rga[m - 1] = _rescale(ga[m - 1], r_bits, q_bits, "G_A'")
        gz[m - 1] = (1 - t["b"][m - 1]) * gap[m - 1]
    t["gz"], t["ga"], t["gap"], t["rga"] = gz, ga, gap, rga
    t["gw"] = [matmul(gz[l].T, t["a"][l]) for l in range(n)]
    shift = 1 << (lr_shift + r_bits)
    new_ws = [np.clip(w - np.floor_divide(g, shift).T, -lim, lim - 1)
              for w, g in zip(ws, t["gw"])]
    return new_ws, t


def max_abs_diff(got_ws, got, ref_ws, ref) -> int:
    """Largest |program - reference| over every witness tensor and every
    updated weight; a count or shape mismatch reads 2^63 - 1."""
    unbounded = int(np.iinfo(np.int64).max)
    pairs = [(got_ws, ref_ws)] + [(got[k], ref[k]) for k in TENSORS]
    if any(len(g) != len(r) for g, r in pairs):
        return unbounded
    worst = 0
    for g, r in ((np.asarray(g), np.asarray(r))
                 for gs, rs in pairs for g, r in zip(gs, rs)):
        if g.shape != r.shape:
            return unbounded
        if g.size:
            worst = max(worst, int(np.abs(g.astype(np.int64) - r).max()))
    return worst


def control_matmul(kind: str):
    """The reference's product in a lower precision, on JAX's default
    device.  ``bf16_f32``: bfloat16 operands, float32 accumulation — what
    a float32 matmul runs at the TPU's default precision; ``float32``:
    float32 at the highest precision; ``int32``: wrapping int32."""
    import jax
    import jax.numpy as jnp

    def mm(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if kind == "int32":
            out = jnp.matmul(jnp.asarray(a, jnp.int32),
                             jnp.asarray(b, jnp.int32),
                             preferred_element_type=jnp.int32)
        elif kind == "float32":
            out = jnp.matmul(jnp.asarray(a, jnp.float32),
                             jnp.asarray(b, jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        elif kind == "bf16_f32":
            out = jnp.matmul(jnp.asarray(a, jnp.bfloat16),
                             jnp.asarray(b, jnp.bfloat16),
                             preferred_element_type=jnp.float32)
        else:
            raise ValueError(f"unknown control precision {kind!r}")
        return np.rint(np.asarray(out, np.float64)).astype(np.int64)

    return mm
