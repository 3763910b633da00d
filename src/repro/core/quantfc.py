"""Quantized fully-connected network training step (Example 4.5 of zkDL).

All values are fixed-point integers at scale 2^R held in int64 numpy
arrays; the witness this module produces is exactly the set of tensors
Protocol 2 commits to and proves relations over:

    Z^l  = A^{l-1} W^l                       (30)  [scale 2^{2R}]
    A^l  = (1 - B^l) . Z''^l                 (31)  [scale 2^R]
    G_Z^L = Z^{L'} - Y                       (32)
    G_A^l = G_Z^{l+1} W^{l+1 T}              (33)  [scale 2^{2R}]
    G_W^l = G_Z^{l T} A^{l-1}                (34)  [scale 2^{2R}]
    G_Z^l = (1 - B^l) . G_A'^l               (35)

with the rescale/sign auxiliary decompositions of Section 4:

    Z^l   = 2^R Z''^l - 2^{Q+R-1} B^l + R_Z^l         (3)
    G_A^l = 2^R G_A'^l + R_GA^l                        (5)

Floor division is used for rescaling, so both remainders live in [0, 2^R)
(the paper mixes floor/round notation; floor keeps the uniqueness argument
of Theorem 4.3 intact -- see DESIGN.md).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    q_bits: int = 16     # Q: rescaled values are Q-bit signed
    r_bits: int = 8      # R: scale factor 2^R

    @property
    def scale(self) -> int:
        return 1 << self.r_bits


def quantize(x: np.ndarray, cfg: QuantConfig) -> np.ndarray:
    """Real array -> fixed-point int64 at scale 2^R, clipped to Q-bit range."""
    v = np.floor(x * cfg.scale).astype(np.int64)
    lim = 1 << (cfg.q_bits - 1)
    return np.clip(v, -lim, lim - 1)


def dequantize(v: np.ndarray, cfg: QuantConfig) -> np.ndarray:
    return v.astype(np.float64) / cfg.scale


def rescale(v: np.ndarray, cfg: QuantConfig):
    """v -> (floor(v / 2^R), remainder in [0, 2^R))."""
    vp = np.floor_divide(v, cfg.scale)
    rem = v - vp * cfg.scale
    assert (rem >= 0).all() and (rem < cfg.scale).all()
    return vp, rem


def relu_aux(z: np.ndarray, cfg: QuantConfig) -> Dict[str, np.ndarray]:
    """Decompose Z per eq. (3): returns Z', Z'', B_{Q-1}, R_Z."""
    zp, r_z = rescale(z, cfg)
    lim = 1 << (cfg.q_bits - 1)
    if (zp < -lim).any() or (zp >= lim).any():
        raise OverflowError("Z' exceeds Q-bit signed range; raise q_bits")
    b = (zp < 0).astype(np.int64)
    zpp = zp + lim * b
    assert (zpp >= 0).all() and (zpp < lim).all()
    return {"zp": zp, "zpp": zpp, "b": b, "rz": r_z}


def grad_aux(ga: np.ndarray, cfg: QuantConfig) -> Dict[str, np.ndarray]:
    """Decompose G_A per eq. (5): returns G_A', R_GA."""
    gap, r_ga = rescale(ga, cfg)
    lim = 1 << (cfg.q_bits - 1)
    if (gap < -lim).any() or (gap >= lim).any():
        raise OverflowError("G_A' exceeds Q-bit signed range; raise q_bits")
    return {"gap": gap, "rga": r_ga}


@dataclasses.dataclass
class StepWitness:
    """Every tensor of one batch update, keyed by name, values int64.

    Shapes: x (B,d), y (B,d), w[l] (d,d), and per-layer (B,d) tensors.
    ``skips`` records the residual topology the step was computed under
    (matmul layer l -> earlier activation layer j, 1-indexed): layer l's
    operand was A^{l-1} + A^j, and the backward gradients in gap/rga are
    the ACCUMULATED totals arriving at each activation (direct path plus
    every skip), which is exactly what their committed decompositions
    must cover for the split claim routing to balance.
    """
    cfg: QuantConfig
    x: np.ndarray
    y: np.ndarray
    w: List[np.ndarray]
    z: List[np.ndarray]
    zpp: List[np.ndarray]
    b: List[np.ndarray]
    rz: List[np.ndarray]
    a: List[np.ndarray]        # a[0] = x, a[l] = relu output of layer l
    gz: List[np.ndarray]       # gz[l], l = 1..L (1-indexed: gz[l-1])
    ga: List[np.ndarray]       # ga[l] for l = 1..L-1 (accumulated totals)
    gap: List[np.ndarray]
    rga: List[np.ndarray]
    gw: List[np.ndarray]
    skips: Dict[int, int] = dataclasses.field(default_factory=dict)

    @property
    def n_layers(self) -> int:
        return len(self.w)


def step_widths(wit: "StepWitness"):
    """The shape table d_0..d_L realized by one step witness."""
    return (wit.x.shape[1],) + tuple(w.shape[1] for w in wit.w)


def step_graph_witness(wit: "StepWitness"):
    """Graph-native view of a step witness: the layer graph implied by
    the witness shapes AND its residual topology, plus per-node named
    tensors via the op registry's witness extractors (the same
    extraction path the proof pipeline's witness stacking consumes; the
    positional lists above remain as the raw training-side carrier)."""
    from repro.core.pipeline.graph import (build_fcnn_graph,
                                           build_residual_fcnn_graph,
                                           extract_node_tensors)

    if wit.skips:
        graph = build_residual_fcnn_graph(step_widths(wit),
                                          wit.x.shape[0], wit.skips)
    else:
        graph = build_fcnn_graph(step_widths(wit), wit.x.shape[0])
    return graph, extract_node_tensors(graph, wit)


def train_step_witness(x: np.ndarray, y: np.ndarray, ws: List[np.ndarray],
                       cfg: QuantConfig,
                       skips: Dict[int, int] | None = None) -> StepWitness:
    """Forward + backward pass in exact integer arithmetic.

    ``skips`` (matmul layer l -> activation layer j, 1-indexed, with
    1 <= j <= l - 2) adds residual connections: layer l's operand is
    A^{l-1} + A^j (forward skip), and the backward pass accumulates the
    gradient of each residual sum into BOTH branches before the eq. (5)
    rescale decomposition (backward split) — gap/rga therefore decompose
    the total gradient arriving at each activation, matching the
    pipeline's claim routing onto both producer slots.
    """
    skips = dict(skips or {})
    n_layers = len(ws)
    # 0-indexed matmul m consumes a[m] (+ a[skip0[m]] on a skip)
    skip0 = {}
    for l, j in skips.items():
        if not (1 <= j <= l - 2):
            raise ValueError(f"skip {l}->{j}: need 1 <= j <= l-2")
        if ws[l - 1].shape[0] != ws[j - 1].shape[1]:
            raise ValueError(f"skip {l}->{j}: width mismatch "
                             f"{ws[l - 1].shape[0]} != {ws[j - 1].shape[1]}")
        skip0[l - 1] = j
    a = [x.astype(np.int64)]
    a_in = []                  # resolved operand of each matmul
    z, zpp, bb, rz = [], [], [], []
    for l in range(n_layers):
        op = a[-1] + a[skip0[l]] if l in skip0 else a[-1]
        a_in.append(op)
        zl = op @ ws[l]
        aux = relu_aux(zl, cfg)
        z.append(zl)
        zpp.append(aux["zpp"]); bb.append(aux["b"]); rz.append(aux["rz"])
        if l < n_layers - 1:
            a.append((1 - aux["b"]) * aux["zpp"])
    # loss layer: square loss on rescaled output, eq (32)
    zp_last = zpp[-1] - (1 << (cfg.q_bits - 1)) * bb[-1]
    gz_last = zp_last - y.astype(np.int64)

    gz = [None] * n_layers
    ga = [None] * (n_layers - 1)
    gap = [None] * (n_layers - 1)
    rga = [None] * (n_layers - 1)
    acc = [None] * n_layers    # accumulated gradient arriving at a[k]
    gz[n_layers - 1] = gz_last
    for m in range(n_layers - 1, 0, -1):
        g_in = gz[m] @ ws[m].T           # gradient wrt matmul m's operand
        acc[m] = g_in if acc[m] is None else acc[m] + g_in
        if m in skip0:                   # backward split: both branches
            j = skip0[m]
            acc[j] = g_in if acc[j] is None else acc[j] + g_in
        # all consumers of a[m] (matmul m + skips from later layers,
        # already processed) have contributed: decompose the total
        aux = grad_aux(acc[m], cfg)
        ga[m - 1] = acc[m]
        gap[m - 1] = aux["gap"]; rga[m - 1] = aux["rga"]
        gz[m - 1] = (1 - bb[m - 1]) * aux["gap"]
    gw = [gz[l].T @ a_in[l] for l in range(n_layers)]
    return StepWitness(cfg=cfg, x=a[0], y=y.astype(np.int64), w=list(ws),
                       z=z, zpp=zpp, b=bb, rz=rz, a=a, gz=gz, ga=ga,
                       gap=gap, rga=rga, gw=gw, skips=skips)


# Weight init: uniform in +-0.3 up to a fan-in of 16, then shrinking as
# 1/sqrt(fan_in) (LeCun) so that every layer keeps the gain it has at
# fan-in 16.  A fixed +-0.3 grows activations and gradients by ~sqrt(fan_in)
# per layer, and at 8 layers x 128 they overflow the Q-bit range that
# `relu_aux` / `grad_aux` enforce.  Fan-ins up to 16 keep their exact
# values, so the seeded trajectories the tests pin are unchanged.
INIT_SCALE = 0.3
INIT_FAN_IN = 16


def init_weights(rng: np.random.Generator, widths,
                 cfg: QuantConfig) -> List[np.ndarray]:
    """Quantized weights W^l, shape (d_{l}, d_{l+1}), for the shape table
    ``widths`` = d_0..d_L, drawn from ``rng``."""
    return [quantize(rng.uniform(-1, 1, (d_in, d_out)) * (
        INIT_SCALE * min(1.0, (INIT_FAN_IN / d_in) ** 0.5)), cfg)
        for d_in, d_out in zip(widths, widths[1:])]


def synthetic_sgd_trajectory(n_steps: int, n_layers: int, batch: int,
                             width: int, cfg: QuantConfig, seed: int = 0,
                             lr_shift: int = 8) -> List[StepWitness]:
    """n_steps consecutive batch-update witnesses along a real integer-SGD
    trajectory on seeded synthetic data (the shared generator for tests,
    benchmarks and examples, so they all measure the same trajectory)."""
    return synthetic_sgd_trajectory_widths(
        n_steps, (width,) * (n_layers + 1), batch, cfg, seed=seed,
        lr_shift=lr_shift)


def synthetic_sgd_trajectory_widths(n_steps: int, widths, batch: int,
                                    cfg: QuantConfig, seed: int = 0,
                                    lr_shift: int = 8,
                                    skips: Dict[int, int] | None = None
                                    ) -> List[StepWitness]:
    """Heterogeneous-shape twin of `synthetic_sgd_trajectory`: ``widths``
    is the full shape table d_0..d_L (pyramid MLPs etc.), matching
    `pipeline.PipelineConfig.widths`.  The forward/backward integer
    arithmetic is shape-agnostic already; only the data generator needed
    the per-layer shapes.  ``skips`` threads the residual topology of
    `train_step_witness` through every step.  Uniform widths (without
    skips) draw the exact same seeded random streams as before, so
    existing trajectories are unchanged.
    """
    widths = tuple(int(w) for w in widths)
    rng = np.random.default_rng(seed)
    ws = init_weights(rng, widths, cfg)
    wits = []
    for _ in range(n_steps):
        x = quantize(rng.uniform(-1, 1, (batch, widths[0])), cfg)
        y = quantize(rng.uniform(-1, 1, (batch, widths[-1])), cfg)
        wit = train_step_witness(x, y, ws, cfg, skips=skips)
        wits.append(wit)
        ws = sgd_apply(ws, wit.gw, lr_shift, cfg)
    return wits


def sgd_apply(ws: List[np.ndarray], gw: List[np.ndarray], lr_shift: int,
              cfg: QuantConfig) -> List[np.ndarray]:
    """W <- W - G_W^T / 2^{lr_shift + R}: gradient at scale 2^{2R} mapped
    back to weight scale 2^R with learning rate 2^{-lr_shift} (provable
    update: one linear relation + one range-checked remainder).

    G_W^l = G_Z^{l,T} A^{l-1} (eq. 34) is (out, in)-shaped while W^l is
    (in, out), so the update transposes -- the square uniform-width case
    masked a missing transpose here until heterogeneous shapes arrived."""
    out = []
    lim = 1 << (cfg.q_bits - 1)
    for w, g in zip(ws, gw):
        step = np.floor_divide(g, 1 << (lr_shift + cfg.r_bits)).T
        out.append(np.clip(w - step, -lim, lim - 1))
    return out
