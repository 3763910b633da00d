"""Zero-knowledge inner-product arguments (Bulletproofs [45] style).

Two variants, both log-size and linear-prover-time:

* ``open_*``: proves <a, b_pub> = c for a *committed* vector a and a
  *public* vector b (the MLE-opening workhorse: b = e(u)).
* ``pair_*``: proves <a, b> = c where BOTH vectors are bound inside one
  commitment C = h^rho G^a H^b -- exactly the statement produced by
  Algorithm 1 for the zkReLU validity equation (19).

Honest-verifier zero knowledge comes from per-round blinding factors on
L/R plus a final Schnorr/sigma opening instead of revealing the folded
scalars.  The prover is JAX (limb arrays); the verifier mixes host ints
with vectorized JAX for the O(n) generator folds.

Prover rounds are FUSED: each round issues exactly one jitted multi-MSM
for the L/R cross terms (the two half-length MSMs, the u^{c} claim term
and the h^{rho} blind ride as extra rows/columns of `group.msm_many`),
one host transfer decoding both L and R, and one jitted fold of every
vector/generator half -- instead of the ~20 eager group-op dispatches
the unfused path paid per round.  All arithmetic is bit-identical to the
unfused primitives (`tests/test_ipa.py` pins the parity, blinds
included), so transcripts do not change.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.field import FQ, FP, add, mont_mul, from_mont, decode, int_to_limbs
from repro.core import execache, group
from repro.core import mle
from repro.core.mle import enc, fdot
from repro.core.transcript import Transcript

Q = FQ.modulus

# ---------------------------------------------------------------------------
# Round execution mode.
#
# "ladder" (default, single-statement proofs): rounds run on a small
# fixed set of buffer sizes (`_ladder_size`) with the live halves
# gathered/masked inside the round body, so log2(n) rounds compile O(1)
# distinct programs instead of one pair per halving shape.  "unrolled"
# keeps the legacy exact-shape schedule as the bit-identity parity
# oracle; multi-statement lockstep proofs always use it.
# ---------------------------------------------------------------------------

IPA_MODES = ("ladder", "unrolled")
_IPA_MODE_ENV = "ZKDL_IPA_MODE"
_ipa_mode_override: str | None = None


def round_mode() -> str:
    """Active IPA round mode: override > $ZKDL_IPA_MODE > "ladder"."""
    name = _ipa_mode_override or os.environ.get(_IPA_MODE_ENV,
                                                "ladder").lower()
    if name not in IPA_MODES:
        raise ValueError(f"unknown ipa mode {name!r}; "
                         f"choose from {IPA_MODES}")
    return name


def set_round_mode(name: str | None) -> None:
    """Process-wide override (None restores the env/default choice)."""
    global _ipa_mode_override
    if name is not None and name not in IPA_MODES:
        raise ValueError(f"unknown ipa mode {name!r}; "
                         f"choose from {IPA_MODES}")
    _ipa_mode_override = name


@dataclasses.dataclass
class IpaProof:
    ls: List[int]
    rs: List[int]
    # final sigma-protocol messages
    sigma: List[int]

    def size_bytes(self) -> int:
        return 32 * (len(self.ls) + len(self.rs) + len(self.sigma))


def _dec_scalar(x) -> int:
    return int(decode(FQ, x)[()])


def _g_pow_const(bases, e: int):
    """bases^e elementwise for one python-int exponent (jitted via g_pow)."""
    from repro.field import int_to_limbs
    e = int(e) % Q
    exps = jnp.broadcast_to(jnp.asarray(int_to_limbs(e)), bases.shape)
    return group.g_pow(bases, exps)


def _fold_vec(t, lo_coef: int, hi_coef: int):
    n2 = t.shape[0] // 2
    lo = mont_mul(FQ, t[:n2], enc(lo_coef)[None])
    hi = mont_mul(FQ, t[n2:], enc(hi_coef)[None])
    return add(FQ, lo, hi)


def _fold_gens(g, lo_exp: int, hi_exp: int):
    n2 = g.shape[0] // 2
    return group.g_mul(_g_pow_const(g[:n2], lo_exp), _g_pow_const(g[n2:], hi_exp))


def _s_vector(n: int, alphas: List[int], low_exp_is_inv: bool):
    """s_i = prod_j (alpha_j or its inverse) by the top-bit split pattern."""
    rounds = len(alphas)
    s = jnp.broadcast_to(enc(1), (n, 4)).astype(jnp.uint32)
    idx = np.arange(n)
    for j, a in enumerate(alphas):
        ai = pow(a, Q - 2, Q)
        lo, hi = (ai, a) if low_exp_is_inv else (a, ai)
        bit = (idx >> (rounds - 1 - j)) & 1
        coef = jnp.where(jnp.asarray(bit[:, None] == 0), enc(lo)[None], enc(hi)[None])
        s = mont_mul(FQ, s, coef)
    return s


def _u_gen():
    return group.derive_generators(b"zkdl/ipa-u", 1)[0]


# ---------------------------------------------------------------------------
# Fused prover rounds (one multi-MSM + one fold dispatch per round).
# ---------------------------------------------------------------------------

def _exp1(e: int) -> jnp.ndarray:
    """One python-int exponent (mod q) -> (4,) standard-form limbs."""
    return jnp.asarray(int_to_limbs(int(e) % Q))


def _lr_extras(up, h, c_l, c_r, rho_l, rho_r):
    """The up^{claim} * h^{rho} tails of both L/R as a tiny two-row MSM
    (kept OUT of the main MSM so its row length stays a power of two --
    appending two columns would force the Pippenger pad to the next
    power of four, quadrupling the sort width)."""
    pts = jnp.broadcast_to(jnp.stack([up, h])[None], (2, 2, 4))
    exps = jnp.stack([jnp.stack([c_l, rho_l]), jnp.stack([c_r, rho_r])])
    return group.msm_many(pts, exps)


def _open_round_lr(gens, a, b, up, h, rho_l, rho_r):
    """L/R of one `open` round fused into one executable:

    L = gens_hi^{a_lo} * up^{<a_lo, b_hi>} * h^{rho_l}
    R = gens_lo^{a_hi} * up^{<a_hi, b_lo>} * h^{rho_r}
    """
    n2 = a.shape[0] // 2
    c_l = from_mont(FQ, fdot(a[:n2], b[n2:]))
    c_r = from_mont(FQ, fdot(a[n2:], b[:n2]))
    a_std = from_mont(FQ, a)
    main = group.msm_many(jnp.stack([gens[n2:], gens[:n2]]),
                          jnp.stack([a_std[:n2], a_std[n2:]]))
    return group.g_mul(main, _lr_extras(up, h, c_l, c_r, rho_l, rho_r))


_open_round_lr = execache.wrap("ipa_open_round_lr", _open_round_lr)


def _pair_round_lr(gg, hh, a, b, up, h_blind, rho_l, rho_r,
                   gam_g_m, gam_h_m):
    """L/R of one `pair` round: both half-MSMs per side fused into one row.

    The stored bases carry deferred outer exponents (see `_pair_fold`):
    the true bases are gg^{gam_g} / hh^{gam_h}, so the deferral rides
    the MSM scalars for free — gg_true^{a} == gg^{gam_g * a} — and the
    emitted L/R equal the eager-fold values bit for bit."""
    n2 = a.shape[0] // 2
    c_l = from_mont(FQ, fdot(a[:n2], b[n2:]))
    c_r = from_mont(FQ, fdot(a[n2:], b[:n2]))
    a_std = from_mont(FQ, mont_mul(FQ, a, gam_g_m[None]))
    b_std = from_mont(FQ, mont_mul(FQ, b, gam_h_m[None]))
    main = group.msm_many(
        jnp.stack([jnp.concatenate([gg[n2:], hh[:n2]]),
                   jnp.concatenate([gg[:n2], hh[n2:]])]),
        jnp.stack([jnp.concatenate([a_std[:n2], b_std[n2:]]),
                   jnp.concatenate([a_std[n2:], b_std[:n2]])]))
    return group.g_mul(main, _lr_extras(up, h_blind, c_l, c_r, rho_l, rho_r))


_pair_round_lr = execache.wrap("ipa_pair_round_lr", _pair_round_lr)


def _fold_halves(vec, lo_m, hi_m):
    n2 = vec.shape[0] // 2
    return add(FQ, mont_mul(FQ, vec[:n2], lo_m[None]),
               mont_mul(FQ, vec[n2:], hi_m[None]))


def _open_fold(a, b, gens, al_m, ali_m, al_std, ali_std):
    """a' = al*a_L + al^-1*a_R, b' = al^-1*b_L + al*b_R, gens' likewise.

    The generator fold runs as ONE g_pow square-and-multiply scan over
    both halves (the 61-round scan is latency-bound on small vectors, so
    one wide scan beats two narrow ones)."""
    n2 = a.shape[0] // 2
    a2 = _fold_halves(a, al_m, ali_m)
    b2 = _fold_halves(b, ali_m, al_m)
    exps = jnp.concatenate([jnp.broadcast_to(ali_std, (n2, 4)),
                            jnp.broadcast_to(al_std, (n2, 4))])
    powed = group.g_pow(gens, exps)
    g2 = group.g_mul(powed[:n2], powed[n2:])
    return a2, b2, g2


_open_fold = execache.wrap("ipa_open_fold", _open_fold)


def _open_fold_dispatch(a, b, gens, al_m, ali_m, al_std, ali_std):
    """`_open_fold`, routed through the Pallas `kernels/sumcheck_fold`
    backend when ZKDL_FOLD_BACKEND=pallas (`mle.fold_backend`): the two
    scalar halves-folds stream through `fold_halves` and the generator
    fold through the fused square-and-multiply `pow_mul_halves` kernel.
    Bit-identical to the XLA path (tests/test_fold_dispatch.py)."""
    if mle.fold_backend() == "pallas":
        from repro.kernels.sumcheck_fold import fold_halves, pow_mul_halves
        a2 = fold_halves(a, al_m, ali_m)
        b2 = fold_halves(b, ali_m, al_m)
        g2 = pow_mul_halves(gens, ali_std, al_std)
        return a2, b2, g2
    return _open_fold(a, b, gens, al_m, ali_m, al_std, ali_std)


def _pair_round_lr_w(gg, h_base, w, a, b, up, h_blind, rho_l, rho_r):
    """First pair round with the H basis held as h_base^{w} (the zkReLU
    H' = H^{1/e} basis, never materialized): the weight rides in the
    MSM exponents — hh_lo^{b_hi} == h_base_lo^{w_lo * b_hi} — so the
    result is bit-identical to `_pair_round_lr` on the explicit basis."""
    n2 = a.shape[0] // 2
    c_l = from_mont(FQ, fdot(a[:n2], b[n2:]))
    c_r = from_mont(FQ, fdot(a[n2:], b[:n2]))
    a_std = from_mont(FQ, a)
    wl = from_mont(FQ, mont_mul(FQ, w[:n2], b[n2:]))
    wr = from_mont(FQ, mont_mul(FQ, w[n2:], b[:n2]))
    main = group.msm_many(
        jnp.stack([jnp.concatenate([gg[n2:], h_base[:n2]]),
                   jnp.concatenate([gg[:n2], h_base[n2:]])]),
        jnp.stack([jnp.concatenate([a_std[:n2], wl]),
                   jnp.concatenate([a_std[n2:], wr])]))
    return group.g_mul(main, _lr_extras(up, h_blind, c_l, c_r, rho_l, rho_r))


_pair_round_lr_w = execache.wrap("ipa_pair_round_lr_w", _pair_round_lr_w)


def _pair_fold_first(a, b, g_table, h_table, w, al_m, ali_m,
                     al2_std, ali2_m):
    """First pair fold over FIXED bases via precomputed squaring tables
    (`group.pow_table`): one conditional multiply per exponent bit
    instead of square-and-multiply, with the H-side weight vector w
    folded into the table exponents.  Like `_pair_fold`, the outer
    exponents are DEFERRED (gam_g = ali, gam_h = al after this round):
    the G side materializes gg_lo * gg_hi^{al^2} — only the hi half of
    the table is powed — and the H side h_base^{w_lo | w_hi * ali^2}.
    Bit-identical to an eager fold of the materialized bases once the
    deferred exponents are applied."""
    n2 = a.shape[0] // 2
    a2 = _fold_halves(a, al_m, ali_m)
    b2 = _fold_halves(b, ali_m, al_m)
    powed_g = group.g_pow_table(g_table[:, n2:],
                                jnp.broadcast_to(al2_std, (n2, 4)))
    gg2 = group.g_mul(g_table[0, :n2], powed_g)
    w_coef = jnp.concatenate([jnp.broadcast_to(enc(1), (n2, 4)),
                              jnp.broadcast_to(ali2_m, (n2, 4))])
    h_exps = from_mont(FQ, mont_mul(FQ, w, w_coef))
    powed_h = group.g_pow_table(h_table, h_exps)
    hh2 = group.g_mul(powed_h[:n2], powed_h[n2:])
    return a2, b2, gg2, hh2


_pair_fold_first = execache.wrap("ipa_pair_fold_first", _pair_fold_first)


def _pair_fold(a, b, gg, hh, al_m, ali_m, al2_std, ali2_std):
    """Pair fold with the OUTER generator exponent deferred.

    The true folded bases are (gg_lo * gg_hi^{al^2})^{ali} and
    (hh_lo * hh_hi^{ali^2})^{al}; only the inner merges are
    materialized — ONE g_pow over n elements instead of 2n — while the
    outer ali / al accumulate into the per-statement deferred exponents
    gam_g / gam_h held as host ints by `pair_prove_many`.  Those fold
    into later L/R MSM scalars (two cheap field muls) and are applied
    once to the two surviving generators before the sigma finale, so
    every emitted group element is bit-identical to folding eagerly."""
    n2 = a.shape[0] // 2
    a2 = _fold_halves(a, al_m, ali_m)
    b2 = _fold_halves(b, ali_m, al_m)
    exps = jnp.concatenate([jnp.broadcast_to(al2_std, (n2, 4)),
                            jnp.broadcast_to(ali2_std, (n2, 4))])
    powed = group.g_pow(jnp.concatenate([gg[n2:], hh[n2:]]), exps)
    gg2 = group.g_mul(gg[:n2], powed[:n2])
    hh2 = group.g_mul(hh[:n2], powed[n2:])
    return a2, b2, gg2, hh2


_pair_fold = execache.wrap("ipa_pair_fold", _pair_fold)


# ---------------------------------------------------------------------------
# Ladder rounds: masked fixed-size bodies.
#
# A pair statement of width n runs log2(n) rounds over halving shapes;
# unrolled, that is 2*log2(n) distinct programs to trace and compile.
# The ladder instead buckets the rounds onto O(1) buffer sizes
# (`_ladder_size`) and runs ONE masked body per size: the live vectors
# occupy a prefix of length n <= S, the live hi half is gathered with a
# host-built index vector, and dead rows are masked to zero field
# elements / zero MSM exponents.  Zero exponents contribute exactly the
# identity (Pippenger substitutes the identity point for zero digits:
# `group._msm_core`) and zero field terms add nothing to the claim dots,
# so every emitted L/R — and therefore the transcript — is bit-identical
# to the exact-shape schedule (tests/test_fold_dispatch.py pins it).
# ---------------------------------------------------------------------------

def _ladder_size(n: int, n0: int) -> int:
    """Round-body buffer size for live length n of a statement that
    started at n0: the five widest rounds (where masked tail rows would
    cost real MSM work) run exact, the rest on a power-of-four descent
    down to an absolute floor of 32 rows.  A
    handful of distinct compiled bodies per statement width (and the
    executable cache makes each a once-per-machine cost); the masked
    tail a round carries is at most 3x its live rows, so the ladder's
    steady-state work stays within a constant of the exact schedule —
    an earlier clamp at n0/16 instead ran every narrow round on a
    n0/16-row buffer, which at merged-key widths made the masked MSMs
    dominate the whole opening phase."""
    if 16 * n >= n0:
        return n
    s = n0 // 16
    while s // 4 >= n and s // 4 >= 32:
        s //= 4
    return s


@functools.lru_cache(maxsize=None)
def _round_mask(n: int, S: int):
    """Gather index + live mask for a masked round: buffer size S, live
    prefix n.  idx_hi[i] = n/2 + i for live rows (dead gathers clamp to
    slot 0 — their exponents are masked to zero, so the gathered value
    never matters)."""
    h = S // 2
    idx = np.zeros(h, np.int32)
    idx[:n // 2] = n // 2 + np.arange(n // 2, dtype=np.int32)
    mask = np.zeros((h, 1), np.uint32)
    mask[:n // 2] = 1
    return jnp.asarray(idx), jnp.asarray(mask)


def _pair_round_lr_m(gg, hh, a, b, up, h_blind, rho_l, rho_r,
                     gam_g_m, gam_h_m, idx_hi, mask):
    """Masked fixed-size `_pair_round_lr` (same deferred gam_g/gam_h
    convention): exact-size rounds pass a degenerate all-live mask, so
    one compiled body serves every round bucketed to this size."""
    h = a.shape[0] // 2
    sel = mask.astype(bool)
    a_lo = jnp.where(sel, a[:h], 0)
    b_lo = jnp.where(sel, b[:h], 0)
    a_hi = jnp.where(sel, a[idx_hi], 0)
    b_hi = jnp.where(sel, b[idx_hi], 0)
    c_l = from_mont(FQ, fdot(a_lo, b_hi))
    c_r = from_mont(FQ, fdot(a_hi, b_lo))
    al_std = from_mont(FQ, mont_mul(FQ, a_lo, gam_g_m[None]))
    ah_std = from_mont(FQ, mont_mul(FQ, a_hi, gam_g_m[None]))
    bl_std = from_mont(FQ, mont_mul(FQ, b_lo, gam_h_m[None]))
    bh_std = from_mont(FQ, mont_mul(FQ, b_hi, gam_h_m[None]))
    main = group.msm_many(
        jnp.stack([jnp.concatenate([gg[idx_hi], hh[:h]]),
                   jnp.concatenate([gg[:h], hh[idx_hi]])]),
        jnp.stack([jnp.concatenate([al_std, bh_std]),
                   jnp.concatenate([ah_std, bl_std])]))
    return group.g_mul(main, _lr_extras(up, h_blind, c_l, c_r, rho_l, rho_r))


_pair_round_lr_m = execache.wrap("ipa_pair_round_lr_m", _pair_round_lr_m)


def _pair_fold_m(a, b, gg, hh, al_m, ali_m, al2_std, ali2_std,
                 idx_hi, mask):
    """Masked fixed-size `_pair_fold`: live outputs land in the prefix
    of the halved buffer; dead scalars fold to zero and dead generators
    to the identity, keeping the buffer invariants for later rounds."""
    h = a.shape[0] // 2
    sel = mask.astype(bool)
    a2 = jnp.where(sel, add(FQ, mont_mul(FQ, a[:h], al_m[None]),
                            mont_mul(FQ, a[idx_hi], ali_m[None])), 0)
    b2 = jnp.where(sel, add(FQ, mont_mul(FQ, b[:h], ali_m[None]),
                            mont_mul(FQ, b[idx_hi], al_m[None])), 0)
    exps = jnp.concatenate([jnp.broadcast_to(al2_std, (h, 4)),
                            jnp.broadcast_to(ali2_std, (h, 4))])
    powed = group.g_pow(jnp.concatenate([gg[idx_hi], hh[idx_hi]]), exps)
    one = group.identity()
    gg2 = jnp.where(sel, group.g_mul(gg[:h], powed[:h]), one[None])
    hh2 = jnp.where(sel, group.g_mul(hh[:h], powed[h:]), one[None])
    return a2, b2, gg2, hh2


_pair_fold_m = execache.wrap("ipa_pair_fold_m", _pair_fold_m)


def _resize_state(st, S: int) -> None:
    """Move a ladder statement's buffers to size S (slice down, or grow
    with neutral elements: zero scalars, identity generators)."""
    cur = st["a"].shape[0]
    if cur == S:
        return
    if cur > S:
        for k in ("a", "b", "gg", "hh"):
            st[k] = st[k][:S]
        return
    zero = jnp.zeros((S - cur, 4), jnp.uint32)
    onep = jnp.broadcast_to(group.identity(),
                            (S - cur, 4)).astype(jnp.uint32)
    st["a"] = jnp.concatenate([st["a"], zero])
    st["b"] = jnp.concatenate([st["b"], zero])
    st["gg"] = jnp.concatenate([st["gg"], onep])
    st["hh"] = jnp.concatenate([st["hh"], onep])


def _pair_rounds_ladder(st, transcript: Transcript,
                        rng: np.random.Generator) -> None:
    """All halving rounds of ONE pair statement on the size ladder.

    Draw order, transcript schedule and emitted L/R values are
    bit-identical to the single-statement lockstep path — only the
    compiled-program schedule differs."""
    n0 = st["n"]
    while st["n"] > 1:
        n = st["n"]
        rho_l = int(rng.integers(0, Q, dtype=np.uint64)) % Q
        rho_r = int(rng.integers(0, Q, dtype=np.uint64)) % Q
        if st["accel"] is not None:
            _, h_base, _, w = st["accel"]
            lr = _pair_round_lr_w(st["gg"], h_base, w, st["a"], st["b"],
                                  st["up"], st["hb"],
                                  _exp1(rho_l), _exp1(rho_r))
        else:
            idx_hi, mask = _round_mask(n, st["a"].shape[0])
            lr = _pair_round_lr_m(st["gg"], st["hh"], st["a"], st["b"],
                                  st["up"], st["hb"], _exp1(rho_l),
                                  _exp1(rho_r), enc(st["gam_g"]),
                                  enc(st["gam_h"]), idx_hi, mask)
        li, ri = group.decode_group_many(lr)
        st["ls"].append(li)
        st["rs"].append(ri)
        transcript.absorb_ints(b"ipa2/lr", [li, ri])
        al = transcript.challenge_int(b"ipa2/alpha", Q)
        ali = pow(al, Q - 2, Q)
        al2, ali2 = al * al % Q, ali * ali % Q
        if st["accel"] is not None:
            g_table, _, h_table, w = st["accel"]
            st["a"], st["b"], st["gg"], st["hh"] = _pair_fold_first(
                st["a"], st["b"], g_table, h_table, w, enc(al),
                enc(ali), _exp1(al2), enc(ali2))
            st["accel"] = None
        else:
            idx_hi, mask = _round_mask(n, st["a"].shape[0])
            st["a"], st["b"], st["gg"], st["hh"] = _pair_fold_m(
                st["a"], st["b"], st["gg"], st["hh"], enc(al), enc(ali),
                _exp1(al2), _exp1(ali2), idx_hi, mask)
        st["gam_g"] = st["gam_g"] * ali % Q
        st["gam_h"] = st["gam_h"] * al % Q
        st["rho"] = (al2 * rho_l + st["rho"] + ali2 * rho_r) % Q
        st["n"] = n // 2
        if st["n"] > 1:
            _resize_state(st, _ladder_size(st["n"], n0))


# ---------------------------------------------------------------------------
# Variant 1: committed a, public b.
# ---------------------------------------------------------------------------

def open_prove(key, a_mont, b_mont, blind: int, claim: int,
               transcript: Transcript, rng: np.random.Generator) -> IpaProof:
    from repro.core.pipeline import profile   # the pipeline imports us
    n = a_mont.shape[0]
    assert n & (n - 1) == 0 and b_mont.shape[0] == n
    gens = key.gens[:n]
    transcript.absorb_int(b"ipa/claim", claim)
    x = transcript.challenge_int(b"ipa/x", Q)
    up = group.g_pow_int(_u_gen(), x)

    a, b, rho = a_mont, b_mont, int(blind)
    ls, rs = [], []
    with profile.span("ipa-rounds"):
        while n > 1:
            n2 = n // 2
            rho_l = int(rng.integers(0, Q, dtype=np.uint64)) % Q
            rho_r = int(rng.integers(0, Q, dtype=np.uint64)) % Q
            lr = _open_round_lr(gens, a, b, up, key.h,
                                _exp1(rho_l), _exp1(rho_r))
            li, ri = group.decode_group_many(lr)
            ls.append(li); rs.append(ri)
            transcript.absorb_ints(b"ipa/lr", [li, ri])
            al = transcript.challenge_int(b"ipa/alpha", Q)
            ali = pow(al, Q - 2, Q)
            a, b, gens = _open_fold_dispatch(a, b, gens, enc(al), enc(ali),
                                             _exp1(al), _exp1(ali))
            rho = (al * al % Q * rho_l + rho + ali * ali % Q * rho_r) % Q
            n = n2

    with profile.span("sigma"):
        # final Schnorr opening of P_f = base^a h^rho, base = g_f up^{b_f}
        a_f, b_f = (int(v) for v in decode(FQ, jnp.stack([a[0], b[0]])))
        s = int(rng.integers(0, Q, dtype=np.uint64)) % Q
        s_rho = int(rng.integers(0, Q, dtype=np.uint64)) % Q
        # K = base^s h^{s_rho} = gens_f^s up^{s b_f} h^{s_rho}: one 3-term MSM
        kk = group.msm(jnp.stack([gens[0], up, key.h]),
                       group.exps_from_ints([s, s * b_f % Q, s_rho]))
        ki = group.decode_group(kk)
        transcript.absorb_int(b"ipa/K", ki)
        e = transcript.challenge_int(b"ipa/e", Q)
        z = (s + e * a_f) % Q
        z_rho = (s_rho + e * rho) % Q
    return IpaProof(ls, rs, [ki, z, z_rho])


def open_verify(key, com, b_mont, claim: int, proof: IpaProof,
                transcript: Transcript) -> bool:
    n = b_mont.shape[0]
    assert n & (n - 1) == 0
    gens = key.gens[:n]
    transcript.absorb_int(b"ipa/claim", claim)
    x = transcript.challenge_int(b"ipa/x", Q)
    up = group.g_pow_int(_u_gen(), x)
    p = group.g_mul(com, group.g_pow_int(up, claim))

    b = b_mont
    alphas = []
    for li, ri in zip(proof.ls, proof.rs):
        transcript.absorb_ints(b"ipa/lr", [li, ri])
        al = transcript.challenge_int(b"ipa/alpha", Q)
        ali = pow(al, Q - 2, Q)
        alphas.append(al)
        b = _fold_vec(b, ali, al)
        p = group.g_mul(p, group.msm(
            jnp.stack([group.encode_group(li), group.encode_group(ri)]),
            group.exps_from_ints([al * al % Q, ali * ali % Q])))

    s = _s_vector(n, alphas, low_exp_is_inv=True)
    g_f = group.msm_field(gens, s)
    b_f = _dec_scalar(b[0])
    base = group.g_mul(g_f, group.g_pow_int(up, b_f))
    ki, z, z_rho = proof.sigma
    transcript.absorb_int(b"ipa/K", ki)
    e = transcript.challenge_int(b"ipa/e", Q)
    lhs = group.g_mul(group.g_pow_int(base, z), group.g_pow_int(key.h, z_rho))
    rhs = group.g_mul(group.encode_group(ki), group.g_pow_int(p, e))
    return group.decode_group(lhs) == group.decode_group(rhs)


# ---------------------------------------------------------------------------
# Variant 2: both vectors committed as C = h^rho G^a H^b (zkReLU eq. 19).
#
# Independent pair statements sharing one transcript run their rounds in
# LOCKSTEP (`pair_prove_many`): each round dispatches every active
# statement's fused L/R multi-MSM asynchronously and pays ONE host
# transfer decoding all of them, so S statements cost max_i(rounds_i)
# round-trip syncs instead of sum_i(rounds_i) — the zkReLU validity
# argument's main + remainder IPAs are exactly this shape.  The
# per-statement arithmetic (and therefore extraction) is unchanged; only
# the transcript interleaving differs, mirrored by `pair_verify_many`.
# ---------------------------------------------------------------------------

def pair_prove_many(stmts, transcript: Transcript,
                    rng: np.random.Generator) -> List[IpaProof]:
    """Prove S pair statements with interleaved rounds.

    ``stmts`` is a list of ``(g_gens, h_gens, h_blind, a_mont, b_mont,
    blind, claim)``, optionally extended with an 8th element
    ``accel = (g_table, h_base, h_table, w_mont)`` declaring that both
    bases are FIXED with precomputed squaring tables and that the true
    H basis is ``h_base^{w}`` (zkReLU's H' = H^{1/e}) — the first round
    then runs `_pair_round_lr_w` / `_pair_fold_first` without ever
    materializing H', bit-identically to the explicit path.  Transcript
    order per round: each active statement's (L, R) is absorbed and its
    alpha drawn, statement by statement in list order.  Rounds book
    under the "ipa-rounds" span, the sigma finales under "sigma"."""
    from repro.core.pipeline import profile   # the pipeline imports us
    states = []
    for stmt in stmts:
        gg, hh, hb, a, b, blind, claim = stmt[:7]
        accel = stmt[7] if len(stmt) > 7 else None
        n = a.shape[0]
        assert n & (n - 1) == 0 and b.shape[0] == n
        # an accel statement needs >= 1 round: the fold is what
        # materializes hh for the sigma finale
        assert accel is None or n > 1, "accel statement needs n >= 2"
        transcript.absorb_int(b"ipa2/claim", claim)
        x = transcript.challenge_int(b"ipa2/x", Q)
        states.append({"n": n, "gg": gg[:n],
                       "hh": hh[:n] if hh is not None else None,
                       "hb": hb, "a": a, "b": b, "rho": int(blind),
                       "up": group.g_pow_int(_u_gen(), x),
                       "accel": accel, "ls": [], "rs": [],
                       # deferred outer exponents: true bases are
                       # gg^{gam_g} / hh^{gam_h} (see `_pair_fold`)
                       "gam_g": 1, "gam_h": 1})

    # single-statement proofs (the aggregated pipeline's merged opening)
    # run the masked size-ladder rounds: O(1) compiled bodies instead of
    # 2 per halving shape, bit-identical transcripts (see above)
    ladder = len(states) == 1 and round_mode() == "ladder"
    with profile.span("ipa-rounds"):
        if ladder:
            _pair_rounds_ladder(states[0], transcript, rng)
        while any(st["n"] > 1 for st in states):
            active = [st for st in states if st["n"] > 1]
            lrs, blind_draws = [], []
            for st in active:
                rho_l = int(rng.integers(0, Q, dtype=np.uint64)) % Q
                rho_r = int(rng.integers(0, Q, dtype=np.uint64)) % Q
                blind_draws.append((rho_l, rho_r))
                if st["accel"] is not None:
                    _, h_base, _, w = st["accel"]
                    lrs.append(_pair_round_lr_w(st["gg"], h_base, w, st["a"],
                                                st["b"], st["up"], st["hb"],
                                                _exp1(rho_l), _exp1(rho_r)))
                else:
                    lrs.append(_pair_round_lr(st["gg"], st["hh"], st["a"],
                                              st["b"], st["up"], st["hb"],
                                              _exp1(rho_l), _exp1(rho_r),
                                              enc(st["gam_g"]),
                                              enc(st["gam_h"])))
            flat = group.decode_group_many(jnp.concatenate(lrs))  # 1 transfer
            for k, (st, (rho_l, rho_r)) in enumerate(zip(active,
                                                         blind_draws)):
                li, ri = flat[2 * k], flat[2 * k + 1]
                st["ls"].append(li); st["rs"].append(ri)
                transcript.absorb_ints(b"ipa2/lr", [li, ri])
                al = transcript.challenge_int(b"ipa2/alpha", Q)
                ali = pow(al, Q - 2, Q)
                al2, ali2 = al * al % Q, ali * ali % Q
                if st["accel"] is not None:
                    g_table, _, h_table, w = st["accel"]
                    st["a"], st["b"], st["gg"], st["hh"] = _pair_fold_first(
                        st["a"], st["b"], g_table, h_table, w, enc(al),
                        enc(ali), _exp1(al2), enc(ali2))
                    st["accel"] = None
                else:
                    st["a"], st["b"], st["gg"], st["hh"] = _pair_fold(
                        st["a"], st["b"], st["gg"], st["hh"], enc(al),
                        enc(ali), _exp1(al2), _exp1(ali2))
                st["gam_g"] = st["gam_g"] * ali % Q
                st["gam_h"] = st["gam_h"] * al % Q
                st["rho"] = (al2 * rho_l + st["rho"] + ali2 * rho_r) % Q
                st["n"] //= 2

    with profile.span("sigma"):
        # apply the deferred outer exponents to the two surviving
        # generators of every statement in ONE batched g_pow (a gam of 1
        # — no rounds, or already materialized — is an exact no-op)
        gam_fin = group.g_pow(
            jnp.stack([st[k][0] for st in states for k in ("gg", "hh")]),
            jnp.stack([_exp1(st[g]) for st in states
                       for g in ("gam_g", "gam_h")]))
        for i, st in enumerate(states):
            st["gg"] = gam_fin[2 * i][None]
            st["hh"] = gam_fin[2 * i + 1][None]
        # sigma finales: ALL statements' folded scalars decode in one
        # transfer, and every A/B commitment rides one batched multi-MSM
        finals = decode(FQ, jnp.stack([st[k][0] for st in states
                                       for k in ("a", "b")]))
        one = group.identity()
        pts, exps, sigmas = [], [], []
        for i, st in enumerate(states):
            a_f, b_f = int(finals[2 * i]), int(finals[2 * i + 1])
            s_a = int(rng.integers(0, Q, dtype=np.uint64)) % Q
            s_b = int(rng.integers(0, Q, dtype=np.uint64)) % Q
            s_rho = int(rng.integers(0, Q, dtype=np.uint64)) % Q
            t_rho = int(rng.integers(0, Q, dtype=np.uint64)) % Q
            # A = g_f^{s_a} h_f^{s_b} up^{a_f s_b + b_f s_a} h^{s_rho}
            # B = up^{s_a s_b} h^{t_rho}
            pts.append(jnp.stack([st["gg"][0], st["hh"][0], st["up"],
                                  st["hb"]]))
            pts.append(jnp.stack([st["up"], st["hb"], one, one]))
            exps.append(group.exps_from_ints(
                [s_a, s_b, (a_f * s_b + b_f * s_a) % Q, s_rho]))
            exps.append(group.exps_from_ints([s_a * s_b % Q, t_rho, 0, 0]))
            sigmas.append((a_f, b_f, s_a, s_b, s_rho, t_rho))
        ab_flat = group.decode_group_many(
            group.msm_many(jnp.stack(pts), jnp.stack(exps)))

        proofs = []
        for i, st in enumerate(states):
            a_f, b_f, s_a, s_b, s_rho, t_rho = sigmas[i]
            ai, bi = ab_flat[2 * i], ab_flat[2 * i + 1]
            transcript.absorb_ints(b"ipa2/AB", [ai, bi])
            e = transcript.challenge_int(b"ipa2/e", Q)
            z_a = (a_f * e + s_a) % Q
            z_b = (b_f * e + s_b) % Q
            z_rho = (st["rho"] * e % Q * e + s_rho * e + t_rho) % Q
            proofs.append(IpaProof(st["ls"], st["rs"],
                                   [ai, bi, z_a, z_b, z_rho]))
        return proofs


def pair_verify_many(stmts, proofs: List[IpaProof],
                     transcript: Transcript) -> bool:
    """Verify S pair statements proven by `pair_prove_many`.

    ``stmts`` is a list of ``(g_gens, h_gens, h_blind, com, claim, n)``;
    replays the interleaved transcript schedule and checks every sigma
    equation (all group comparisons decode in one transfer)."""
    states = []
    for (gg, hh, hb, com, claim, n), proof in zip(stmts, proofs):
        assert n & (n - 1) == 0
        transcript.absorb_int(b"ipa2/claim", claim)
        x = transcript.challenge_int(b"ipa2/x", Q)
        up = group.g_pow_int(_u_gen(), x)
        if len(proof.ls) != n.bit_length() - 1 or \
                len(proof.rs) != len(proof.ls):
            return False
        states.append({"n": n, "n0": n, "gg": gg, "hh": hh, "hb": hb,
                       "up": up, "proof": proof, "round": 0, "alphas": [],
                       "p": group.g_mul(com, group.g_pow_int(up, claim))})

    while any(st["n"] > 1 for st in states):
        for st in states:
            if st["n"] <= 1:
                continue
            li = st["proof"].ls[st["round"]]
            ri = st["proof"].rs[st["round"]]
            transcript.absorb_ints(b"ipa2/lr", [li, ri])
            al = transcript.challenge_int(b"ipa2/alpha", Q)
            ali = pow(al, Q - 2, Q)
            st["alphas"].append(al)
            st["p"] = group.g_mul(st["p"], group.msm(
                jnp.stack([group.encode_group(li),
                           group.encode_group(ri)]),
                group.exps_from_ints([al * al % Q, ali * ali % Q])))
            st["round"] += 1
            st["n"] //= 2

    sides = []
    for st in states:
        n = st["n0"]
        s = _s_vector(n, st["alphas"], low_exp_is_inv=True)
        s_inv = _s_vector(n, st["alphas"], low_exp_is_inv=False)
        g_f = group.msm_field(st["gg"][:n], s)
        h_f = group.msm_field(st["hh"][:n], s_inv)
        if len(st["proof"].sigma) != 5:
            return False
        ai, bi, z_a, z_b, z_rho = st["proof"].sigma
        transcript.absorb_ints(b"ipa2/AB", [ai, bi])
        e = transcript.challenge_int(b"ipa2/e", Q)
        lhs = group.g_mul(
            group.g_mul(group.g_pow_int(st["p"], e * e % Q),
                        group.g_pow_int(group.encode_group(ai), e)),
            group.encode_group(bi))
        rhs = group.g_mul(
            group.g_mul(group.g_pow_int(g_f, z_a * e % Q),
                        group.g_pow_int(h_f, z_b * e % Q)),
            group.g_mul(group.g_pow_int(st["up"], z_a * z_b % Q),
                        group.g_pow_int(st["hb"], z_rho)))
        sides.extend([lhs, rhs])
    flat = group.decode_group_many(jnp.stack(sides))
    return all(flat[2 * i] == flat[2 * i + 1] for i in range(len(states)))


def pair_prove(g_gens, h_gens, h_blind, a_mont, b_mont, blind: int, claim: int,
               transcript: Transcript, rng: np.random.Generator) -> IpaProof:
    """Single-statement pair argument (S=1 lockstep degenerates to the
    classic sequential schedule)."""
    (proof,) = pair_prove_many(
        [(g_gens, h_gens, h_blind, a_mont, b_mont, blind, claim)],
        transcript, rng)
    return proof


def pair_verify(g_gens, h_gens, h_blind, com, claim: int, proof: IpaProof,
                transcript: Transcript, n: int) -> bool:
    return pair_verify_many([(g_gens, h_gens, h_blind, com, claim, n)],
                            [proof], transcript)
