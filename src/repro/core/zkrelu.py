"""zkReLU: batched validity proofs for the auxiliary inputs (Section 4.1).

Given the stacked (over layers) auxiliary tensors

    Z''  in [0, 2^{Q-1})^Ds        B_{Q-1} in {0,1}^Ds
    G_A' in [-2^{Q-1}, 2^{Q-1})^Ds
    R_Z, R_GA in [0, 2^R)^Ds

the prover commits to the bit matrices

    B  = [[bits(Z'') | 0], [signed-bits(G_A')]]   in {0,1}^{2Ds x Q}
    B' = B - 1 (except the forced-zero column, which stays 0)

via com_B^ip = h^r G^B H^{B'} (Protocol 1), and proves the single combined
inner-product relation (19)

    < B_k - z 1,  z^2 (e_relu (x) s_Q) + (z 1 + B'_k) . (e_relu (x) e_bit) >
        = z^3 - (1 - v_k) z^2 + z v'_k

with B_k = B + k \bar{B}_{Q-1}, via the commitment transformation of
Algorithm 1 followed by the two-sided zero-knowledge IPA.  Theorem 4.1
gives soundness: acceptance implies all range constraints hold.

The remainders R_Z / R_GA use the identical machinery with an unsigned
R-bit s-vector and no k-term (their own (19)-analogue), as the paper's
"combined ... using random linear combinations" step.

Execution model: the eq. (19) witness tables are never materialized on
the host.  `prove_statements` hands the raw stacked integers to
`repro.kernels.validity_tables`, which shift/masks the bits out and
assembles both (main + remainder) a/b tables in one accelerator
dispatch; the bit matrices themselves (`build_aux_bits`, vectorized
shift/mask) exist only for the Pedersen commitments.  Both statements
are then folded into ONE pair IPA: callers either merge them into the
pipeline's direct-sum opening (`pipeline.openings`) or, standalone,
into a lam-weighted two-statement merge over the vk-level merged basis
(`prove_validity` / `verify_validity`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax.numpy as jnp
import numpy as np

from repro.field import FQ, FP, mont_mul, batch_inv, from_mont
from repro.core import group, ipa
from repro.core.mle import enc, enc_vec, expand_point, hexpand_point
from repro.core.transcript import Transcript
from repro.kernels import validity_tables as vtab

Q_MOD = FQ.modulus
P_MOD = FP.modulus


def _rand_scalar(rng) -> int:
    return int(rng.integers(0, Q_MOD, dtype=np.uint64)) % Q_MOD


def bits_unsigned(v: np.ndarray, nbits: int) -> np.ndarray:
    """(n,) int64 in [0, 2^nbits) -> (n, nbits) 0/1 int8."""
    assert (v >= 0).all() and (v < (1 << nbits)).all()
    return ((v[:, None] >> np.arange(nbits, dtype=np.int64)[None, :]) & 1
            ).astype(np.int8)


def bits_signed(v: np.ndarray, nbits: int) -> np.ndarray:
    """(n,) int64 in [-2^{nbits-1}, 2^{nbits-1}) -> (n, nbits) two's compl."""
    lim = 1 << (nbits - 1)
    assert (v >= -lim).all() and (v < lim).all()
    u = np.where(v < 0, v + (1 << nbits), v).astype(np.int64)
    return ((u[:, None] >> np.arange(nbits, dtype=np.int64)[None, :]) & 1
            ).astype(np.int8)


@dataclasses.dataclass(frozen=True)
class ValidityKeys:
    """Generator bases. The B_{Q-1} sub-basis is the column Q-1 of the
    Z''-half of G (paper: G_{[0:D, Q-1]} = g), so a commitment to B_{Q-1}
    under g IS a commitment to \bar{B}_{Q-1} under G."""
    g_big: jnp.ndarray     # (2 Ds Q, 4)
    h_big: jnp.ndarray     # (2 Ds Q, 4)
    g_r: jnp.ndarray       # (2 Ds R, 4)  remainder bases
    h_r: jnp.ndarray       # (2 Ds R, 4)
    h_blind: jnp.ndarray   # (4,)
    ds: int
    q_bits: int
    r_bits: int

    @property
    def g_col(self) -> jnp.ndarray:
        """g = G[0:Ds, Q-1]: basis for standalone B_{Q-1} commitments."""
        idx = np.arange(self.ds) * self.q_bits + (self.q_bits - 1)
        return self.g_big[idx]

    @property
    def h_col(self) -> jnp.ndarray:
        idx = np.arange(self.ds) * self.q_bits + (self.q_bits - 1)
        return self.h_big[idx]

    @property
    def n_main(self) -> int:
        return 2 * self.ds * self.q_bits

    @property
    def n_rem(self) -> int:
        return 2 * self.ds * self.r_bits

    @property
    def merged_len(self) -> int:
        """Power-of-two length of the lam-merged (main ++ rem) statement."""
        n, m = self.n_main + self.n_rem, 1
        while m < n:
            m <<= 1
        return m

    def _tag(self) -> bytes:
        return b"ds%d-q%d-r%d" % (self.ds, self.q_bits, self.r_bits)

    @functools.cached_property
    def g_merged(self) -> jnp.ndarray:
        """G basis of the merged statement: G ++ G_R ++ fresh pad."""
        pad = self.merged_len - self.n_main - self.n_rem
        parts = [self.g_big, self.g_r]
        if pad:
            parts.append(group.derive_generators(
                b"zkrelu/Gpad/" + self._tag(), pad))
        return jnp.concatenate(parts)

    @functools.cached_property
    def h_merged(self) -> jnp.ndarray:
        pad = self.merged_len - self.n_main - self.n_rem
        parts = [self.h_big, self.h_r]
        if pad:
            parts.append(group.derive_generators(
                b"zkrelu/Hpad/" + self._tag(), pad))
        return jnp.concatenate(parts)

    # precomputed squaring chains (`group.pow_table`) for the fixed
    # bases: built lazily once per key, they let the merged validity IPA
    # run its FIRST (widest) round with one conditional multiply per
    # exponent bit and skip materializing H' = H^{1/e} entirely.
    # Memory: each table is 61x its basis (976 bytes/element), so the
    # accel path only engages below POW_TABLE_MAX_ELEMS — larger keys
    # fall back to the explicit (bit-identical) H' path rather than
    # pinning hundreds of MB per table on the key.
    @functools.cached_property
    def g_merged_table(self) -> jnp.ndarray:
        return group.pow_table(self.g_merged)

    @functools.cached_property
    def h_merged_table(self) -> jnp.ndarray:
        return group.pow_table(self.h_merged)


# accel tables above this basis length would pin > ~64 MB each on the
# key; past it the first-round speedup no longer justifies the memory
POW_TABLE_MAX_ELEMS = 1 << 16


def make_validity_keys(ds: int, q_bits: int, r_bits: int) -> ValidityKeys:
    # Q and R must be powers of two so the bit index is a clean MLE variable
    # block (the paper pads tensors to powers of two for the same reason).
    assert q_bits & (q_bits - 1) == 0, "q_bits must be a power of two"
    assert r_bits & (r_bits - 1) == 0, "r_bits must be a power of two"
    assert ds & (ds - 1) == 0, "stacked aux length must be a power of two"
    tag = b"ds%d-q%d-r%d" % (ds, q_bits, r_bits)
    return ValidityKeys(
        g_big=group.derive_generators(b"zkrelu/G/" + tag, 2 * ds * q_bits),
        h_big=group.derive_generators(b"zkrelu/H/" + tag, 2 * ds * q_bits),
        g_r=group.derive_generators(b"zkrelu/GR/" + tag, 2 * ds * r_bits),
        h_r=group.derive_generators(b"zkrelu/HR/" + tag, 2 * ds * r_bits),
        h_blind=group.derive_generators(b"zkrelu/hb/" + tag, 1)[0],
        ds=ds, q_bits=q_bits, r_bits=r_bits)


def _commit_pm_bits(gens, plus_bits, minus_bits, h_blind, blind: int):
    """h^blind * gens^{plus} * gens^{-minus} for 0/1 matrices (flattened)."""
    acc = group.msm_bits(gens, jnp.asarray(plus_bits.reshape(-1).astype(np.uint32)))
    if minus_bits is not None:
        m = group.msm_bits(gens, jnp.asarray(minus_bits.reshape(-1).astype(np.uint32)))
        acc = group.g_mul(acc, group.g_inv(m))
    if blind:
        acc = group.g_mul(acc, group.g_pow_int(h_blind, blind))
    return acc


@dataclasses.dataclass
class AuxBits:
    """Bit matrices for the stacked aux tensors (host int8 arrays), plus
    the raw stacked integers they decompose — the validity-table kernel
    consumes the raw values directly and never reads the matrices."""
    b_mat: np.ndarray       # (2Ds, Q) bits of (Z'' ; G_A')
    bneg: np.ndarray        # (2Ds, Q) -B' = 1 - B, with forced-zero column 0
    bq: np.ndarray          # (Ds,) B_{Q-1}
    br_mat: np.ndarray      # (2Ds, R) bits of (R_Z ; R_GA)
    brneg: np.ndarray       # (2Ds, R) 1 - B_R
    zpp: np.ndarray         # (Ds,) int64 Z''
    gap: np.ndarray         # (Ds,) int64 G_A'
    rz: np.ndarray          # (Ds,) int64 R_Z
    rga: np.ndarray         # (Ds,) int64 R_GA


def build_aux_bits(zpp: np.ndarray, gap: np.ndarray, bq: np.ndarray,
                   rz: np.ndarray, rga: np.ndarray, q_bits: int,
                   r_bits: int) -> AuxBits:
    ds = zpp.shape[0]
    b_mat = np.zeros((2 * ds, q_bits), dtype=np.int8)
    b_mat[:ds, : q_bits - 1] = bits_unsigned(zpp, q_bits - 1)
    b_mat[ds:, :] = bits_signed(gap, q_bits)
    bneg = 1 - b_mat                       # -B' = 1 - B
    bneg[:ds, q_bits - 1] = 0              # forced-zero column: B' = 0 there
    br_mat = np.zeros((2 * ds, r_bits), dtype=np.int8)
    br_mat[:ds] = bits_unsigned(rz, r_bits)
    br_mat[ds:] = bits_unsigned(rga, r_bits)
    return AuxBits(b_mat=b_mat, bneg=bneg, bq=bq.astype(np.int8),
                   br_mat=br_mat, brneg=1 - br_mat,
                   zpp=zpp.astype(np.int64), gap=gap.astype(np.int64),
                   rz=rz.astype(np.int64), rga=rga.astype(np.int64))


@dataclasses.dataclass
class ValidityCommitments:
    com_b_ip: int          # h^r G^B H^{B'}
    com_bq1: int           # h^{rq1} g_col^{B_{Q-1}}
    com_bq1p: int          # h^{r'} h_col^{B'_{Q-1}}
    com_br_ip: int         # h^{rr} GR^{B_R} HR^{B'_R}


@dataclasses.dataclass
class ValidityBlinds:
    r: int
    rq1: int
    rq1p: int
    rr: int


def commit_validity(keys: ValidityKeys, bits: AuxBits, rng) -> (
        tuple):
    """Protocol 1 (trainer side): commitments to bit matrices.

    com_bq1 (B_{Q-1} under the g_col sub-basis, own blind rq1) is part of
    this bundle: the merged opening pins the bq MLE at the same random
    point through two routes — the slot commitment and, via the k-term,
    this column commitment — so the two must agree w.h.p.  Publishing it
    here (rather than splicing g_col into another key) keeps every slice
    of the merged IPA basis generator-disjoint.
    """
    r = _rand_scalar(rng)
    rq1 = _rand_scalar(rng)
    rq1p = _rand_scalar(rng)
    rr = _rand_scalar(rng)
    com_b = _commit_pm_bits(keys.g_big, bits.b_mat, None, keys.h_blind, 0)
    com_bp = _commit_pm_bits(keys.h_big, np.zeros_like(bits.bneg), bits.bneg,
                             keys.h_blind, 0)
    com_b_ip = group.g_mul(group.g_mul(com_b, com_bp),
                           group.g_pow_int(keys.h_blind, r))
    # com of B_{Q-1} over g_col, com of B'_{Q-1} = B_{Q-1} - 1 over h_col
    com_bq1 = _commit_pm_bits(keys.g_col, bits.bq.reshape(-1, 1), None,
                              keys.h_blind, rq1)
    bq1p_neg = (1 - bits.bq).astype(np.int8)   # -(B_{Q-1}-1)
    com_bq1p = _commit_pm_bits(keys.h_col, np.zeros((keys.ds, 1), np.int8),
                               bq1p_neg.reshape(-1, 1), keys.h_blind, rq1p)
    com_br = _commit_pm_bits(keys.g_r, bits.br_mat, None, keys.h_blind, 0)
    com_brp = _commit_pm_bits(keys.h_r, np.zeros_like(bits.brneg), bits.brneg,
                              keys.h_blind, 0)
    com_br_ip = group.g_mul(group.g_mul(com_br, com_brp),
                            group.g_pow_int(keys.h_blind, rr))
    coms = ValidityCommitments(
        com_b_ip=group.decode_group(com_b_ip),
        com_bq1=group.decode_group(com_bq1),
        com_bq1p=group.decode_group(com_bq1p),
        com_br_ip=group.decode_group(com_br_ip))
    return coms, ValidityBlinds(r=r, rq1=rq1, rq1p=rq1p, rr=rr)


def _s_q_vector(q_bits: int) -> List[int]:
    """s_Q = (1, 2, ..., 2^{Q-2}, -2^{Q-1}) mod q."""
    s = [pow(2, j, Q_MOD) for j in range(q_bits - 1)]
    s.append(Q_MOD - pow(2, q_bits - 1, Q_MOD))
    return s


def _main_claim(v_k: int, vp_k: int, z: int, s_sum: int = -1) -> int:
    """RHS of (19): -z^3 sum(s) - (1 - v_k) z^2 + z v'_k.

    For the signed s_Q vector sum(s) = -1, recovering the paper's
    z^3 - (1-v_k) z^2 + z v'_k; the unsigned remainder s-vector has
    sum(s) = 2^R - 1.
    """
    return (-pow(z, 3, Q_MOD) * s_sum - (1 - v_k) * z * z + z * vp_k) % Q_MOD


@dataclasses.dataclass
class ValidityStatements:
    """Both eq. (19) pair-IPA statements, ready to be folded into a
    single direct-sum opening.  a/b are (n, 4) Montgomery witness
    tables; w is the H-basis exponent weight vector 1/e (Montgomery);
    claims/blinds are canonical ints."""
    a_main: jnp.ndarray
    b_main: jnp.ndarray
    w_main: jnp.ndarray
    claim_main: int
    blind_main: int
    a_rem: jnp.ndarray
    b_rem: jnp.ndarray
    w_rem: jnp.ndarray
    claim_rem: int
    blind_rem: int


def prove_statements(keys: ValidityKeys, bits: AuxBits,
                     blinds: ValidityBlinds, u_relu: List[int], v: int,
                     v_q1: int, v_r: int,
                     transcript: Transcript) -> ValidityStatements:
    """Draw the validity challenges and build both statement witnesses.

    u_relu = (u_star..., u'') is the row point; v / v_q1 / v_r are the
    (already transcript-absorbed) MLE-evaluation claims.  Challenges
    k, u_bit, z (and the remainder's u_bit_r, z_r) are drawn from the
    shared transcript; the a/b tables for BOTH statements come out of
    one `validity_tables` kernel dispatch over the raw aux integers.
    """
    ds, qb, rb = keys.ds, keys.q_bits, keys.r_bits
    k = transcript.challenge_int(b"zkrelu/k", Q_MOD)
    u_bit = transcript.challenge_ints(b"zkrelu/ubit", Q_MOD,
                                      (qb - 1).bit_length())
    z = transcript.challenge_int(b"zkrelu/z", Q_MOD)
    u_bit_r = transcript.challenge_ints(b"zkrelu/ubitr", Q_MOD,
                                        (rb - 1).bit_length())
    z_r = transcript.challenge_int(b"zkrelu/zr", Q_MOD)

    e_relu = expand_point(u_relu)
    assert e_relu.shape[0] == 2 * ds
    e_bit = expand_point(u_bit)[:qb]
    e_bit_r = expand_point(u_bit_r)[:rb]

    # e_relu (x) e_bit and the z^2-scaled e_relu (x) s tables, both
    # statements concatenated in kernel-layout order
    e_full_m = mont_mul(FQ, e_relu[:, None, :],
                        e_bit[None, :, :]).reshape(-1, 4)
    e_full_r = mont_mul(FQ, e_relu[:, None, :],
                        e_bit_r[None, :, :]).reshape(-1, 4)
    es_m = mont_mul(FQ,
                    mont_mul(FQ, e_relu[:, None, :],
                             enc_vec(_s_q_vector(qb))[None, :, :]
                             ).reshape(-1, 4),
                    enc(z * z % Q_MOD)[None])
    s_r = [pow(2, j, Q_MOD) for j in range(rb)]
    es_r = mont_mul(FQ,
                    mont_mul(FQ, e_relu[:, None, :],
                             enc_vec(s_r)[None, :, :]).reshape(-1, 4),
                    enc(z_r * z_r % Q_MOD)[None])

    layout = vtab.build_layout(bits.zpp, bits.gap, bits.bq, bits.rz,
                               bits.rga, qb, rb)
    a, b = vtab.build_tables(layout, k, z, z_r,
                             jnp.concatenate([e_full_m, e_full_r]),
                             jnp.concatenate([es_m, es_r]))
    n_main = layout.n_main

    # derived claim values (the verifier recomputes these itself)
    upp = u_relu[-1]
    v_k = (v - k * pow(2, qb - 1, Q_MOD) % Q_MOD
           * ((1 - upp) % Q_MOD) % Q_MOD * v_q1) % Q_MOD
    vp_k = _vp_k(k, u_relu, u_bit, qb)
    a_main, b_main, a_rem, b_rem = (a[:n_main], b[:n_main], a[n_main:],
                                    b[n_main:])
    # the H-basis weights 1/e: two lane-blocked batch inversions (a
    # chain of about n / `_INV_LANES` steps each), dispatched after
    # every table: the device runs programs in order, so a wait on the
    # tables never waits on them
    w_main, w_rem = batch_inv(FQ, e_full_m), batch_inv(FQ, e_full_r)
    return ValidityStatements(
        a_main=a_main, b_main=b_main, w_main=w_main,
        claim_main=_main_claim(v_k, vp_k, z),
        blind_main=(blinds.r + k * (blinds.rq1 + blinds.rq1p)) % Q_MOD,
        a_rem=a_rem, b_rem=b_rem, w_rem=w_rem,
        claim_rem=_main_claim(v_r, 1, z_r, s_sum=(1 << rb) - 1),
        blind_rem=blinds.rr)


@dataclasses.dataclass
class ValidityVerifyCtx:
    """Verifier-side mirror of `ValidityStatements`: the transformed
    commitments (Algorithm 1), recomputed claims and materialized
    H' = H^{1/e} bases the merged-IPA verifier splices in."""
    com_t: jnp.ndarray
    com_tr: jnp.ndarray
    claim_main: int
    claim_rem: int
    h_prime_main: jnp.ndarray
    h_prime_rem: jnp.ndarray


def verify_statements(keys: ValidityKeys, coms: ValidityCommitments,
                      v: int, v_q1: int, v_r: int, u_relu: List[int],
                      transcript: Transcript) -> ValidityVerifyCtx:
    """Redraw the validity challenges and transform the commitments."""
    ds, qb, rb = keys.ds, keys.q_bits, keys.r_bits
    k = transcript.challenge_int(b"zkrelu/k", Q_MOD)
    u_bit = transcript.challenge_ints(b"zkrelu/ubit", Q_MOD,
                                      (qb - 1).bit_length())
    z = transcript.challenge_int(b"zkrelu/z", Q_MOD)
    u_bit_r = transcript.challenge_ints(b"zkrelu/ubitr", Q_MOD,
                                        (rb - 1).bit_length())
    z_r = transcript.challenge_int(b"zkrelu/zr", Q_MOD)

    upp = u_relu[-1]
    v_k = (v - k * pow(2, qb - 1, Q_MOD) % Q_MOD
           * ((1 - upp) % Q_MOD) % Q_MOD * v_q1) % Q_MOD
    vp_k = _vp_k(k, u_relu, u_bit, qb)

    # com_{B_{Q-1}}^ip = com_{B_{Q-1}} * com_{B'_{Q-1}}   (Protocol 1 line 3)
    com_bq1_ip = group.decode_group(
        group.g_mul(group.encode_group(coms.com_bq1),
                    group.encode_group(coms.com_bq1p)))
    com_t = transform_commitment(keys, coms.com_b_ip, com_bq1_ip, k, z, u_bit)
    com_tr = transform_commitment(keys, coms.com_br_ip, None, None, z_r,
                                  u_bit_r, remainder=True)
    e_relu = expand_point(u_relu)
    return ValidityVerifyCtx(
        com_t=com_t, com_tr=com_tr,
        claim_main=_main_claim(v_k, vp_k, z),
        claim_rem=_main_claim(v_r, 1, z_r, s_sum=(1 << rb) - 1),
        h_prime_main=_h_prime_basis(keys.h_big, e_relu,
                                    expand_point(u_bit)[:qb]),
        h_prime_rem=_h_prime_basis(keys.h_r, e_relu,
                                   expand_point(u_bit_r)[:rb]))


def prove_validity(keys: ValidityKeys, bits: AuxBits,
                   blinds: ValidityBlinds, u_relu: List[int], v: int,
                   v_q1: int, v_r: int, transcript: Transcript,
                   rng) -> ipa.IpaProof:
    """Standalone validity of aux inputs: ONE merged pair IPA.

    The main and remainder statements become disjoint slices of the
    merged basis (G ++ G_R ++ pad); the remainder slice is lam-scaled so
    claim monomials stay distinct (claim = c_main + lam^2 c_rem) and the
    verifier can assemble the merged commitment as com_t * com_tr^lam.
    The pipeline does the same fold with rho-powers inside its
    direct-sum opening — this wrapper is the two-statement special case.
    """
    st = prove_statements(keys, bits, blinds, u_relu, v, v_q1, v_r,
                          transcript)
    lam = transcript.challenge_int(b"zkrelu/lam", Q_MOD)
    lam_m = enc(lam)
    pad = keys.merged_len - keys.n_main - keys.n_rem
    zeros = jnp.zeros((pad, 4), dtype=jnp.uint32)
    a = jnp.concatenate([st.a_main,
                         mont_mul(FQ, st.a_rem, lam_m[None]), zeros])
    b = jnp.concatenate([st.b_main,
                         mont_mul(FQ, st.b_rem, lam_m[None]), zeros])
    ones = jnp.broadcast_to(enc(1), (pad, 4)).astype(jnp.uint32)
    w = jnp.concatenate([st.w_main, st.w_rem, ones])
    claim = (st.claim_main + lam * lam % Q_MOD * st.claim_rem) % Q_MOD
    blind = (st.blind_main + lam * st.blind_rem) % Q_MOD
    if keys.merged_len <= POW_TABLE_MAX_ELEMS:
        stmt = (keys.g_merged, None, keys.h_blind, a, b, blind, claim,
                (keys.g_merged_table, keys.h_merged, keys.h_merged_table, w))
    else:
        hh = group.g_pow(keys.h_merged, from_mont(FQ, w))
        stmt = (keys.g_merged, hh, keys.h_blind, a, b, blind, claim)
    (proof,) = ipa.pair_prove_many([stmt], transcript, rng)
    return proof


def _vp_k(k: int, u_relu: List[int], u_bit: List[int], qb: int) -> int:
    """v'_k = 1 + (k-1) beta(bin(Q-1), u_bit) (1 - u'')   (eq. 15)."""
    upp = u_relu[-1]
    e_bit = hexpand_point(u_bit)
    beta = e_bit[qb - 1]
    return (1 + (k - 1) * beta % Q_MOD * ((1 - upp) % Q_MOD)) % Q_MOD


def _h_weights(e_relu, e_bit):
    """1/e for e = e_relu (x) e_bit — the H-basis weights (Montgomery)."""
    e_full = mont_mul(FQ, e_relu[:, None, :], e_bit[None, :, :]).reshape(-1, 4)
    return batch_inv(FQ, e_full)


def _h_prime_basis(h_big, e_relu, e_bit):
    """H'_i = H_i^{1/e_i}, e = e_relu (x) e_bit (Algorithm 1 basis).

    Verifier-side only: the prover keeps the weights in exponent form
    (`ipa.pair_prove_many` accel statements) and never materializes H'."""
    return group.g_pow(h_big, from_mont(FQ, _h_weights(e_relu, e_bit)))


def transform_commitment(keys: ValidityKeys, com_b_ip: int, com_bq1_ip: int,
                         k: int, z: int, u_bit: List[int],
                         remainder: bool = False) -> jnp.ndarray:
    """Algorithm 1: transform com into a commitment of (B_k - z1, b) under
    the bases (G, H^{e^{o-1}}).  Returns the group element."""
    qb = keys.r_bits if remainder else keys.q_bits
    g_big = keys.g_r if remainder else keys.g_big
    h_big = keys.h_r if remainder else keys.h_big
    com = group.encode_group(com_b_ip)
    if not remainder and k is not None:
        com = group.g_mul(com, group.g_pow_int(group.encode_group(com_bq1_ip), k))
    # g^prod ^ {-z}
    gprod = group.tree_prod(g_big)
    com = group.g_mul(com, group.g_pow_int(gprod, (-z) % Q_MOD))
    # (h^prod_j)^{z^2 s_j / e_bit_j} column products
    e_bit = hexpand_point(u_bit)[:qb]
    s_vals = ([pow(2, j, Q_MOD) for j in range(qb)] if remainder
              else _s_q_vector(qb))
    n_rows = 2 * keys.ds
    h_cols = h_big.reshape(n_rows, qb, 4)
    for j in range(qb):
        colprod = group.tree_prod(h_cols[:, j])
        expo = (z * z % Q_MOD * s_vals[j] % Q_MOD
                * pow(e_bit[j], Q_MOD - 2, Q_MOD)) % Q_MOD
        expo = (expo + z) % Q_MOD            # + (h^prod)^z folded per column
        com = group.g_mul(com, group.g_pow_int(colprod, expo))
    return com


def verify_validity(keys: ValidityKeys, coms: ValidityCommitments,
                    v: int, v_q1: int, v_r: int, u_relu: List[int],
                    proof: ipa.IpaProof, transcript: Transcript) -> bool:
    """Standalone verifier for the merged validity IPA."""
    ctx = verify_statements(keys, coms, v, v_q1, v_r, u_relu, transcript)
    lam = transcript.challenge_int(b"zkrelu/lam", Q_MOD)
    com = group.g_mul(ctx.com_t, group.g_pow_int(ctx.com_tr, lam))
    claim = (ctx.claim_main + lam * lam % Q_MOD * ctx.claim_rem) % Q_MOD
    hh = jnp.concatenate([ctx.h_prime_main, ctx.h_prime_rem,
                          keys.h_merged[keys.n_main + keys.n_rem:]])
    return ipa.pair_verify_many(
        [(keys.g_merged, hh, keys.h_blind, com, claim, keys.merged_len)],
        [proof], transcript)
