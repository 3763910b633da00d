"""Standalone tests for zkReLU auxiliary-input validity (Section 4.1)."""
import numpy as np
import pytest

from repro.field import FQ, modarith
from repro.core import zkrelu
from repro.core.mle import hexpand_point
from repro.core.transcript import Transcript

Q_MOD = FQ.modulus

DS = 8        # stacked aux length (power of 2)
QB = 8        # Q bits
RB = 4        # R bits


def make_aux(rng, ds=DS):
    zpp = rng.integers(0, 1 << (QB - 1), size=ds).astype(np.int64)
    gap = rng.integers(-(1 << (QB - 1)), 1 << (QB - 1), size=ds).astype(np.int64)
    bq = rng.integers(0, 2, size=ds).astype(np.int64)
    rz = rng.integers(0, 1 << RB, size=ds).astype(np.int64)
    rga = rng.integers(0, 1 << RB, size=ds).astype(np.int64)
    return zpp, gap, bq, rz, rga


def honest_claims(zpp, gap, bq, rz, rga, u_relu):
    """Host-side MLE evals: v, v_{Q-1}, v_r at u_relu = (u_star..., u'')."""
    ds = zpp.shape[0]
    u_star, upp = u_relu[:-1], u_relu[-1]
    e = hexpand_point(u_star)
    vz = sum(int(zpp[i]) * e[i] for i in range(ds)) % Q_MOD
    vg = sum(int(gap[i]) % Q_MOD * e[i] for i in range(ds)) % Q_MOD
    vq1 = sum(int(bq[i]) * e[i] for i in range(ds)) % Q_MOD
    vrz = sum(int(rz[i]) * e[i] for i in range(ds)) % Q_MOD
    vrga = sum(int(rga[i]) * e[i] for i in range(ds)) % Q_MOD
    v = ((1 - upp) * vz + upp * vg) % Q_MOD
    vr = ((1 - upp) * vrz + upp * vrga) % Q_MOD
    return v, vq1, vr


def coms_list(coms):
    return [coms.com_b_ip, coms.com_bq1, coms.com_bq1p, coms.com_br_ip]


def run_protocol(tamper=None):
    rng = np.random.default_rng(42)
    zpp, gap, bq, rz, rga = make_aux(rng)
    keys = zkrelu.make_validity_keys(DS, QB, RB)
    bits = zkrelu.build_aux_bits(zpp, gap, bq, rz, rga, QB, RB)
    if tamper == "bitflip":
        bits.b_mat[3, 2] ^= 1
    if tamper == "value":
        # commitments honest, but the prover's raw witness disagrees
        bits.zpp[3] ^= 4

    coms, blinds = zkrelu.commit_validity(keys, bits, rng)

    n_vars = DS.bit_length() - 1
    tp = Transcript(b"zkrelu-test")
    tp.absorb_ints(b"coms", coms_list(coms))
    u_relu = tp.challenge_ints(b"urelu", Q_MOD, n_vars + 1)
    v, vq1, vr = honest_claims(zpp, gap, bq, rz, rga, u_relu)
    tp.absorb_ints(b"claims", [v, vq1, vr])

    proof = zkrelu.prove_validity(keys, bits, blinds, u_relu, v, vq1, vr,
                                  tp, rng)

    tv = Transcript(b"zkrelu-test")
    tv.absorb_ints(b"coms", coms_list(coms))
    u_relu_v = tv.challenge_ints(b"urelu", Q_MOD, n_vars + 1)
    assert u_relu_v == u_relu
    tv.absorb_ints(b"claims", [v, vq1, vr])
    return zkrelu.verify_validity(keys, coms, v, vq1, vr,
                                  u_relu, proof, tv)


def test_validity_accepts_honest():
    assert run_protocol()


def test_validity_rejects_bitflip():
    assert not run_protocol(tamper="bitflip")


def test_validity_rejects_witness_value_flip():
    assert not run_protocol(tamper="value")


def test_validity_rejects_wrong_claim():
    rng = np.random.default_rng(1)
    zpp, gap, bq, rz, rga = make_aux(rng)
    keys = zkrelu.make_validity_keys(DS, QB, RB)
    bits = zkrelu.build_aux_bits(zpp, gap, bq, rz, rga, QB, RB)
    coms, blinds = zkrelu.commit_validity(keys, bits, rng)
    n_vars = DS.bit_length() - 1
    tp = Transcript(b"t2")
    u_relu = tp.challenge_ints(b"urelu", Q_MOD, n_vars + 1)
    v, vq1, vr = honest_claims(zpp, gap, bq, rz, rga, u_relu)
    v_bad = (v + 1) % Q_MOD
    tp.absorb_ints(b"claims", [v_bad, vq1, vr])
    proof = zkrelu.prove_validity(keys, bits, blinds, u_relu, v_bad, vq1, vr,
                                  tp, rng)
    tv = Transcript(b"t2")
    u2 = tv.challenge_ints(b"urelu", Q_MOD, n_vars + 1)
    tv.absorb_ints(b"claims", [v_bad, vq1, vr])
    assert not zkrelu.verify_validity(keys, coms, v_bad, vq1, vr,
                                      u2, proof, tv)


def test_cross_statement_swap_rejects():
    """Fold-in soundness: a prover that swaps the main/remainder slices
    inside the merged direct-sum IPA (proving the right claims against
    the wrong basis positions) must be rejected."""
    from repro.field import mont_mul
    from repro.core import group, ipa
    from repro.core.mle import enc
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    zpp, gap, bq, rz, rga = make_aux(rng)
    keys = zkrelu.make_validity_keys(DS, QB, RB)
    bits = zkrelu.build_aux_bits(zpp, gap, bq, rz, rga, QB, RB)
    coms, blinds = zkrelu.commit_validity(keys, bits, rng)

    n_vars = DS.bit_length() - 1
    tp = Transcript(b"swap")
    tp.absorb_ints(b"coms", coms_list(coms))
    u_relu = tp.challenge_ints(b"urelu", Q_MOD, n_vars + 1)
    v, vq1, vr = honest_claims(zpp, gap, bq, rz, rga, u_relu)
    tp.absorb_ints(b"claims", [v, vq1, vr])

    st = zkrelu.prove_statements(keys, bits, blinds, u_relu, v, vq1, vr, tp)
    lam = tp.challenge_int(b"zkrelu/lam", Q_MOD)
    lam_m = enc(lam)
    pad = keys.merged_len - keys.n_main - keys.n_rem
    zeros = jnp.zeros((pad, 4), dtype=jnp.uint32)
    # malicious layout: remainder witness into the main slice and vice
    # versa (padded/truncated to the slice widths), claims unchanged
    a_sw = jnp.concatenate([
        jnp.concatenate([st.a_rem] * (keys.n_main // keys.n_rem)),
        mont_mul(FQ, st.a_main[:keys.n_rem], lam_m[None]), zeros])
    b_sw = jnp.concatenate([
        jnp.concatenate([st.b_rem] * (keys.n_main // keys.n_rem)),
        mont_mul(FQ, st.b_main[:keys.n_rem], lam_m[None]), zeros])
    ones = jnp.broadcast_to(enc(1), (pad, 4)).astype(jnp.uint32)
    w = jnp.concatenate([st.w_main, st.w_rem, ones])
    claim = (st.claim_main + lam * lam % Q_MOD * st.claim_rem) % Q_MOD
    blind = (st.blind_main + lam * st.blind_rem) % Q_MOD
    stmt = (keys.g_merged, None, keys.h_blind, a_sw, b_sw, blind, claim,
            (keys.g_merged_table, keys.h_merged, keys.h_merged_table, w))
    (proof,) = ipa.pair_prove_many([stmt], tp, rng)

    tv = Transcript(b"swap")
    tv.absorb_ints(b"coms", coms_list(coms))
    u2 = tv.challenge_ints(b"urelu", Q_MOD, n_vars + 1)
    tv.absorb_ints(b"claims", [v, vq1, vr])
    assert not zkrelu.verify_validity(keys, coms, v, vq1, vr, u2, proof, tv)


def test_bits_roundtrip():
    rng = np.random.default_rng(2)
    v = rng.integers(-(1 << 7), 1 << 7, size=32).astype(np.int64)
    b = zkrelu.bits_signed(v, 8)
    rec = sum(b[:, j].astype(np.int64) << j for j in range(7)) - (b[:, 7].astype(np.int64) << 7)
    assert (rec == v).all()
    u = rng.integers(0, 1 << 7, size=32).astype(np.int64)
    bu = zkrelu.bits_unsigned(u, 7)
    rec_u = sum(bu[:, j].astype(np.int64) << j for j in range(7))
    assert (rec_u == u).all()


def test_statement_weights_invert_e():
    """prove_statements' H-basis weights are 1/e, element by element, for
    e = e_relu (x) e_bit (and e_relu (x) e_bit_r), at a geometry where
    the main statement's inversion spans several rows of lanes."""
    from repro.field import mont_mul
    from repro.core.mle import expand_point

    ds, qb, rb = 512, 16, 8
    assert 2 * ds * qb > modarith._INV_LANES
    rng = np.random.default_rng(11)
    keys = zkrelu.make_validity_keys(ds, qb, rb)
    bits = zkrelu.build_aux_bits(
        rng.integers(0, 1 << (qb - 1), size=ds),
        rng.integers(-(1 << (qb - 1)), 1 << (qb - 1), size=ds),
        rng.integers(0, 2, size=ds), rng.integers(0, 1 << rb, size=ds),
        rng.integers(0, 1 << rb, size=ds), qb, rb)
    blinds = zkrelu.ValidityBlinds(*(int(rng.integers(0, 1 << 60))
                                     for _ in range(4)))
    tp, tv = Transcript(b"weights"), Transcript(b"weights")
    n_vars = ds.bit_length()
    u_relu = tp.challenge_ints(b"urelu", Q_MOD, n_vars)
    tv.challenge_ints(b"urelu", Q_MOD, n_vars)
    st = zkrelu.prove_statements(keys, bits, blinds, u_relu, 1, 2, 3, tp)

    # the same challenge draws, in prove_statements' order
    tv.challenge_int(b"zkrelu/k", Q_MOD)
    u_bit = tv.challenge_ints(b"zkrelu/ubit", Q_MOD, (qb - 1).bit_length())
    tv.challenge_int(b"zkrelu/z", Q_MOD)
    u_bit_r = tv.challenge_ints(b"zkrelu/ubitr", Q_MOD,
                                (rb - 1).bit_length())
    e_relu = expand_point(u_relu)
    one = np.asarray(FQ.one)
    for w, u, width in ((st.w_main, u_bit, qb), (st.w_rem, u_bit_r, rb)):
        e = mont_mul(FQ, e_relu[:, None, :],
                     expand_point(u)[:width][None, :, :]).reshape(-1, 4)
        assert w.shape == e.shape == (2 * ds * width, 4)
        prod = np.asarray(mont_mul(FQ, w, e))
        assert (prod == one).all(), np.argwhere((prod != one).any(-1))[:5]
