"""Aggregated proof pipeline tests: T=2 prove/verify roundtrip plus
tamper rejections (flipped aux bit, wrong step count, stale transcript,
cross-step claim splicing), the heterogeneous pyramid roundtrip, and the
golden-digest pins that freeze the uniform-graph transcript of the v3
merged one-IPA opening protocol (data folds + zkReLU validity in a
single pair IPA)."""
import copy
import hashlib

import numpy as np
import pytest

from repro.core.quantfc import (QuantConfig, synthetic_sgd_trajectory,
                                synthetic_sgd_trajectory_widths)
from repro.core.pipeline import (PipelineConfig, ProofSession, make_keys,
                                 prove_session, verify_session)

CFG = PipelineConfig(n_layers=2, batch=2, width=4, q_bits=16, r_bits=4,
                     n_steps=2)
QC = QuantConfig(q_bits=CFG.q_bits, r_bits=CFG.r_bits)

# pyramid MLP: 4 distinct layer widths, multi-bucket in every family
HET_WIDTHS = (16, 8, 4, 2)
HET_CFG = PipelineConfig(n_layers=3, batch=2, widths=HET_WIDTHS,
                         q_bits=16, r_bits=4, n_steps=2)


def make_step_witnesses(seed=0, n_steps=CFG.n_steps, cfg=CFG):
    """n_steps consecutive batch updates with real integer SGD between."""
    return synthetic_sgd_trajectory(n_steps, cfg.n_layers, cfg.batch,
                                    cfg.width, QC, seed=seed)


@pytest.fixture(scope="module")
def keys():
    return make_keys(CFG)


@pytest.fixture(scope="module")
def proof(keys):
    return prove_session(keys, make_step_witnesses(seed=1),
                         np.random.default_rng(1))


def test_aggregated_roundtrip_accepts(keys, proof):
    trace = []
    assert verify_session(keys, proof, trace=trace), trace
    assert proof.n_steps == CFG.n_steps
    # one aggregated transcript: a single set of commitments/IPAs covers
    # both steps, so the proof stays well under 2x a single-step proof
    assert proof.size_bytes() < 20_000
    assert len(proof.coms.x) == CFG.n_steps * CFG.batch


def test_rejects_flipped_aux_bit(keys):
    wits = make_step_witnesses(seed=2)
    wits[1].b[0][0, 0] ^= 1          # flip a ReLU sign bit in step 1
    bad = prove_session(keys, wits, np.random.default_rng(2))
    assert not verify_session(keys, bad)


def test_rejects_tampered_step1_gradient(keys):
    wits = make_step_witnesses(seed=3)
    wits[1].gw[0][0, 0] += 1         # forged gradient in the SECOND step
    bad = prove_session(keys, wits, np.random.default_rng(3))
    assert not verify_session(keys, bad)


def test_rejects_wrong_step_count(keys):
    session = ProofSession(keys, np.random.default_rng(4))
    session.add_step(make_step_witnesses(seed=4, n_steps=1)[0])
    with pytest.raises(ValueError, match="step"):
        session.prove()             # only 1 of 2 steps queued

    wits = make_step_witnesses(seed=5, n_steps=3)
    full = ProofSession(keys, np.random.default_rng(5))
    full.add_step(wits[0])
    full.add_step(wits[1])
    with pytest.raises(ValueError, match="already holds"):
        full.add_step(wits[2])      # session window is full


def test_rejects_step_count_tamper(keys, proof):
    bad = copy.deepcopy(proof)
    bad.n_steps = 1                 # claim fewer steps than proven
    trace = []
    assert not verify_session(keys, bad, trace=trace)
    assert trace == ["step-count"]


def test_rejects_stale_transcript(keys, proof):
    # same proof replayed against a different session label: every
    # challenge diverges, so the first sumcheck must already fail
    assert not verify_session(keys, proof, label=b"zkdl/other-session")


def test_rejects_cross_step_claim_swap(keys, proof):
    bad = copy.deepcopy(proof)
    bad.openings["zL_b/0"], bad.openings["zL_b/1"] = \
        bad.openings["zL_b/1"], bad.openings["zL_b/0"]
    assert not verify_session(keys, bad)


def test_rejects_tampered_opening(keys, proof):
    bad = copy.deepcopy(proof)
    bad.openings["a1"] = (bad.openings["a1"] + 1) % (2**61)
    assert not verify_session(keys, bad)


# ---------------------------------------------------------------------------
# Heterogeneous layer graph (FAC4DNN over a pyramid MLP)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def het_keys():
    return make_keys(HET_CFG)


def test_heterogeneous_pyramid_roundtrip(het_keys):
    """A pyramid MLP with 4 distinct widths proves T=2 steps in ONE
    aggregated session; every matmul family splits into shape buckets."""
    buckets = HET_CFG.graph.buckets
    assert len(buckets["fwd"]) == 3       # inner dims 16 / 8 / 4
    assert len(buckets["bwd"]) == 2       # inner dims 4 / 2
    assert len(buckets["gw"]) == 1        # inner dim = batch, always one
    wits = synthetic_sgd_trajectory_widths(2, HET_WIDTHS, HET_CFG.batch,
                                           QC, seed=11)
    proof = prove_session(het_keys, wits, np.random.default_rng(11))
    trace = []
    assert verify_session(het_keys, proof, trace=trace), trace
    assert len(proof.sc_fwd) == 3 and len(proof.fwd_claims) == 3
    assert len(proof.sc_bwd) == 2 and len(proof.gw_claims) == 0


def test_heterogeneous_rejects_tampered_witness(het_keys):
    wits = synthetic_sgd_trajectory_widths(2, HET_WIDTHS, HET_CFG.batch,
                                           QC, seed=12)
    wits[1].gw[1][0, 0] += 1              # forged gradient, narrow layer
    bad = prove_session(het_keys, wits, np.random.default_rng(12))
    assert not verify_session(het_keys, bad)

    wits = synthetic_sgd_trajectory_widths(2, HET_WIDTHS, HET_CFG.batch,
                                           QC, seed=13)
    wits[0].b[2][0, 0] ^= 1               # flipped ReLU bit, widest slot
    bad = prove_session(het_keys, wits, np.random.default_rng(13))
    assert not verify_session(het_keys, bad)


def test_heterogeneous_rejects_claim_split_tamper(het_keys):
    """Moving mass between two buckets' split claims keeps the sum (so
    the split check passes) but must break a bucket sumcheck."""
    wits = synthetic_sgd_trajectory_widths(2, HET_WIDTHS, HET_CFG.batch,
                                           QC, seed=14)
    proof = prove_session(het_keys, wits, np.random.default_rng(14))
    bad = copy.deepcopy(proof)
    bad.fwd_claims[0] = (bad.fwd_claims[0] + 1) % (2**61 - 1)
    bad.fwd_claims[1] = (bad.fwd_claims[1] - 1) % (2**61 - 1)
    assert not verify_session(het_keys, bad)


def check_stacking_invariants(widths, n_steps, seed, batch=2):
    """Graph-stacking invariants (shared with the hypothesis twin in
    test_property_based.py): slot maps are bijections onto their padded
    axes, every occupied block equals its node's zero-padded tensor, and
    every element outside the occupied blocks is exactly zero."""
    from repro.core.pipeline.witness import pad2d, stack_witnesses

    cfg = PipelineConfig(n_layers=len(widths) - 1, batch=batch,
                         widths=tuple(widths), q_bits=16, r_bits=4,
                         n_steps=n_steps)
    wits = synthetic_sgd_trajectory_widths(n_steps, widths, batch, QC,
                                           seed=seed)
    sw = stack_witnesses(wits, cfg)
    g = cfg.graph

    slots = [cfg.slot(t, i) for t in range(cfg.t_pad)
             for i in range(cfg.l_pad)]
    assert sorted(slots) == list(range(cfg.s_pad))
    wslots = [cfg.wslot(t, i) for t in range(cfg.t_pad)
              for i in range(cfg.lw_pad)]
    assert sorted(wslots) == list(range(cfg.sw_pad))

    zpp = sw.zpp_s.reshape(cfg.t_pad, cfg.l_pad, cfg.d_elem)
    occupied = np.zeros_like(zpp, dtype=bool)
    for t in range(n_steps):
        for i, node in enumerate(g.aux_nodes):
            blk = zpp[t, i, : node.elem_pad]
            want = pad2d(wits[t].zpp[node.layer - 1], node.rows_pad,
                         node.cols_pad).reshape(-1)
            np.testing.assert_array_equal(blk, want)
            occupied[t, i, : node.elem_pad] = True
    assert (zpp[~occupied] == 0).all()

    w_s = sw.w_s.reshape(cfg.t_pad, cfg.lw_pad, cfg.w_elem)
    occupied_w = np.zeros_like(w_s, dtype=bool)
    for t in range(n_steps):
        for i, node in enumerate(g.weight_nodes):
            rp, cp = g.weight_shape(node)
            blk = w_s[t, i, : rp * cp]
            want = pad2d(wits[t].w[node.layer - 1], rp, cp).reshape(-1)
            np.testing.assert_array_equal(blk, want)
            occupied_w[t, i, : rp * cp] = True
    assert (w_s[~occupied_w] == 0).all()


@pytest.mark.parametrize("widths,n_steps", [
    ((16, 8, 4, 2), 2),       # pyramid, multi-bucket
    ((6, 4, 3, 2), 1),        # non-pow2: per-dimension padding
    ((4, 4, 4), 3),           # uniform, padded step axis
])
def test_stacking_invariants(widths, n_steps):
    check_stacking_invariants(widths, n_steps, seed=21)


def test_non_pow2_widths_roundtrip():
    """Non-power-of-two widths pad per dimension inside each slot."""
    widths = (6, 4, 3, 2)
    cfg = PipelineConfig(n_layers=3, batch=2, widths=widths, q_bits=16,
                         r_bits=4, n_steps=1)
    keys = make_keys(cfg)
    wits = synthetic_sgd_trajectory_widths(1, widths, cfg.batch, QC,
                                           seed=15)
    proof = prove_session(keys, wits, np.random.default_rng(15))
    trace = []
    assert verify_session(keys, proof, trace=trace), trace


# ---------------------------------------------------------------------------
# Uniform graphs must reproduce the seed protocol bit-for-bit
# ---------------------------------------------------------------------------

def _flat_ints(x):
    if isinstance(x, (int, np.integer)):
        return [int(x)]
    out = []
    for v in x:
        out.extend(_flat_ints(v))
    return out


def proof_digest(proof):
    """Canonical digest of every scalar in an AggregatedProof."""
    h = hashlib.sha256()

    def absorb(tag, ints):
        h.update(tag.encode())
        for v in _flat_ints(ints):
            h.update(int(v).to_bytes(16, "little"))

    absorb("coms", proof.coms.as_ints())
    absorb("openings", [v for _, v in sorted(proof.openings.items())])
    for fam in ("fwd", "bwd", "gw"):
        for sc in getattr(proof, "sc_" + fam):
            absorb(fam + "/msgs", sc.messages)
        absorb(fam + "/finals", getattr(proof, fam + "_finals"))
    absorb("anchor/msgs", proof.sc_anchor.messages)
    absorb("anchor/finals", proof.anchor_finals)
    absorb("ipa/agg", [proof.ipa_agg.ls, proof.ipa_agg.rs,
                       proof.ipa_agg.sigma])
    return h.hexdigest()


# recorded for the v3 merged one-IPA opening protocol (layers=2,
# batch=2, width=4, q=16, r=4, trajectory seed=7, prover rng seed=7).
# History: originally recorded from the pre-graph-IR pipeline and kept
# bit-identical through the IR / batching / serialization refactors;
# re-recorded for PR 5 (unified commitment-key layout + direct-sum
# aggregated opening) and again for PR 6, which folds both zkReLU
# validity statements into the single pair IPA over the merged key
# (fresh bq generators, com_bq1 published, validity challenges drawn
# before rho/agg) -- the transcript changes by design; both pipelines
# verified the same seeded trajectories before re-recording
GOLDEN = {
    1: "25adee334f3087831ba4588932c3f6d5a38bfbb816b888a42f9504a94769a5c0",
    2: "d098df1fea85a092589dabc3701e040eff473b17db571331701ecb7ff99e6fef",
}


@pytest.mark.parametrize("T", [1, 2])
def test_uniform_graph_transcript_pinned(T):
    """Any unintended transcript / witness / rng change must show up as
    a digest mismatch; intended protocol changes re-record GOLDEN (and
    the byte goldens in test_proofio.py) explicitly."""
    cfg = PipelineConfig(n_layers=2, batch=2, width=4, q_bits=16,
                         r_bits=4, n_steps=T)
    keys = make_keys(cfg)
    wits = synthetic_sgd_trajectory(T, 2, 2, 4, QC, seed=7)
    proof = prove_session(keys, wits, np.random.default_rng(7))
    assert proof_digest(proof) == GOLDEN[T]
    assert proof.fwd_claims == []         # single bucket: split implicit


@pytest.mark.parametrize("fold_backend", ["jnp", "pallas"])
def test_v3_bytes_invariant_to_compile_path(fold_backend):
    """The compile-O(1) prover (scan-shaped sumcheck bodies + masked IPA
    ladder) and the legacy per-shape unrolled prover must emit
    byte-identical serialized v3 proofs, under both fold backends, and
    both must reproduce the pinned golden digest — the whole
    depth/T-invariant compile machinery is transcript-invisible."""
    from repro.core import ipa, mle, sumcheck
    from repro.core.pipeline import encode_proof

    cfg = PipelineConfig(n_layers=2, batch=2, width=4, q_bits=16,
                         r_bits=4, n_steps=1)
    keys = make_keys(cfg)

    def run():
        wits = synthetic_sgd_trajectory(1, 2, 2, 4, QC, seed=7)
        return prove_session(keys, wits, np.random.default_rng(7))

    try:
        mle.set_fold_backend(fold_backend)
        sumcheck.set_scan_mode("scan")
        ipa.set_round_mode("ladder")
        scan_proof = run()
        sumcheck.set_scan_mode("unrolled")
        ipa.set_round_mode("unrolled")
        unrolled_proof = run()
    finally:
        mle.set_fold_backend(None)
        sumcheck.set_scan_mode(None)
        ipa.set_round_mode(None)
    assert encode_proof(scan_proof) == encode_proof(unrolled_proof)
    assert proof_digest(scan_proof) == GOLDEN[1]
    assert verify_session(keys, scan_proof)


def test_uniform_stacking_matches_seed_layout():
    """Graph-driven stacking reproduces the seed's positional formula
    flat[(t * l_pad + (l-1)) * B*d + row * d + col] exactly."""
    from repro.core.pipeline.witness import stack_witnesses

    wits = synthetic_sgd_trajectory(CFG.n_steps, CFG.n_layers, CFG.batch,
                                    CFG.width, QC, seed=9)
    sw = stack_witnesses(wits, CFG)
    B, d = CFG.batch, CFG.width
    for name, per_layer in (("zpp_s", lambda w: w.zpp),
                            ("bq_s", lambda w: w.b),
                            ("rz_s", lambda w: w.rz),
                            ("gap_s", lambda w: w.gap),
                            ("rga_s", lambda w: w.rga)):
        seed_flat = np.zeros((CFG.t_pad, CFG.l_pad, B * d), dtype=np.int64)
        for t, w in enumerate(wits):
            for i, tensor in enumerate(per_layer(w)):
                seed_flat[t, i] = tensor.reshape(-1)
        np.testing.assert_array_equal(getattr(sw, name),
                                      seed_flat.reshape(-1), err_msg=name)
    seed_w = np.zeros((CFG.t_pad, CFG.l_pad, d * d), dtype=np.int64)
    for t, w in enumerate(wits):
        for i in range(CFG.n_layers):
            seed_w[t, i] = w.w[i].reshape(-1)
    np.testing.assert_array_equal(sw.w_s, seed_w.reshape(-1))


def test_batched_commit_phase_matches_sequential_commits(keys):
    """The commit phase's two msm_many dispatches must reproduce the
    per-tensor `pedersen.commit` elements exactly (same blinds), so
    batching can never alter a transcript byte."""
    from repro.core import group, pedersen
    from repro.core.pipeline.session import SessionProver
    from repro.core.pipeline.tables import enc_tensor
    from repro.core.pipeline.witness import stack_witnesses

    sw = stack_witnesses(make_step_witnesses(seed=31), CFG)
    prover = SessionProver(keys, np.random.default_rng(31))
    coms = prover.commit(sw)
    tabs, blinds = prover.tabs, prover.blinds
    seq = {name: pedersen.commit(keys.slot_keys[name], tabs.tabs[name],
                                 blinds[name])
           for name in ("y", "w", "gw", "zpp", "rz", "gap", "rga")}
    for name, el in seq.items():
        assert getattr(coms, name) == group.decode_group(el), name
    for ci, x, xb in zip(coms.x, sw.x, prover.x_blinds):
        assert ci == group.decode_group(
            pedersen.commit(keys.kx, enc_tensor(x), xb))


def test_prover_phase_profile_accounts_for_total(keys):
    """The per-phase profiler must cover ~all of prove() wall clock."""
    session = ProofSession(keys, np.random.default_rng(33))
    for w in make_step_witnesses(seed=33):
        session.add_step(w)
    session.prove()
    prof = session.last_profile
    assert prof is not None and prof.total_s > 0
    assert set(prof.phases_s) >= {"stack", "commit", "challenges",
                                  "matmul", "anchor", "openings"}
    assert prof.accounted_s <= prof.total_s * 1.001 + 1e-6
    assert prof.accounted_s >= prof.total_s * 0.9


@pytest.fixture(scope="module")
def profiled(keys):
    """The span records of one prove."""
    session = ProofSession(keys, np.random.default_rng(35))
    for w in make_step_witnesses(seed=35):
        session.add_step(w)
    session.prove()
    return session.last_profile


def test_prove_records_every_phase_and_sub_phase(profiled):
    """`prove` holds every phase; `openings` holds every sub-phase, the
    inversion's wait right after the validity tables; one window id."""
    from repro.core.pipeline.profile import PHASES, SUB_PHASES

    recs = profiled.records
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    assert set(by_name) == {"prove", *PHASES, *SUB_PHASES}
    (top,) = by_name["prove"]
    assert top["parent"] is None
    for names, parent in ((PHASES, "prove"), (SUB_PHASES, "openings")):
        for name in names:
            for r in by_name[name]:
                assert recs[r["parent"]]["name"] == parent, name
    assert len(by_name["claim-combine"]) == 2
    (valid,), (inverse,) = by_name["zkrelu-validity"], by_name["zkrelu-inverse"]
    assert valid["end"] <= inverse["start"]
    assert {r["window"] for r in recs} == {top["window"]}


def test_no_span_self_time_is_negative(profiled):
    recs = profiled.records
    inner = [0.0] * len(recs)
    for r in recs:
        if r["parent"] is not None:
            inner[r["parent"]] += r["end"] - r["start"]
    for r, s in zip(recs, inner):
        assert r["end"] - r["start"] - s >= -1e-9, r["name"]


def test_host_reads_count_every_decode_and_follow_the_geometry(keys):
    """Every call into the three decode functions books one host read,
    and two proves of one geometry read the same number of times."""
    import sys

    from repro.core import group
    from repro.field import modarith

    codes = {modarith.decode.__code__, group.decode_group.__code__,
             group.decode_group_many.__code__}
    calls = []

    def hook(frame, event, arg):
        if event == "call" and frame.f_code in codes:
            calls.append(frame.f_code.co_name)

    reads = []
    for seed in (36, 37):
        session = ProofSession(keys, np.random.default_rng(seed))
        for w in make_step_witnesses(seed=seed):
            session.add_step(w)
        calls.clear()
        sys.setprofile(hook)
        try:
            session.prove()
        finally:
            sys.setprofile(None)
        reads.append(sum(r["host_reads"]
                         for r in session.last_profile.records))
        assert reads[-1] == len(calls) > 0
    assert reads[0] == reads[1]


def test_prover_service_keeps_each_committed_windows_spans(tmp_path):
    """Thread isolation: one `window_profiles` entry per committed
    window (the prove, `encode`, `persist`, all under the window's id),
    and the warm-up's records in `warm_profile`."""
    from repro.core.pipeline import build_fcnn_graph
    from repro.launch import serve
    from repro.launch.serve import ProverService

    widths, T = (4, 4, 4), 2
    service = ProverService(build_fcnn_graph(widths, batch=2), QC,
                            n_steps=T, out_dir=str(tmp_path), rng_seed=5)
    service.start(warm=True)
    for wit in synthetic_sgd_trajectory_widths(2 * T, widths, 2, QC,
                                               seed=5):
        service.submit(wit)
    service.close()
    committed = {w for w, rec in serve.read_manifest(str(tmp_path)).items()
                 if rec["status"] == serve.COMMITTED}
    assert committed == {0, 1} == set(service.window_profiles)
    for w, recs in service.window_profiles.items():
        top = [r["name"] for r in recs if r["parent"] is None]
        assert top == ["prove", "encode", "persist"]
        assert {r["window"] for r in recs} == {w}
    warm = service.warm_profile
    names = [r["name"] for r in warm]
    assert names[0] == "keygen" and "warm-verify" in names
    (i_warm,) = [i for i, r in enumerate(warm) if r["name"] == "warm"]
    for name in ("prove", "warm-verify"):
        (r,) = [r for r in warm if r["name"] == name]
        assert r["parent"] == i_warm
