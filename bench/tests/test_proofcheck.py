"""The benchmark's own proof check: it passes the program's sound proofs
and fails each planted fault.  On proofs recorded from the program on
the CPU (3-4-4-1 at T=1, 3-8-8-1 at T=2, batch 4), so no test proves."""
import json
import os

import pytest

from bench import control, proofcheck, spec
from bench.tests.layouts import program_layout

DATA = os.path.join(os.path.dirname(__file__), "data")
LABEL = b"zkdl/train"
RECORDED = ["3-4-4-1_t1", "3-8-8-1_t2"]


def _recorded(name):
    with open(os.path.join(DATA, f"proof_{name}.bin"), "rb") as f:
        raw = f.read()
    with open(os.path.join(DATA, f"layout_{name}.json")) as f:
        return raw, json.load(f)


@pytest.mark.parametrize("traffic", ["prove", "prove_t2"])
def test_stated_proof_layout(traffic):
    """The configuration's stated layout is the program's at the cell's
    geometry, and its wire length is the proof size the chip measured."""
    cell = spec.load_cell(f"autompg-dnn.{traffic}")
    t = cell.traffic["steps_per_proof"]
    stated = cell.config["proof_layout_by_steps_per_proof"][str(t)]
    assert stated == program_layout(cell.config, t)
    assert proofcheck.layout_bytes(stated) == {1: 2178, 2: 2705}[t]


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_layout_is_the_programs(name):
    widths, t = name.split("_t")
    config = dict(spec.load_cell("autompg-dnn.prove").config,
                  widths=[int(w) for w in widths.split("-")], batch=4)
    assert _recorded(name)[1] == program_layout(config, int(t))


def test_constants_and_transcript_match_the_program():
    from repro.core.transcript import Transcript
    from repro.field import FP, FQ

    assert (proofcheck.Q, proofcheck.P) == (FQ.modulus, FP.modulus)
    ours, theirs = proofcheck.Transcript(LABEL), Transcript(LABEL)
    ours.absorb(b"coms", [3, proofcheck.P - 2])
    theirs.absorb_ints(b"coms", [3, proofcheck.P - 2])
    assert [ours.challenge(b"u/%d" % i) for i in range(3)] == \
        theirs.challenge_ints(b"u", FQ.modulus, 3)


@pytest.mark.parametrize("name", RECORDED)
def test_sound_proof_passes(name):
    raw, lay = _recorded(name)
    assert proofcheck.check([raw], lay, LABEL, 16, 8) == {
        "proof_layout_mismatches": 0, "proof_elements_out_of_range": 0,
        "sumcheck_equations_failed": 0}


@pytest.mark.parametrize("fault", sorted(control.PROOF_FAULTS))
@pytest.mark.parametrize("name", RECORDED)
def test_planted_fault_fails(name, fault):
    raw, lay = _recorded(name)
    got = proofcheck.check([control.plant(raw, fault)], lay, LABEL, 16, 8)
    assert got[control.PROOF_FAULTS[fault][1]] > 0, got


@pytest.mark.parametrize("edit", ["truncated", "unreduced_scalar",
                                  "opening_altered", "wrong_label"])
def test_other_faults_fail(edit):
    from repro.core.pipeline.proofio import decode_proof, encode_proof

    raw, lay = _recorded("3-8-8-1_t2")
    label = LABEL
    if edit == "truncated":
        raw = raw[:-8]
    elif edit == "wrong_label":
        label = b"zkdl/other"
    else:
        proof = decode_proof(raw)
        if edit == "unreduced_scalar":
            proof.sc_gw[0].messages[0][2] += proofcheck.Q
        else:
            proof.openings["a6"] = (proof.openings["a6"] + 1) % proofcheck.Q
        raw = encode_proof(proof)
    got = proofcheck.check([raw], lay, label, 16, 8)
    assert sum(got.values()) > 0, got
