"""The recorded chip trace's reduction, number for number: busy time,
the traced window, time by program and the idle gaps' seconds.  A
change to how gaps are named must leave every one of them as it is."""
import json
import os

import pytest

from bench import devtrace

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")

PROGRAM_S = {
    "reshape": 4.553e-05, "broadcast_in_dim": 8.9145e-05,
    "concatenate": 0.000113102, "from_mont": 3.1302e-05,
    "convert_element_type": 1.4899e-05, "_msm_many_impl": 0.036122945,
    "_where": 9.3544e-05, "tree_prod": 0.018067028, "g_pow": 0.00392652,
    "mont_mul": 0.001605328, "add": 2.002e-06, "select_n": 1.875e-06,
    "_broadcast_arrays": 1.407e-06, "gather": 0.000570424}


@pytest.fixture(scope="module")
def summary():
    with open(DATA) as f:
        rec = json.load(f)
    return devtrace.reduce([tuple(e) for e in rec["spans"] + rec["device"]])


def test_busy_and_window(summary):
    assert summary.busy_s == pytest.approx(0.060685051, abs=1e-12)
    assert summary.window_s == pytest.approx(0.234541043, abs=1e-12)
    assert summary.truncated


def test_time_by_program(summary):
    assert summary.program_s == pytest.approx(PROGRAM_S, abs=1e-12)


def test_idle_gap_seconds(summary):
    secs = [s for _, s in summary.gaps]
    assert len(secs) == 400
    assert sum(secs) == pytest.approx(0.173855992, abs=1e-12)
    assert max(secs) == pytest.approx(0.028836861, abs=1e-12)
    assert sum(secs) == pytest.approx(summary.window_s - summary.busy_s,
                                      abs=1e-12)
