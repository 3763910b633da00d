"""The trace reduction: busy union, idle share, time by program, and
idle gaps named by the benchmark's host spans."""
import json
import os

import pytest

from bench import devtrace

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _synthetic():
    ms = 1_000_000
    return [
        (HOST, "main", "bench/window", 0, 100 * ms),
        (HOST, "main", "bench/submit", 0, 5 * ms),
        (HOST, "main", "bench/wait_commit", 5 * ms, 95 * ms),
        (HOST, "main", "bench/train_step", 40 * ms, 15 * ms),
        (HOST, "worker", "PjitFunction(other)", 10 * ms, 1 * ms),
        (DEV, "XLA Modules", "jit__msm_many_impl(7)", 10 * ms, 30 * ms),
        (DEV, "XLA Ops", "fusion.1", 10 * ms, 20 * ms),
        (DEV, "XLA Ops", "fusion.2", 25 * ms, 15 * ms),   # overlaps .1
        (DEV, "XLA Modules", "jit_mont_mul(3)", 60 * ms, 20 * ms),
        (DEV, "XLA Ops", "fusion.3", 60 * ms, 20 * ms),
        (DEV, "XLA Ops", "fusion.4", 95 * ms, 10 * ms),   # past the window
    ]


def test_reduce_by_hand():
    s = devtrace.reduce(_synthetic())
    assert s.window_s == pytest.approx(0.100) and not s.truncated
    # busy: the program runs [10, 40) and [60, 80); op events not read
    assert s.busy_s == pytest.approx(0.050)
    assert s.idle_share == pytest.approx(0.5)
    assert s.program_s == pytest.approx({"_msm_many_impl": 0.030,
                                         "mont_mul": 0.020})
    gaps = dict(devtrace.top(s.gaps))
    # [0,10): wait_commit open at its midpoint; [40,60): train_step, the
    # innermost span, after the msm; [80,100): after mont_mul
    assert gaps == pytest.approx({"wait_commit": 0.010,
                                  "train_step after _msm_many_impl": 0.020,
                                  "wait_commit after mont_mul": 0.020})


def test_truncated_trace_covers_what_was_kept():
    """Device events that stop long before the window ends, or an op
    line at the profiler's buffer size, mean it dropped the later device
    events: only the part they cover counts as traced."""
    ev = [e if e[2] != "bench/window" else (HOST, "main", e[2], 0,
                                            3_000_000_000)
          for e in _synthetic()]
    s = devtrace.reduce(ev, {DEV: 4})       # the tail is 97% idle
    assert s.truncated and s.window_s == pytest.approx(0.080)
    assert s.busy_s == pytest.approx(0.050)
    assert not devtrace.reduce(_synthetic(), {DEV: 4}).truncated
    assert devtrace.reduce(_synthetic(), {
        DEV: devtrace.OPS_BUFFER_EVENTS}).truncated


def test_trace_of_the_first_seconds():
    """A mix that traces only the first seconds of the window: the
    ``bench/traced`` span is the traced window, and it is partial."""
    ms = 1_000_000
    s = devtrace.reduce(_synthetic() + [(HOST, "main", "bench/traced", 0,
                                          50 * ms)])
    assert s.truncated and s.window_s == pytest.approx(0.050)
    assert s.busy_s == pytest.approx(0.030)     # [10, 40)


def test_no_device_events_reads_nothing():
    assert devtrace.reduce([e for e in _synthetic() if e[0] == HOST]) is None


def test_program_name():
    assert devtrace.program_name("jit__fold_pair(12)") == "_fold_pair"
    assert devtrace.program_name("jit_mont_mul") == "mont_mul"


def test_recorded_chip_trace():
    """A few milliseconds of a traced run on a TPU v5e, as recorded:
    the reduction reads busy time under the window and names programs
    as the executable cache's functions are named."""
    with open(DATA) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["spans"] + rec["device"]]
    s = devtrace.reduce(events)
    assert s is not None and s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.program_s and all(v > 0 for v in s.program_s.values())
    assert sum(s.program_s.values()) >= s.busy_s * 0.5
    assert set(rec["expect_programs"]) <= set(s.program_s)
