"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e by ``record_trace.py`` (``fixtures/``)."""

from pathlib import Path

import pytest

from benchmarks.chip import trace as tr

FIXTURES = Path(__file__).parent / "fixtures"


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def test_union_and_gaps_clip_to_the_window():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 20, 10), ev("d", 35, 20)]
    assert tr.merged(evs, 0, 40) == [(0, 15), (20, 30), (35, 40)]
    assert tr.gaps(evs, 0, 40) == [(15, 20), (30, 35)]
    assert tr.covered_ns(evs, 0, 40) == 30
    assert tr.gaps(evs, 2, 12) == []
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_clock_shift_puts_each_program_after_its_dispatch():
    spans = [ev("bench.decode", 100, 5), ev("bench.fetch", 105, 50),
             ev("bench.decode", 200, 5)]
    mods = [ev("jit_f(1)", 90, 40), ev("jit_f(1)", 196, 40)]
    assert tr.clock_shift(mods, spans) == 10
    with pytest.raises(ValueError, match="dispatch spans"):
        tr.clock_shift(mods[:1], spans)               # counts differ
    assert tr.clock_shift([ev("jit_f(1)", 150, 1), ev("jit_f(1)", 250, 1)],
                          spans) == 0                 # never negative


def test_names():
    op = ev("%checkpoint.1 = bf16[4,512]{1,0} custom-call(%a), x=1", 0, 1)
    assert op.op == "checkpoint.1"
    assert ev("jit_decode_step(1234)", 0, 1).module == "jit_decode_step"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    trace = tr.Trace(
        ops={"/device:TPU:0": [ev("%a = x", 0, 10), ev("%b = x", 30, 10)]},
        modules={"/device:TPU:0": [ev("jit_f(1)", 0, 10),
                                   ev("jit_f(1)", 30, 10)]},
        spans=[ev("bench.window", 0, 50), ev("bench.decode", 0, 1),
               ev("bench.fetch", 8, 20), ev("bench.decode", 30, 1)])
    s = tr.summarize(trace)
    assert s.shift_ns == 0
    assert s.window_s == pytest.approx(50e-9)
    assert s.busy_s == pytest.approx(20e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.idle_gaps == [("bench.fetch", pytest.approx(20e-9)),
                           (tr.NO_SPAN, pytest.approx(10e-9))]
    assert s.module("jit_f") == (pytest.approx(20e-9), 2)
    assert s.kernel("jit_f", {"b"}) == (pytest.approx(10e-9), 1)


@pytest.fixture(scope="module")
def small():
    """Three steps of a program holding the flash kernel, on one v5e, with
    a 20 ms sleep in the second step's fetch (``record_trace.py``)."""
    return (tr.summarize(tr.load(str(FIXTURES / "small_trace.xplane.pb"))),
            (FIXTURES / "small_trace.hlo.txt").read_text())


def test_recorded_trace_window_busy_and_shift(small):
    s, _ = small
    # read by hand: bench.window spans 25.387919 ms; the first program
    # starts 0.816 ms and the third 0.945 ms before its dispatch span.
    assert s.window_s == pytest.approx(0.025387919)
    assert s.shift_ns == pytest.approx(945214.0)
    assert s.busy_s == pytest.approx(0.000387405)
    assert 0.98 < s.idle_share < 0.99
    assert s.module("jit_step") == (pytest.approx(0.000388282), 3)


def test_recorded_trace_finds_the_kernel_by_its_mosaic_name(small):
    s, hlo = small
    ops = tr.mosaic_ops(hlo, "_fa_kernel")
    assert ops == {"step.1"}
    assert tr.mosaic_ops(hlo, "_no_such_kernel") == set()
    seconds, events = s.kernel("jit_step", ops)
    assert events == 3 and seconds == pytest.approx(0.000104865)
    assert s.kernel("jit_other", ops) == (0.0, 0)


def test_recorded_trace_breakdown(small):
    s, _ = small
    names = [n for n, _ in s.device_ops]
    assert names[:2] == ["jit_step/fusion.1", "jit_step/step.1"]
    assert len(s.device_ops) == len(s.idle_gaps) == 10
    # the sleep: the longest idle gap, while the host was in bench.fetch
    assert s.idle_gaps[0][0] == "bench.fetch"
    assert 0.020 < s.idle_gaps[0][1] < 0.023
