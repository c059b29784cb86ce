"""``batch_build_ms``, ``dispatch_ms`` and ``spans.per_round_stat`` on a
trace built by hand.

Two traced rounds; times are ns on the trace's clock.  The program's
spans carry the stat ``round``; spans of the same names that enclose
them and carry no stats (as the harness once wrote) are left out.

  round 0  batch build  private 10,000,000-50,000,000, public
                        45,000,000-60,000,000 (5 ms overlap): 50 ms
           dispatch     60,000,000-63,000,000: 3 ms
  round 1  batch build  private 400,000,000-442,000,000, public
                        450,000,000-460,000,000: 42 + 10 = 52 ms
           dispatch     460,000,000-462,500,000 and an overlapping
                        461,000,000-463,000,000: 3 ms

so ``batch_build_ms`` reads (50 + 52) / 2 = 51 and ``dispatch_ms``
(3 + 3) / 2 = 3.  The stat-less spans, were they counted, would change
both.  Of the spans' numeric stats, ``tokens`` sums to 8,192 + 2,048 a
round and ``programs`` to 1 in round 0 and 2 in round 1."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, spans  # noqa: E402
from benchmarks.chip import trace as T  # noqa: E402

MS = 1_000_000


def _span(name, start, end, **stats):
    return T.Event(name, float(start), float(end - start),
                   {k: str(v) for k, v in stats.items()})


def _program_spans():
    return [
        _span("batch build", 10 * MS, 50 * MS, round=0, which="private",
              tokens=8192),
        _span("batch build", 45 * MS, 60 * MS, round=0, which="public",
              tokens=2048),
        _span("dispatch", 60 * MS, 63 * MS, round=0, programs=1),
        _span("batch build", 400 * MS, 442 * MS, round=1, which="private",
              tokens=8192),
        _span("batch build", 450 * MS, 460 * MS, round=1, which="public",
              tokens=2048),
        _span("dispatch", 460 * MS, 462.5 * MS, round=1, programs=1),
        _span("dispatch", 461 * MS, 463 * MS, round=1, programs=1),
    ]


def _harness_spans():
    return [
        _span("round", 5 * MS, 70 * MS, step_num=0),
        _span("batch build", 9 * MS, 61 * MS),
        _span("dispatch", 59 * MS, 64 * MS),
        _span("round", 390 * MS, 470 * MS, step_num=1),
        _span("batch build", 399 * MS, 461 * MS),
        _span("dispatch", 459 * MS, 465 * MS),
    ]


def _read(metric, host):
    trace = T.Trace([], sorted(host, key=lambda e: e.start))
    ctx = harness.TraceContext(trace, [], None, {})
    return harness.metric_module(metric).read(ctx)


@pytest.mark.parametrize("metric,value", [("batch_build_ms", 51.0),
                                          ("dispatch_ms", 3.0)])
def test_mean_per_round_of_the_programs_spans(metric, value):
    assert _read(metric, _program_spans() + _harness_spans()) == \
        pytest.approx(value)


@pytest.mark.parametrize("metric", ["batch_build_ms", "dispatch_ms"])
def test_harness_spans_without_stats_are_left_out(metric):
    alone = _read(metric, _program_spans())
    assert alone == _read(metric, _program_spans() + _harness_spans())


@pytest.mark.parametrize("metric,value", [("batch_build_ms", 50.0),
                                          ("dispatch_ms", 3.0)])
def test_one_round_is_its_own_mean(metric, value):
    spans = [e for e in _program_spans() if e.stats["round"] == "0"]
    assert _read(metric, spans + _harness_spans()) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["batch_build_ms", "dispatch_ms"])
def test_no_program_spans_no_reading(metric):
    # a program that writes no spans of its own: the harness's alone
    assert _read(metric, _harness_spans()) is None
    assert _read(metric, []) is None


def _trace():
    return T.Trace([], sorted(_program_spans() + _harness_spans(),
                              key=lambda e: e.start))


@pytest.mark.parametrize("name,stat,value", [
    ("batch build", "tokens", 10_240.0),
    ("dispatch", "programs", 1.5),
    ("batch build", "programs", None),
    ("metrics sync", "round", None)])
def test_per_round_stat_is_the_mean_of_each_rounds_sum(name, stat, value):
    assert spans.per_round_stat(_trace(), name, stat) == value


def test_a_text_stat_is_no_counter():
    with pytest.raises(ValueError):
        spans.per_round_stat(_trace(), "batch build", "which")


def test_per_round_stat_counts_rounds_that_carry_it():
    # a round whose spans lack the stat is no round of that counter
    host = _program_spans() + [_span("dispatch", 800 * MS, 801 * MS,
                                     round=2)]
    trace = T.Trace([], sorted(host, key=lambda e: e.start))
    assert spans.per_round_stat(trace, "dispatch", "programs") == 1.5
    assert spans.per_round_ms(trace, "dispatch") == pytest.approx(
        (3 + 3 + 1) / 3)


def test_gap_labels_keep_the_spans_names():
    """A device gap under both a harness span and the program's span of
    the same name takes that name once."""
    dev = T.Device("/device:TPU:0",
                   ops=[_span("%fusion.1 = f32[8] fusion()", 0, 9 * MS),
                        _span("%fusion.1 = f32[8] fusion()", 64 * MS,
                              70 * MS)],
                   async_ops=[], modules=[])
    trace = T.Trace([dev], sorted(_program_spans() + _harness_spans(),
                                  key=lambda e: e.start))
    labels = T.labelled_gaps(trace, dev, 0, 70 * MS, None)
    assert labels == [("between rounds (host): batch build", 55 * MS)]
