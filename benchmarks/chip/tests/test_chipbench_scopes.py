"""Device time by named scope and counters written as span stats, on a
small trace recorded on a TPU v5 lite (``data/scoped_rounds.xplane.pb``,
written by ``record_fixture.py --scoped``), with every expected value
worked out by hand from the trace's events.

Three ``round`` steps.  In each the chip runs two programs; times below
are ns on the trace's clock, operations as (start, duration).

  jit_attn     the flash-attention forward under ``private_loss``, then
               its sum outside any scope (``jit(attn)/reduce_sum``);
  jit_squares  a copy (copy-start, copy-done: no JAX operation), then
               one fusion under ``jvp(eq2)`` and two under
               ``transpose(jvp(eq2))``.

  jit_attn runs     48,225,249 (6,181)  58,016,236 (5,842)
                    66,823,571 (6,218)
  jit_squares runs  49,165,485 (6,725)  58,637,868 (6,778)
                    67,604,730 (6,763)

``jit_squares`` has the most device time, so it is the round program,
and the window runs from its first run to its last: two rounds, holding
squares 0 and 1 and attn 1 and 2.

  private_loss  flash 5,425 (round 1) + 5,800 (round 2) = 11,225
  eq2           1,478 + 1,577 + 2,421 and 1,477 + 1,578 + 2,417 = 10,948
  none          copies 14 + 1,224 and 13 + 1,278, sums 408 + 410 = 3,347

so, a round, ``private_loss_ms`` reads 0.0056125, ``eq2_ms`` 0.005474,
``unscoped_ms`` 0.0016735, and ``round_device_ms`` their sum, 0.01276:
no operation here is a loop's control.  ``public_logits_ms`` and
``optimizer_ms`` find no operation and give no reading.

The host's spans: two ``batch build`` a round with the stat ``rows``
(64 and 8, 128 and 8, 192 and 8) and a stat-less one that the reader
leaves out; two ``dispatch`` a round, (241,151 + 289,300), (300,160 +
334,900) and (321,470 + 227,800) ns."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, spans  # noqa: E402
from benchmarks.chip import roofline as R  # noqa: E402
from benchmarks.chip import trace as T  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "scoped_rounds.xplane.pb"
SCOPE_METRICS = ("private_loss_ms", "public_logits_ms", "eq2_ms",
                 "optimizer_ms", "unscoped_ms")


@pytest.fixture(scope="module")
def trace():
    return T.load(str(FIXTURE))


@pytest.fixture(scope="module")
def ctx(trace):
    window = T.device_window(trace.devices[0], len(T.rounds(trace)))
    return harness.TraceContext(trace, [window], SimpleNamespace(chips=1),
                                harness.peaks("TPU v5 lite"))


def test_the_window_is_the_squares_program(ctx):
    (w,) = ctx.windows
    assert w.program.startswith("jit_squares(")
    assert (w.lo, w.hi, w.rounds) == (49_165_485, 67_604_730, 2)


def test_ops_keep_the_scopes_of_their_stack(trace):
    stacks = sorted({e.tf_op for e in trace.devices[0].ops})
    assert stacks == ["",
                      "jit(attn)/private_loss/flash_attention_fwd/"
                      "pallas_call:",
                      "jit(attn)/reduce_sum:",
                      "jit(squares)/jvp(eq2)/dot_general:",
                      "jit(squares)/transpose(jvp(eq2))/dot_general:"]


@pytest.mark.parametrize("scope,ns", [("private_loss", 11_225),
                                      ("eq2", 10_948), (None, 3_347)])
def test_scope_ms_gives_each_program_its_scope(ctx, scope, ns):
    among = ("private_loss", "eq2")
    assert R.scope_ms(ctx, scope, among) == pytest.approx(ns / 2 * 1e-6)


def test_a_scope_with_no_operation_gives_no_reading(ctx):
    assert R.scope_ms(ctx, "optimizer") is None
    assert R.scope_ms(SimpleNamespace(windows=[]), None) is None


@pytest.mark.parametrize("metric,ms", [("private_loss_ms", 0.0056125),
                                       ("public_logits_ms", None),
                                       ("eq2_ms", 0.005474),
                                       ("optimizer_ms", None),
                                       ("unscoped_ms", 0.0016735)])
def test_scope_metrics(ctx, metric, ms):
    value = harness.metric_module(metric).read(ctx)
    assert value == (None if ms is None else pytest.approx(ms))


def test_scopes_and_loop_control_make_up_the_round(ctx):
    """The five scope metrics, with the time in which only a control-flow
    operation runs (here none), sum to ``round_device_ms``."""
    read = [harness.metric_module(m).read(ctx) for m in SCOPE_METRICS]
    (w,) = ctx.windows
    control = (T.busy(w.device.ops, w.lo, w.hi) -
               T.busy(T.leaf_ops(w.device), w.lo, w.hi)) / w.rounds * 1e-6
    total = harness.metric_module("round_device_ms").read(ctx)
    assert total == pytest.approx(0.01276)
    assert control == 0
    assert sum(v for v in read if v is not None) + control == \
        pytest.approx(total, rel=1e-9)


def test_the_programs_span_stats_are_kept(trace):
    builds = [e for e in trace.host if e.name == "batch build"]
    assert [e.stats for e in builds] == [
        {"round": str(r), "rows": str(rows)}
        for r in range(3) for rows in (64 * (r + 1), 8)]
    assert spans.per_round_stat(trace, "batch build", "rows") == 136.0
    assert spans.per_round_ms(trace, "dispatch") == pytest.approx(
        (241_151 + 289_300 + 300_160 + 334_900 + 321_470 + 227_800) / 3
        * 1e-6)
