"""``trace.py`` on a small trace recorded on a TPU v5 lite
(``data/rounds.xplane.pb``, written by ``record_fixture.py``), with every
expected value worked out by hand from the trace's events.

The trace holds three ``round`` steps.  In each, the chip runs a flash
attention program (the Pallas call, then a reduce) and a matmul program
(copy-start, copy-done, fusion); times below are ns on the trace's clock.

  flash program runs   43,522,388 (5,856)  50,358,772 (5,850)
                       57,924,814 (5,842)
  matmul program runs  44,412,238 (3,091)  51,107,152 (2,848)
                       58,700,477 (2,850)

The flash program has the most device time of the programs that ran once
a round, so it is the round program, and the window runs from its first
run to its last: 57,924,814 - 43,522,388 = 14,402,426 ns, two rounds."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip import trace as T  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "rounds.xplane.pb"
FLASH = "jit__lambda(12639158506718349436)"


@pytest.fixture(scope="module")
def trace():
    return T.load(str(FIXTURE))


@pytest.fixture(scope="module")
def window(trace):
    return T.device_window(trace.devices[0], len(T.rounds(trace)))


def test_planes_lines_and_steps(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    dev = trace.devices[0]
    assert (len(dev.ops), len(dev.async_ops), len(dev.modules)) == (15, 3, 6)
    steps = T.rounds(trace)
    assert [s.stats["step_num"] for s in steps] == ["0", "1", "2"]
    assert steps[0].start == 44_603_876 and steps[0].dur == 1_622_880


def test_the_window_is_on_the_device_clock(window):
    assert window.program == FLASH
    assert (window.lo, window.hi) == (43_522_388, 57_924_814)
    assert window.rounds == 2


def test_the_window_skips_the_settling_rounds(trace):
    # the first run of the round program left out: one round, from the
    # second run to the third, and its one gap
    dev, n = trace.devices[0], len(T.rounds(trace))
    window = T.device_window(dev, n, skip=1)
    assert (window.lo, window.hi, window.rounds) == (50_358_772, 57_924_814,
                                                     1)
    assert T.inter_round_gaps(dev, window.program, window.lo,
                              window.hi) == [7_557_349]
    assert T.device_window(dev, n, skip=2) is None


def test_busy_time(trace, window):
    # round 0: flash 5,440 and reduce 408 touch (5,848), copy-start 13,
    # copy-done 1,271, fusion 1,801 -> 8,933; round 1: 5,432 + 408 + 13 +
    # 1,028 + 1,802 = 8,683; round 2's operations start after the window
    assert T.busy(window.device.ops, window.lo, window.hi) == 17_616


def test_gaps_between_round_programs(window):
    # 50,358,772 - 43,528,244 = 6,830,528 less the matmul program's
    # operations 13 + 1,271 + 1,801; then 57,924,814 - 50,364,622 =
    # 7,560,192 less 13 + 1,028 + 1,802
    gaps = T.inter_round_gaps(window.device, window.program, window.lo,
                              window.hi)
    assert gaps == [6_827_443, 7_557_349]


def test_idle_gaps_and_their_labels(trace, window):
    labelled = T.labelled_gaps(trace, window.device, window.lo, window.hi,
                               window.program)
    longest = sorted(labelled, key=lambda g: -g[1])[:3]
    # fusion end 51,110,000 -> flash 57,924,822 is cut at the window's end
    assert longest == [("between rounds (host)", 6_814_814),
                       ("between rounds (host)", 5_943_451),
                       ("between rounds (host)", 883_998)]
    # the flash program's start to its first operation (43,522,388 ->
    # 43,522,394) and its Pallas call to its reduce in round 1
    inside = sorted(d for label, d in labelled
                    if label == "inside the round program")
    assert inside == [2, 6]
    # the matmul program's copy-start -> copy-done -> fusion, twice
    other = [d for label, d in labelled
             if label == f"inside {FLASH.split('(')[0]}"]
    assert sorted(other) == [1, 1, 2, 2]
    assert sum(d for _, d in labelled) == 14_402_426 - 17_616


def test_events_by_name(window):
    ops = T.by_name(T.leaf_ops(window.device), window.lo, window.hi)
    flash = [v for k, v in ops.items() if "tpu_custom_call" in k]
    assert flash == [(2, 10_872)]
    assert sum(t for _, t in ops.values()) == 17_616
    assert sorted(c for c, _ in ops.values()) == [2, 2, 2, 2, 2]


def test_pallas_calls_and_roofline(trace, window):
    calls = T.pallas_calls(window.device, window.lo, window.hi)
    assert [ev.dur for _, ev in calls] == [5_440, 5_432]
    mod = harness.metric_module("flash_attn_fwd_roofline")
    call = calls[0][0]
    assert mod.match(call)
    # 4 x 2 heads x 256 * 257 / 2 pairs x 128; q, k, v, out 131,072 B
    # each and lse 2,048 B: bandwidth-bound, 526,336 / 819e9 s per call
    assert mod.flops(call) == 33_685_504
    assert mod.nbytes(call) == 526_336
    ctx = harness.TraceContext(trace, [window], None,
                               harness.peaks("TPU v5 lite"))
    assert mod.read(ctx) == pytest.approx(
        100 * 2 * 526_336 / 819e9 / 10_872e-9)


def test_context_numbers(trace, window):
    cell = SimpleNamespace(chips=1)
    ctx = harness.TraceContext(trace, [window], cell,
                               harness.peaks("TPU v5 lite"))
    assert ctx.rounds == 2
    assert ctx.window_s == pytest.approx(14_402_426e-9)
    assert ctx.mean_busy_s() == pytest.approx(17_616e-9)
    gap = harness.metric_module("inter_round_gap_ms").read(ctx)
    assert gap == pytest.approx((6_827_443 + 7_557_349) / 2 * 1e-6)
    busy = harness.metric_module("round_device_ms").read(ctx)
    assert busy == pytest.approx(17_616e-9 / 2 * 1e3)
    idle = harness.metric_module("device_idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - 17_616 / 14_402_426))


def test_the_xspace_reads_as_profile_data(trace):
    """``load`` reads the XSpace itself, for the ops' metadata; every
    event it keeps is the one ``jax.profiler.ProfileData`` gives, to the
    ns and stat for stat."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(FIXTURE))
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    lines = {ln.name: [(e.name, float(e.start_ns), float(e.duration_ns),
                        {str(k): str(v) for k, v in e.stats})
                       for e in ln.events] for ln in plane.lines}
    dev = trace.devices[0]
    for line, kept in (("XLA Ops", dev.ops), ("Async XLA Ops", dev.async_ops),
                       ("XLA Modules", dev.modules)):
        assert [(e.name, e.start, e.dur, e.stats) for e in kept] == \
            lines[line]
    host = next(p for p in data.planes if p.name == "/host:CPU")
    steps = [(e.name, float(e.start_ns), float(e.duration_ns))
             for ln in host.lines for e in ln.events if e.name == "round"]
    assert [(e.name, e.start, e.dur) for e in trace.host] == steps


def test_ops_keep_their_name_stack(trace):
    # the fixture's programs are jitted lambdas with no named scope
    stacks = {e.tf_op for e in trace.devices[0].ops}
    assert "jit(<lambda>)/pallas_call:" in stacks
    assert "jit(<lambda>)/reduce_sum:" in stacks
    assert "" in stacks                 # copies carry no JAX operation
    assert all(e.tf_op == "" for e in trace.devices[0].modules)


@pytest.mark.parametrize("tf_op,stack,scope", [
    ("jit(step)/transpose(jvp(eq2))/dot_general:",
     ["step", "eq2", "dot_general"], "eq2"),
    ("jit(step)/jvp(private_loss)/vmap(eq2)/mul:",
     ["step", "private_loss", "eq2", "mul"], "private_loss"),
    ("jit(<lambda>)/pallas_call:", ["<lambda>", "pallas_call"], None),
    ("", [""], None)])
def test_an_op_belongs_to_its_outermost_scope(tf_op, stack, scope):
    assert T.name_stack(tf_op) == stack
    ev = T.Event("%op", 0.0, 1.0, {}, tf_op)
    assert T.scope_of(ev, ("eq2", "private_loss")) == scope


def test_union_clip_and_overlap():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert T.clip([(0, 10), (12, 20)], 5, 15) == [(5, 10), (12, 15)]
    assert T.overlap((0, 10), [(2, 4), (3, 6), (8, 12)]) == 6
