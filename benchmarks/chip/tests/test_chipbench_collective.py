"""``allgather_exposed_ms`` on traces built by hand, with two device
planes; times are ns on the trace's clock, one traced round unless a case
says otherwise.  The TPU's form of the gather is the one a four-chip trace
of ``qwen3-4b.dml.k4.4chip`` holds: an ``async-collective-start`` /
``async-collective-done`` pair of fusions, with compute fusions between
them that carry its buffers.

  hidden   a zero-length start at 5 ms and its done at 8 ms, under fusions
           that run 0-10 ms: nothing is exposed, 0 ms;
  alone    a synchronous all-gather runs 10-12 ms between two fusions:
           2 ms;
  tpu      an async-collective-start at 10-10.5 ms, a fusion that carries
           its buffers 10.5-14 ms, idle 14-14.3 ms, its done 14.3-14.301:
           0.5 + 0.3 + 0.001 ms;
  split    the gather in two numbered pieces: start.1 10-10.2 ms, a
           carrier 10.2-13, done.1 13-13.5, start.2 13.5-13.6, idle
           13.6-14, done.2 14-15: in flight 10-15 ms, of which the carrier
           covers 2.8, so 2.2 ms;

and with two chips the higher reading is taken.  ``data/k4_gather_round.json``
holds one round of chip 0 from a traced run of that cell, as the trace has
it (every other operation as the union of its intervals)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip import trace as T  # noqa: E402

MS = 1_000_000
PAYLOAD = "bf16[4,2048,18992]{2,1,0}"
PIECE = "bf16[1,2048,18992]{2,1,0}"


def _op(text, start_ms, end_ms):
    return T.Event(text, start_ms * MS, (end_ms - start_ms) * MS)


def _fusion(n, start_ms, end_ms):
    return _op(f"%fusion.{n} = {PIECE} fusion({PIECE} %p.{n}), "
               "kind=kLoop", start_ms, end_ms)


def _gather(n, start_ms, end_ms):
    return _op(f"%all-gather.{n} = {PAYLOAD} all-gather({PIECE} %x.{n}), "
               "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}",
               start_ms, end_ms)


def _collective(which, start_ms, end_ms, results=f"({PIECE}, {PAYLOAD}, "
                "s32[2]{0:S(4)}, u32[]{:S(2)})", piece=""):
    """The TPU's asynchronous collective fusion: no number where the
    program holds one, ``piece`` ".<n>" where it is split."""
    if which == "done":
        results = PAYLOAD
    return _op(f"%async-collective-{which}{piece} = {results} fusion({PIECE} "
               f"%copy.263, {PAYLOAD} %get-tuple-element.5034), "
               "kind=kCustom, calls=%fused_computation.972",
               start_ms, end_ms)


def _carrier(start_ms, end_ms):
    """A compute fusion that threads the gather's buffers through."""
    return _op(f"%fusion.1078 = (bf16[1,4,1024,48,128]{{4,2,3,1,0}}, {PIECE}, "
               f"{PAYLOAD}) fusion(bf16[1,4,1024,2560]{{3,2,1,0}} %p, {PIECE} "
               f"%async-collective-start), kind=kOutput, "
               "calls=%fused_computation.1", start_ms, end_ms)


CASES = {
    "hidden": ([_fusion(1, 0, 5), _collective("start", 5, 5),
                _carrier(5, 8), _collective("done", 8, 8),
                _fusion(2, 8, 10)], 0.0),
    "alone": ([_fusion(1, 0, 10), _gather(2, 10, 12),
               _fusion(2, 12, 20)], 2.0),
    "tpu": ([_fusion(1, 0, 10), _collective("start", 10, 10.5),
             _carrier(10.5, 14), _collective("done", 14.3, 14.301),
             _fusion(2, 14.301, 20)], 0.801),
    "split": ([_fusion(1, 0, 10),
               _collective("start", 10, 10.2, piece=".1"), _carrier(10.2, 13),
               _collective("done", 13, 13.5, piece=".1"),
               _collective("start", 13.5, 13.6, piece=".2"),
               _collective("done", 14, 15, piece=".2"), _fusion(2, 15, 20)],
              2.2),
}


def _window(ops, rounds=1, lo_ms=0, hi_ms=20, n=0):
    dev = T.Device(f"/device:TPU:{n}", ops, [], [])
    return T.Window(dev, "jit_step", lo_ms * MS, hi_ms * MS, rounds)


def _read(windows):
    trace = T.Trace([w.device for w in windows], [])
    ctx = harness.TraceContext(trace, windows, None, {})
    return harness.metric_module("allgather_exposed_ms").read(ctx)


@pytest.mark.parametrize("case", sorted(CASES))
def test_exposed_time_of_the_gather(case):
    ops, want = CASES[case]
    assert _read([_window(ops)]) == pytest.approx(want)


def test_per_round_and_the_highest_chip():
    alone, split = CASES["alone"], CASES["split"]
    two = [_window(alone[0], rounds=2, n=0),
           _window(split[0], rounds=2, n=1)]
    assert _read(two) == pytest.approx(2.2 / 2)
    assert _read(two[::-1]) == pytest.approx(2.2 / 2)


def test_the_window_cuts_what_lies_outside_it():
    ops, _ = CASES["split"]
    # in flight 10-15 ms; the window ends at 14 ms: 0.2 + 1 ms exposed
    assert _read([_window(ops, hi_ms=14)]) == pytest.approx(1.2)


def test_no_gather_no_reading():
    assert _read([_window([_fusion(1, 0, 10)])]) is None
    assert _read([]) is None


def test_only_the_gathers_own_operations_match():
    mod = harness.metric_module("allgather_exposed_ms")
    assert mod.is_gather(_gather(3, 0, 1).name)
    assert mod.is_gather(_collective("done", 0, 1, piece=".3").name)
    assert mod.is_gather(_collective("start", 0, 1).name)
    assert mod.is_gather(_collective("done", 0, 1).name)
    assert not mod.is_gather(_fusion(3, 0, 1).name)
    assert not mod.is_gather(_carrier(0, 1).name)
    assert not mod.is_gather(
        f"%all-reduce.1 = f32[4]{{0}} all-reduce(f32[4]{{0}} %x), "
        "channel_id=2")
    # an asynchronous collective whose result keeps the operand's shape
    # (an all-reduce) is not the gather
    assert not mod.is_gather(_collective("start", 0, 1,
                                         results=f"({PIECE}, {PIECE})").name)


def test_a_round_of_the_four_chip_trace():
    # one chip's round as the TPU ran it: the gather is an asynchronous
    # collective fusion, with the private batch's projections between
    # its start and done carrying its buffers
    data = json.loads((Path(__file__).parent / "data" /
                       "k4_gather_round.json").read_text())
    ops = [T.Event(name, start, dur) for name, start, dur in data["named"]]
    ops += [T.Event(f"%fusion.{i} = f32[1]{{0}} fusion(f32[1]{{0}} %p)",
                    start, dur)
            for i, (start, dur) in enumerate(data["others"])]
    mod = harness.metric_module("allgather_exposed_ms")
    gather = [ev.name.split(" = ")[0] for ev in ops if mod.is_gather(ev.name)]
    assert gather == ["%async-collective-start", "%async-collective-done"]
    dev = T.Device("/device:TPU:0", ops, [], [])
    in_flight = sum(e - s for s, e in mod.in_flight(dev)) * 1e-6
    assert in_flight == pytest.approx(data["in_flight_ms"])
    assert in_flight > 70.0
    window = T.Window(dev, "jit_step", 0, data["hi"], 1)
    assert _read([window]) == pytest.approx(data["exposed_ms"])
    assert 0.4 < _read([window]) < 0.6
