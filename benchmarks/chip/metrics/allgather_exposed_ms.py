"""Collective (``core/distributed.py`` ``make_sharded_dml_step``): device
time a traced round on a chip during which a piece of the public-batch
logits' all-gather is in flight and no other operation runs there, the
highest over the cell's chips.  What the gather costs the round: a gather
hidden behind compute reads 0.

The gather is matched by its own HLO operations.  On the TPU the
compiler makes it an asynchronous collective fusion: an
``async-collective-start`` / ``async-collective-done`` pair whose results
hold the gathered payload (the first operand's shape with its leading
axis a multiple of the operand's), in flight from the start of the one
to the end of the other, paired by the HLO name's number when it is
split into pieces.  The compute fusions that run in between carry the
gather's buffers through, but they are not the gather's: they are what
hides it.  A synchronous ``all-gather`` counts as it runs.  The round
program holds no other all-gather.
"""
from __future__ import annotations

import re
from typing import List, Optional

from benchmarks.chip import trace as T

LAYER = "collective"
UNIT = "ms"
MOVES = "tokens_per_s"

_PAIR = re.compile(r"\s*%?async-collective-(start|done)(\.\d+)?")


def _gathers(call: T.Call) -> bool:
    """A result holds the first operand stacked along its leading axis."""
    if not call.operands or not call.operands[0].shape:
        return False
    a = call.operands[0].shape
    return any(len(r.shape) == len(a) and r.shape[1:] == a[1:] and
               r.shape[0] > a[0] and r.shape[0] % a[0] == 0
               for r in call.results)


def is_gather(name: str) -> bool:
    """An operation of the all-gather."""
    call = T.parse_hlo(name[:4000])
    if call is None:
        return False
    if call.kind == "all-gather":
        return True
    return call.op.startswith("async-collective-") and _gathers(call)


def in_flight(device: T.Device) -> List[T.Interval]:
    """The intervals in which a piece of the gather is in flight."""
    out = []
    starts = {}
    for ev in sorted(device.ops, key=lambda e: e.start):
        if not is_gather(ev.name):
            continue
        m = _PAIR.match(ev.name)
        if m is None:
            out.append((ev.start, ev.end))
        elif m.group(1) == "start":
            starts[m.group(2)] = ev.start
        else:
            out.append((starts.pop(m.group(2), ev.start), ev.end))
    return T.union(out)


def exposed_ns(device: T.Device, lo: float, hi: float) -> float:
    """ns of [lo, hi] in which the gather is in flight and no other
    operation runs on the chip."""
    others = [(ev.start, ev.end) for ev in T.leaf_ops(device)
              if not is_gather(ev.name)]
    return sum((e - s) - T.overlap((s, e), others)
               for s, e in T.clip(in_flight(device), lo, hi))


def read(ctx) -> Optional[float]:
    worst = None
    for w in ctx.windows:
        if not any(is_gather(ev.name) for ev in w.device.ops):
            continue
        per_round = exposed_ns(w.device, w.lo, w.hi) / w.rounds
        worst = per_round if worst is None else max(worst, per_round)
    return None if worst is None else worst * 1e-6
