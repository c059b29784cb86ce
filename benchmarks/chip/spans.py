"""The program's own host spans, shared by the host-loop metrics.

``trace.load`` keeps the host events named ``batch build`` and
``dispatch``.  The program (``core/populations/lm.py``) writes spans of
those names with the stat ``round``, the round they belong to; the
harness's own spans of the same names (``harness.annotate_host``) carry
no stats and are left out here.  A program that writes no such span (one
older than its spans) gives no reading.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Optional

from benchmarks.chip import trace as T


def per_round_ms(trace, name: str) -> Optional[float]:
    """Mean over the traced rounds of the host time a round's ``name``
    spans cover (the union of their intervals, so overlapping spans count
    once), in ms; None when the trace has no such span."""
    rounds = defaultdict(list)
    for ev in trace.host:
        if ev.name == name and "round" in ev.stats:
            rounds[ev.stats["round"]].append(ev)
    if not rounds:
        return None
    covered = [T.busy(evs, min(e.start for e in evs), max(e.end for e in evs))
               for evs in rounds.values()]
    return sum(covered) / len(covered) * 1e-6
