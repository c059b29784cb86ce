"""The program's own host spans, shared by the host-loop metrics.

``trace.load`` keeps every host event that carries the stat ``round``:
the program (``core/populations/lm.py``, ``core/api.py``) writes its
spans so, naming the round they belong to, with stats of their own
(``batch build``: ``which``, ``tokens``, ``ahead``; ``dispatch``:
``programs``).  A span without that stat is left out here.  A program
that writes no such span (one older than its spans) gives no reading.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

from benchmarks.chip import trace as T


def _by_round(trace, name: str) -> Dict[str, List[T.Event]]:
    rounds = defaultdict(list)
    for ev in trace.host:
        if ev.name == name and "round" in ev.stats:
            rounds[ev.stats["round"]].append(ev)
    return rounds


def per_round_ms(trace, name: str) -> Optional[float]:
    """Mean over the traced rounds of the host time a round's ``name``
    spans cover (the union of their intervals, so overlapping spans count
    once), in ms; None when the trace has no such span."""
    rounds = _by_round(trace, name)
    if not rounds:
        return None
    covered = [T.busy(evs, min(e.start for e in evs), max(e.end for e in evs))
               for evs in rounds.values()]
    return sum(covered) / len(covered) * 1e-6


def per_round_stat(trace, name: str, stat: str) -> Optional[float]:
    """Mean over the traced rounds of a round's sum of the numeric stat
    ``stat`` over its ``name`` spans (a counter the program writes as a
    span stat); None when no such span carries it."""
    sums = []
    for evs in _by_round(trace, name).values():
        values = [float(e.stats[stat]) for e in evs if stat in e.stats]
        if values:
            sums.append(sum(values))
    return sum(sums) / len(sums) if sums else None
