"""Host loop (``core/api.py`` ``Federation``, ``core/populations/lm.py``
batch build and metric sync): the mean device-idle time between the end
of one run of the round program and the start of the next, averaged over
the cell's chips.  Helper programs between rounds count as busy."""
from benchmarks.chip import trace as T

LAYER = "host loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    per_chip = []
    for w in ctx.windows:
        gaps = T.inter_round_gaps(w.device, w.program, w.lo, w.hi)
        if gaps:
            per_chip.append(sum(gaps) / len(gaps))
    return sum(per_chip) / len(per_chip) * 1e-6 if per_chip else None
