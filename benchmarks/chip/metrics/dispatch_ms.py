"""Host loop (``core/populations/lm.py`` ``LMClients._dispatch``): host
time a round spends calling its jitted round program until the call
returns with the program enqueued, from the program's ``dispatch`` spans,
averaged over the traced rounds."""
from benchmarks.chip import spans

LAYER = "host loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    return spans.per_round_ms(ctx.trace, "dispatch")
