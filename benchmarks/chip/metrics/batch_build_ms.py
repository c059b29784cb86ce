"""Host loop (``core/populations/lm.py`` ``_private_batch`` and
``_public_batch``): host time a round spends building its private and
public token batches, from the program's ``batch build`` spans, averaged
over the traced rounds.  Falls only where the build itself gets faster;
``inter_round_gap_ms`` also falls where the build is hidden behind the
device's round."""
from benchmarks.chip import spans

LAYER = "host loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    return spans.per_round_ms(ctx.trace, "batch build")
