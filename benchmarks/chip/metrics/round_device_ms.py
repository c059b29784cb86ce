"""Round program (``core/distributed.py``): busy device time per traced
round, the union of the operations' intervals, averaged over the cell's
chips."""
LAYER = "round program"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    if not ctx.rounds:
        return None
    return ctx.mean_busy_s() / ctx.rounds * 1e3
