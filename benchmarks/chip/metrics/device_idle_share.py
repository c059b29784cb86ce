"""Device: the share of the traced window in which no operation runs on
the chip (1 - busy / window), averaged over the cell's chips."""
LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.mean_busy_s() / ctx.window_s)
