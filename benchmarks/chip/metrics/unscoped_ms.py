"""Round program (``core/distributed.py``) and what runs beside it: busy
device time a traced round of the operations under none of the round's
four scopes (``private_loss``, ``public_logits``, ``eq2``,
``optimizer``), averaged over the cell's chips: operations that XLA made
with no JAX operation behind them (qwen3-4b's (2 V x d) embedding fusion,
copies), the sum that joins the losses into one objective, and any helper
program run between rounds.  With the four scope metrics it makes up
``round_device_ms``, less the time in which only a loop's own control
runs."""
from benchmarks.chip import roofline as R

LAYER = "round program"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    return R.scope_ms(ctx, None, R.ROUND_SCOPES)
