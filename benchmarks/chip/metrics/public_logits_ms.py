"""Round program (``core/distributed.py``): busy device time a traced
round of the operations under the ``public_logits`` scope: every
client's forward and backward on the shared public batch and its public
cross-entropy, averaged over the cell's chips.

Read from each device operation's name stack (``tf_op``); an operation
belongs to the outermost of the round's four scopes in it, so the
backward pass (``transpose(jvp(public_logits))``) counts with its forward."""
from benchmarks.chip import roofline as R

LAYER = "round program"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    return R.scope_ms(ctx, "public_logits", R.ROUND_SCOPES)
