"""Round program (``core/distributed.py``): busy device time a traced round of
the operations under the ``eq2`` scope: the mutual term of Eq. 2 and its
backward (the dense ``kl_mutual_pair``, or SparseDML's top-k and
``sparse_kl_topk``; on a mesh also the all-gather of the public-batch
logits), averaged over the cell's chips.

Read from each device operation's name stack (``tf_op``); an operation
belongs to the outermost of the round's four scopes in it, so the
backward pass (``transpose(jvp(eq2))``) counts with its forward."""
from benchmarks.chip import roofline as R

LAYER = "round program"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    return R.scope_ms(ctx, "eq2", R.ROUND_SCOPES)
