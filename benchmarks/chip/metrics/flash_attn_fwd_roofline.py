"""Flash attention forward (``kernels/flash_attention.py``): share of its
roofline in the traced rounds.  Compute-bound at these shapes.

Matched by signature, since the Pallas call carries no name of its own in
the trace: a ``tpu_custom_call`` with three operands q (..., Hq, S, hd),
k and v (..., Hkv, T, hd) and two results, the output and an f32
log-sum-exp (..., Hq, S, 1).  Seen in the trace under the HLO names
``closed_call.24``/``.28`` (forward) and ``rematted_computation.22``/
``.23`` (the forward recomputed in the backward pass) of qwen3-4b's round.

FLOPs: the score and value products, 4 x batch x Hq x hd x the
query-key pairs a call computes.  The pairs are the cell's family's
``attention_pairs(config, S, T)`` where the family has it: the mean over
its attention layers, which may differ (a sliding window on some), since
a window's call and a full one have the same signature.  Otherwise every
layer is full and causal: S (S + 1) / 2, or S x T where the keys are not
the queries.  Bytes: q, k, v, the output and the log-sum-exp.
"""
from benchmarks.chip import roofline as R

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"


def match(call) -> bool:
    if call.target != "tpu_custom_call" or len(call.operands) != 3 or \
            len(call.results) != 2:
        return False
    q, k, v = call.operands
    lse = call.results[1]
    return (q.shape[:-3] == k.shape[:-3] and k.shape == v.shape and
            len(q.shape) >= 4 and lse.dtype == "f32" and lse.shape[-1] == 1
            and lse.shape[:-1] == q.shape[:-1])


def flops(call, ctx=None) -> float:
    q, k, _ = call.operands
    batch = R.prod(q.shape[:-3])
    Hq, S, hd = q.shape[-3:]
    T = k.shape[-2]
    cell = getattr(ctx, "cell", None)
    counts = getattr(getattr(cell, "family", None), "attention_pairs", None)
    if counts is not None:
        pairs = counts(cell.config, S, T)
    else:
        pairs = S * (S + 1) / 2 if S == T else S * T
    return 4.0 * batch * Hq * pairs * hd


def nbytes(call, ctx=None) -> float:
    return R.interface_bytes(call)


def read(ctx):
    return R.share(ctx, match, flops, nbytes)
