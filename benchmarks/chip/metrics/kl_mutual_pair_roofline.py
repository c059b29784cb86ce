"""Dense Eq.-2 kernel ``kl_mutual_pair`` (``kernels/kl_mutual.py``): share
of its roofline in the traced rounds.  Bandwidth-bound.

Matched by signature: a ``tpu_custom_call`` with operands live (Kl, N,
Vp), fixed (Kg, N, Vp) and pair weights (Kl, Kg), and one f32 result
(Kl, N).  Seen in the trace as ``jvp__.1``.

Counted at the true vocabulary V of the cell's configuration (the call's
Vp is V padded to the vocabulary block).  Bytes: live and fixed in their
dtype, the weights and the result.  FLOPs: the streaming softmax of both
sides, 4 (Kl + Kg) N V, and the cross accumulator, 3 Kl Kg N V.
"""
from benchmarks.chip import roofline as R

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"


def match(call) -> bool:
    if call.target != "tpu_custom_call" or len(call.operands) != 3 or \
            len(call.results) != 1:
        return False
    live, fixed, w = call.operands
    out = call.results[0]
    return (len(live.shape) == 3 and len(fixed.shape) == 3 and
            len(w.shape) == 2 and live.shape[1:] == fixed.shape[1:] and
            w.shape == (live.shape[0], fixed.shape[0]) and
            out.shape == live.shape[:2])


def _vocab(call, ctx) -> int:
    V = call.operands[0].shape[2]
    return min(V, ctx.cell.family.dims(ctx.cell.config)["V"]) if ctx else V


def flops(call, ctx=None) -> float:
    live, fixed, _ = call.operands
    Kl, N, _ = live.shape
    Kg = fixed.shape[0]
    return float(N * _vocab(call, ctx) * (4 * (Kl + Kg) + 3 * Kl * Kg))


def nbytes(call, ctx=None) -> float:
    live, fixed, w = call.operands
    V, Vp = _vocab(call, ctx), live.shape[2]
    return (live.nbytes + fixed.nbytes) * V / Vp + w.nbytes + \
        call.results[0].nbytes


def read(ctx):
    return R.share(ctx, match, flops, nbytes)
