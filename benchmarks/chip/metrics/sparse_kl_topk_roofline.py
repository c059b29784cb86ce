"""Sparse Eq.-2 kernel ``sparse_kl_topk`` (``kernels/sparse_kl.py``): share
of its roofline in the traced rounds.  Bandwidth-bound.

Matched by signature: a ``tpu_custom_call`` with operands live (Kl, N,
Vp), the received top-k indices s32 (J, N, k) and log-probabilities f32
(J, N, k), and pair weights (Kl, J), and one f32 result (n_b, Kl, bb)
with n_b x bb = N.

Counted at the vocabulary the live logits hold, V of the cell's
configuration (the call's Vp is V padded to the vocabulary block with
columns that hold no class).  Bytes: live at V in its dtype, the indices,
log-probabilities, weights and the result.  FLOPs: the streaming softmax
and entropy of the live side, 4 Kl N V, and the terms of the gathered
classes (their probability, its sum and its product with the received
log-probability), 4 Kl J N k.  The kernel gathers by a one-hot product
over each vocabulary block; that product is the kernel's way to the
gather, not work the Eq.-2 term needs, and is not counted.
"""
from benchmarks.chip import roofline as R

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"


def match(call) -> bool:
    if call.target != "tpu_custom_call" or len(call.operands) != 4 or \
            len(call.results) != 1:
        return False
    live, idx, logp, w = call.operands
    out = call.results[0]
    return (len(live.shape) == 3 and len(idx.shape) == 3 and
            idx.dtype == "s32" and logp.dtype == "f32" and
            idx.shape == logp.shape and live.shape[1] == idx.shape[1] and
            w.shape == (live.shape[0], idx.shape[0]) and
            len(out.shape) == 3 and out.shape[1] == live.shape[0] and
            out.shape[0] * out.shape[2] == live.shape[1])


def _vocab(call, ctx) -> int:
    V = call.operands[0].shape[2]
    return min(V, ctx.cell.family.dims(ctx.cell.config)["V"]) if ctx else V


def flops(call, ctx=None) -> float:
    live, idx, _, _ = call.operands
    Kl, N, _ = live.shape
    J, _, k = idx.shape
    return float(4 * Kl * N * _vocab(call, ctx) + 4 * Kl * J * N * k)


def nbytes(call, ctx=None) -> float:
    live, idx, logp, w = call.operands
    V, Vp = _vocab(call, ctx), live.shape[2]
    return live.nbytes * V / Vp + idx.nbytes + logp.nbytes + w.nbytes + \
        call.results[0].nbytes


def read(ctx):
    return R.share(ctx, match, flops, nbytes)
