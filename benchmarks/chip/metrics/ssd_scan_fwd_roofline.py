"""Mamba-2 SSD forward ``ssd_scan`` (``kernels/ssd_scan.py``): share of its
roofline in the traced rounds.

Matched by signature: a ``tpu_custom_call`` with six operands, x
(..., H, nc, L, P), dt as a column and as a row, A, and B and C
(..., G, nc, L, N), and three results: y, the final state f32
(..., H, P, N) and every chunk's entry state f32 (..., H, nc, P, N).  Seen
in the trace as ``closed_call.23``/``.24`` (forward) and
``rematted_computation.20``/``.21`` (recomputed in the backward pass).

FLOPs per (batch, head, chunk): the products of the chunked algorithm,
C B^T (2 L^2 N), the masked scores times x (2 L^2 P), the entering
state's output (2 L P N) and the state update (2 L P N).  Bytes: every
operand and result.
"""
from benchmarks.chip import roofline as R

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"


def match(call) -> bool:
    if call.target != "tpu_custom_call" or len(call.operands) != 6 or \
            len(call.results) != 3:
        return False
    x, b, c = call.operands[0], call.operands[4], call.operands[5]
    y, state, states = call.results
    return (y.shape == x.shape and len(x.shape) >= 5 and
            b.shape == c.shape and state.dtype == "f32" and
            states.shape[:-2] == x.shape[:-2])


def flops(call, ctx=None) -> float:
    x, b = call.operands[0], call.operands[4]
    *batch, H, nc, L, P = x.shape
    N = b.shape[-1]
    tiles = R.prod(batch) * H * nc
    return float(tiles * (2 * L * L * N + 2 * L * L * P + 4 * L * P * N))


def nbytes(call, ctx=None) -> float:
    return R.interface_bytes(call)


def read(ctx):
    return R.share(ctx, match, flops, nbytes)
