"""Round program, whole step: model FLOPs of the traced rounds over the
traced window x chips x the chip's peak bf16 FLOP/s.

Model FLOPs per trained token are 6 x the parameters that multiply
activations in matrix products (the LM head counted, the embedding lookup
not) plus 3 x the forward causal attention score and value products; the
recomputation of remat is not counted.  A round trains K x (B + B_pub) x
S tokens.  The family's reference module counts both terms from the
configuration file (``matmul_params``, ``attention_flops_per_token``);
Mamba-2's SSD mixing has no attention term."""
LAYER = "round program"
UNIT = "%"
MOVES = "tokens_per_s"


def flops_per_token(family, config: dict, seq: int) -> float:
    return 6.0 * family.matmul_params(config) + \
        3.0 * family.attention_flops_per_token(config, seq)


def read(ctx):
    cell = ctx.cell
    if not ctx.rounds or ctx.window_s <= 0:
        return None
    total = ctx.rounds * cell.tokens_per_round * flops_per_token(
        cell.family, cell.config, cell.traffic["seq"])
    return 100.0 * total / (ctx.window_s * cell.chips *
                            ctx.peaks["bf16_flops"])
