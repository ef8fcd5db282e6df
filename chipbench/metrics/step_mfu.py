"""step.mfu: the whole step's share of the chips' bf16 peak -- training
FLOPs per token (the configuration's reference counts them from its
shapes; recompute not counted) times the traced window's tokens per
second, over chips x peak."""


def read(ctx):
    cell = ctx.cell
    fpt = cell.ref.flops_per_token(cell.config["model"],
                                   cell.traffic["seq_len"])
    return 100.0 * fpt * ctx.tokens_per_s / (ctx.chips
                                             * ctx.peaks["bf16_flops"])
