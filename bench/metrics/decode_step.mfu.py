"""Model step: the dense-equivalent FLOPs of every token decoded in the
window (2 x the weights each token multiplies, plus attention over its
context; ``harness.flops``) / (window x chips x the chip's bf16 peak).
A compressed configuration is charged the FLOPs of the dense model it
stands for, so the count does not depend on how a site is executed."""
from harness.flops import ModelShape, request_decode_flops


def read(ctx):
    if ctx.peaks is None:
        return None
    shape = ModelShape.from_config(ctx.cell.config)
    flops = sum(request_decode_flops(shape, r.prompt_len, r.n_tok)
                for r in ctx.window.records.values())
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.window.seconds * peak)
