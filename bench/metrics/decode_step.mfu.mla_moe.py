"""Model step of an MLA + MoE decoder: the FLOPs of every token decoded in
the window, counted as published (``harness.mla_moe_cost``: 2 x the weights a
token multiplies, the MLA projections once per token and only its routed and
shared experts, plus attention over its context) / (window x chips x the
chip's bf16 peak)."""
from harness.mla_moe_cost import MLAMoEShape, request_decode_flops


def read(ctx):
    if ctx.peaks is None:
        return None
    shape = MLAMoEShape.from_config(ctx.cell.config)
    flops = sum(request_decode_flops(shape, r.prompt_len, r.n_tok)
                for r in ctx.window.records.values())
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.window.seconds * peak)
