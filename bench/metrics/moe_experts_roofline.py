"""MoE experts (``kernels/moe_group_matmul.py``): the grouped launches' least
possible time over their measured device time, in the decode steps that lie
whole inside the traced slice.

The least time of a step is the sum over its MoE layers of the larger of
FLOPs / bf16 peak and bytes / HBM bandwidth (``harness.mla_moe_cost.
moe_layer_cost``): each layer reads the three bf16 matrices of every expert
that received a row once, plus the rows' activations in and out, and computes
2 x rows x 3 x d_model x d_expert FLOPs, rows being the step's mean decoded
tokens x top_k.  Padding, row tiles and the weights a launch reads twice
for a group that straddles a row tile are never counted.

The launches' device time is the summed duration of their operations
(``_moe_group_matmul_gate_up``, ``_moe_group_matmul_down``) inside the
harness's ``bench.step`` spans; the steps are those spans.  A program without
those launches gives nothing to read.
"""
import re

from harness.mla_moe_cost import MLAMoEShape, moe_layer_cost
from harness.trace import matching_ns, spans_inside

KERNELS = re.compile(r"_moe_group_matmul_(gate_up|down)")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.window.steps:
        return None
    shape = MLAMoEShape.from_config(ctx.cell.config)
    steps = ctx.window.steps
    tokens = sum(n for *_, n in steps) / len(steps)
    flops, nbytes = moe_layer_cost(shape, tokens)
    least = (shape.layers - shape.n_dense) * max(
        flops / ctx.peaks["bf16_flops_per_s"],
        nbytes / ctx.peaks["hbm_bytes_per_s"])
    spans = spans_inside(ctx.trace["host"], ctx.trace["window_ns"],
                         "bench.step")
    busy_ns = sum(matching_ns(evs, span, KERNELS)
                  for evs in ctx.trace["devices"].values() for span in spans)
    if busy_ns == 0:
        return None
    return 100.0 * len(spans) * least / (busy_ns * 1e-9)
