"""LCC kernels (``kernels/lcc_chain_matmul.py``, ``lcc_group_matmul.py``):
their least possible time over their measured device time, in the decode
steps that lie whole inside the traced slice.

The least time is counted from the decomposition's own content
(``harness.flops.lcc_site_cost``): one add per stored term per decoded row,
and the term streams at the artifact's 6 bytes per term plus the f32
activations in and out.  Per decode step it is the sum over the sites of
the larger of adds / bf16 peak and bytes / HBM bandwidth; at olmo-1b's
widths every site is bound by bytes (0.39 GB of streams per layer).
One-hot tiles and padding are never counted, so the share cannot be raised
by a layout that does more work.  A change to the stored stream format
changes the count and needs a benchmark change.

The kernels' device time is the summed duration of their operations
(``_lcc_chain_matmul``, ``_lcc_group_matmul`` in the trace) inside the
harness's ``bench.step`` spans; the steps are those spans.
"""
import re

from harness.flops import lcc_site_cost
from harness.trace import matching_ns, spans_inside

KERNELS = re.compile(r"_lcc_(chain|group)_matmul")


def read(ctx):
    sites = getattr(ctx.system, "lcc_sites", None)
    if ctx.trace is None or not sites or not ctx.window.steps:
        return None
    steps = ctx.window.steps
    batch = sum(n for *_, n in steps) / len(steps)
    least = 0.0
    for _name, terms, k, n in sites:
        adds, nbytes = lcc_site_cost(terms, k, n, batch)
        least += max(adds / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    spans = spans_inside(ctx.trace["host"], ctx.trace["window_ns"],
                         "bench.step")
    busy_ns = sum(matching_ns(evs, span, KERNELS)
                  for evs in ctx.trace["devices"].values() for span in spans)
    if busy_ns == 0:
        return None
    return 100.0 * len(spans) * least / (busy_ns * 1e-9)
