"""Device: share of the traced slice with no operation running, 1 - busy /
slice, where busy is the union of the device operations' intervals; mean
over the cell's chips.  Nothing without a trace."""
from harness.trace import idle_pct


def read(ctx):
    return None if ctx.trace is None else idle_pct(ctx.trace)
