"""95th percentile over all requests due in the window with two tokens or
more of (last token so far - first token) / (tokens so far - 1) (host
clock)."""
from harness.stats import pctl, tpot_s


def read(ctx):
    v = pctl(tpot_s(ctx.window), 95)
    return None if v is None else v * 1e3
