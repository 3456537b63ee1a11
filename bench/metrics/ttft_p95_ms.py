"""95th percentile over all requests due in the window of the time from
each request's due time to its first token; a request with no first token
by the close counts at the time it had waited then (host clock)."""
from harness.stats import pctl, ttft_s


def read(ctx):
    v = pctl(ttft_s(ctx.window), 95)
    return None if v is None else v * 1e3
