"""Scheduler (``serving/scheduler.py``): 95th percentile over all requests
due in the window of the time from the due time to the admission pass that
admitted it (the close, for one not admitted by then); harness clock."""
from harness.stats import pctl, queue_wait_s


def read(ctx):
    v = pctl(queue_wait_s(ctx.window), 95)
    return None if v is None else v * 1e3
