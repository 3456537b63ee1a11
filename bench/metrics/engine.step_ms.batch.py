"""Engine decode step (``ServingEngine.step`` through ``Scheduler.step``):
the window's summed step time over its steps, each step synced on its
tokens; harness span."""
from harness.stats import step_mean_s


def read(ctx):
    v = step_mean_s(ctx.window)
    return None if v is None else v * 1e3
