"""Engine prefill (``ServingEngine.submit`` through ``Scheduler.admit``):
95th percentile of the synced wall of the window's admission passes that
admitted a request; harness span."""
from harness.stats import admit_pass_s, pctl


def read(ctx):
    v = pctl(admit_pass_s(ctx.window), 95)
    return None if v is None else v * 1e3
