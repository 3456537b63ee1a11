"""Step profiling and the live roofline.

:class:`StepProfiler` is a bounded wall-time ring buffer for the engine's
fused decode step.  The caller's clock must already include the device work:
the serving engine reads its sampled tokens back inside the timed region,
which syncs every step.

:func:`roofline` joins the per-site shift-add budget of an artifact's
:class:`~repro.core.cost.ModelCostReport` with a measured decode throughput
into achieved adds/s.  :func:`live_roofline` feeds it from a *running*
engine — artifact from the executor, tok/s from the engine's own profiler,
launch counts from the per-bucket registry.
"""
from __future__ import annotations

import time
from collections import deque

__all__ = ["StepProfiler", "roofline", "live_roofline"]


def _pct(sorted_vals, q: float) -> float | None:
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[int(i)]


class StepProfiler:
    """Ring buffer of per-step wall times.

    Usage (the engine's step loop)::

        t0 = prof.begin()
        out = np.asarray(step_fn(...))  # synced: the host reads the result
        prof.end(t0, tokens=n_active)
    """

    def __init__(self, capacity: int = 4096, clock=time.perf_counter):
        self.capacity = int(capacity)
        self.clock = clock
        self._ring: deque = deque(maxlen=self.capacity)  # (wall_s, tokens)
        self._n = 0          # lifetime samples (ring may have dropped old ones)

    def begin(self) -> float:
        return self.clock()

    def end(self, t0: float, tokens: int = 0) -> float:
        self._n += 1
        dt = self.clock() - t0
        self._ring.append((dt, int(tokens)))
        return dt

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def total_steps(self) -> int:
        return self._n

    def summary(self) -> dict:
        """Aggregates over the samples currently in the ring."""
        samples = list(self._ring)
        if not samples:
            return {"steps": 0, "total_steps": self._n,
                    "tok_s": None, "mean_ms": None, "p50_ms": None,
                    "p99_ms": None}
        walls = sorted(s[0] for s in samples)
        total_wall = sum(walls)
        total_tok = sum(s[1] for s in samples)
        return {
            "steps": len(samples),
            "total_steps": self._n,
            "tok_s": (total_tok / total_wall) if total_wall > 0 else None,
            "mean_ms": total_wall / len(walls) * 1e3,
            "p50_ms": _pct(walls, 0.50) * 1e3,
            "p99_ms": _pct(walls, 0.99) * 1e3,
        }


def roofline(artifact, decode_tok_s, *, pallas_launches=None,
             mode: str | None = None, arch: str | None = None) -> dict:
    """Per-site shift-add budget x measured throughput -> achieved adds/s."""
    rep = artifact.report
    total_lcc = rep.total_stage("lcc")
    tok_s = None if decode_tok_s is None else float(decode_tok_s)
    sec = {
        "mode": mode, "arch": arch,
        "total_baseline_adds": rep.total_baseline(),
        "total_lcc_adds": total_lcc,
        "decode_tok_s_n8": round(tok_s, 2) if tok_s is not None else None,
        "pallas_launches": pallas_launches,
        "achieved_adds_per_s": (round(tok_s * total_lcc)
                                if tok_s is not None else None),
        "sites": [{"site": l.name, "baseline_adds": l.baseline_adds,
                   "lcc_adds": l.stage_adds.get("lcc"),
                   "ratio": (round(l.ratio("lcc"), 2)
                             if l.stage_adds.get("lcc") else None),
                   "achieved_adds_per_s": (
                       round(tok_s * l.stage_adds["lcc"])
                       if tok_s is not None and l.stage_adds.get("lcc")
                       else None)}
                  for l in rep.layers],
    }
    stats = getattr(artifact, "pipeline_stats", None) or {}
    waste = stats.get("padding_waste")
    if waste:
        sec["padding_waste"] = waste
    return sec


def live_roofline(engine) -> dict | None:
    """Roofline table from a *running* compressed engine's own telemetry:
    artifact from the executor, tok/s from ``engine.profiler``, launch count
    from the per-bucket trace registry.  ``None`` for dense engines or when
    the profiler hasn't accumulated any decode steps yet."""
    art = getattr(engine, "artifact", None)
    prof = getattr(engine, "profiler", None)
    if art is None or prof is None:
        return None
    summ = prof.summary()
    if not summ["steps"]:
        return None
    sec = roofline(
        art, summ["tok_s"],
        pallas_launches=engine.pallas_launches_per_step,
        mode="live", arch=getattr(engine.cfg, "name", None))
    sec["profiler"] = summ
    return sec
