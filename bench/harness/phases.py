"""Reduce the program's own spans and phase counters to per-layer numbers.

The serving path opens ``serving.*`` spans on the profiler's clock
(``repro.obs.spans``) and counts host seconds per phase, prefill positions
and compiles by span in its metrics registries.  This module reads them; the
normalised trace (:mod:`harness.trace`) keeps only the harness's ``bench.*``
spans, so the program's are loaded beside it:

    program = [[span name, start_ns, dur_ns], ...]   # host events serving.*

Counters are read from two ``MetricsRegistry.snapshot()`` dicts, one at the
window's opening and one at its close, so set-up and the traced slice never
enter a reading.  Every function here is plain arithmetic over those.
"""
from __future__ import annotations

import glob
import os

from .trace import idle_gaps, union

__all__ = ["PREFIX", "STEP_HOST_PHASES", "load_program", "name_gap",
           "program_gaps", "idle_covered", "admit_idle_ms", "counter_delta",
           "step_host_ms", "prefill_pad_share", "compiles_by_span"]

PREFIX = "serving."
# the host phases of a decode step outside the wait for its tokens, as
# counted in serving_phase_seconds_total (own time: nested phases apart);
# ``step`` is the step's time outside every phase nested in it
STEP_HOST_PHASES = ("step", "step.grow", "step.upload", "step.dispatch",
                    "step.events", "step.release", "deliver")


def load_program(trace_dir: str) -> list[list]:
    """The ``serving.*`` host events of the newest ``.xplane.pb`` under
    ``trace_dir``, sorted by start."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    out.append([e.name, int(e.start_ns), int(e.duration_ns)])
    out.sort(key=lambda e: e[1])
    return out


def name_gap(gs: int, ge: int, program, host) -> str:
    """Of the innermost ``serving.*`` spans over [gs, ge) — those holding no
    other span over it — the one that overlaps it most; the harness span
    that overlaps it most where no program span does; ``none`` where
    neither does.  A gap across two phases of a step is named by the phase
    that holds more of it, never by the step around both."""
    over = []
    for name, s, d in program:
        if s >= ge:
            break
        ov = min(ge, s + d) - max(gs, s)
        if ov > 0:
            over.append((ov, name, s, s + d))
    inner = [o for o in over
             if not any(p is not o and o[2] <= p[2] and p[3] <= o[3]
                        and (p[2], p[3]) != (o[2], o[3]) for p in over)]
    if inner:
        return max(inner, key=lambda o: o[0])[1]
    best, best_ov = "none", 0
    for name, s, d in host:
        if s >= ge:
            break
        ov = min(ge, s + d) - max(gs, s)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def program_gaps(trace: dict, program, top: int = 10) -> list[list]:
    """The ``top`` longest device idle gaps of the traced slice, each named
    by :func:`name_gap`: ``[[name, seconds], ...]``, longest first."""
    window = trace["window_ns"]
    gaps = []
    for evs in trace["devices"].values():
        for gs, ge in idle_gaps(evs, window):
            gaps.append((name_gap(gs, ge, program, trace["host"]), ge - gs))
    gaps.sort(key=lambda g: -g[1])
    return [[n, t * 1e-9] for n, t in gaps[:top]]


def _intersect(a, b) -> list[tuple[int, int]]:
    """The intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def idle_covered(trace: dict, program,
                 within=("bench.step", "bench.admit")) -> tuple[int, int]:
    """(device idle ns inside the harness spans named ``within``, the part
    of it inside some ``serving.*`` span), summed over the chips."""
    window = trace["window_ns"]
    outer = union([(s, s + d) for n, s, d in trace["host"] if n in within],
                  *window)
    inner = _intersect(outer, union([(s, s + d) for _, s, d in program],
                                    *window))
    idle = covered = 0
    for evs in trace["devices"].values():
        gaps = idle_gaps(evs, window)
        idle += _length(_intersect(gaps, outer))
        covered += _length(_intersect(gaps, inner))
    return idle, covered


def admit_idle_ms(trace: dict, program) -> float | None:
    """Mean, over the ``serving.admit`` spans that lie whole in the traced
    slice and admitted a request (hold a ``serving.submit`` span), of the
    device's idle time inside the span (mean over the chips), in ms."""
    lo, hi = trace["window_ns"]
    submits = [s for n, s, _ in program if n == PREFIX + "submit"]
    spans = [(s, s + d) for n, s, d in program
             if n == PREFIX + "admit" and s >= lo and s + d <= hi
             and any(s <= t < s + d for t in submits)]
    if not spans:
        return None
    devs = list(trace["devices"].values())
    merged = union(spans, lo, hi)
    idle = sum(_length(_intersect(idle_gaps(evs, (lo, hi)), merged))
               for evs in devs)
    return idle / len(devs) / len(spans) * 1e-6


def counter_delta(before: dict, after: dict, name: str) -> dict:
    """A counter's growth between two registry snapshots, by its label
    values (a one-label counter's key is the label's value)."""
    def values(snap):
        out = {}
        for row in snap.get(name, {}).get("values", []):
            labels = tuple(row["labels"].values())
            out[labels[0] if len(labels) == 1 else labels] = row["value"]
        return out

    b, a = values(before), values(after)
    return {k: v - b.get(k, 0.0) for k, v in a.items() if v - b.get(k, 0.0)}


def step_host_ms(before: dict, after: dict) -> float | None:
    """The program's host time per decode step outside the wait for its
    tokens: the step's host phases over the window's steps, in ms."""
    steps = counter_delta(before, after, "serving_decode_steps_total")
    n = sum(steps.values())
    if not n:
        return None
    ph = counter_delta(before, after, "serving_phase_seconds_total")
    return sum(ph.get(p, 0.0) for p in STEP_HOST_PHASES) / n * 1e3


def prefill_pad_share(before: dict, after: dict) -> float | None:
    """Bucket padding's share of the positions the window's prefills
    computed, pad / (prompt + pad), in percent."""
    tok = counter_delta(before, after, "serving_prefill_tokens_total")
    computed = tok.get("prompt", 0.0) + tok.get("pad", 0.0)
    return None if not computed else 100.0 * tok.get("pad", 0.0) / computed


def compiles_by_span(before: dict, after: dict) -> dict:
    """{span: [backend compiles, compile seconds]} between two snapshots of
    the process-global registry."""
    n = counter_delta(before, after, "serving_compiles_total")
    s = counter_delta(before, after, "serving_compile_seconds_total")
    return {k: [int(v), s.get(k, 0.0)] for k, v in sorted(n.items())}
