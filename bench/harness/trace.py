"""Reduce a profiler trace of the device to the benchmark's numbers.

A trace is first normalised (:func:`load_xplane`) to a small dict, the form
the tests record in the repository:

    {"devices": {"<device plane>": [[op name, start_ns, dur_ns], ...]},
     "host": [[span name, start_ns, dur_ns], ...],
     "window_ns": [start, end]}

``devices`` holds each chip's "XLA Ops" line (one event per device
operation, named by the start of its HLO text: ``%fusion.12 = bf16[...]``;
a ``while`` op's event encloses the events of its body); ``host`` the
harness's own ``bench.*`` spans, on the same clock.  Every reduction below
is plain interval arithmetic over that dict.
"""
from __future__ import annotations

import glob
import os
import re

__all__ = ["load_xplane", "union", "busy_ns", "idle_pct", "idle_gaps",
           "attribute_gaps", "op_totals", "self_totals", "matching_ns",
           "spans_inside", "collective_exclusive_ns", "breakdown",
           "COLLECTIVE", "NAME_CHARS"]

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
NAME_CHARS = 96  # of an op's HLO text kept as its name


def load_xplane(trace_dir: str) -> dict:
    """Normalise the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``.  Raises when there is none, or no device operations."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host, lines_seen = {}, [], {}
    t_lo, t_hi = None, None
    for plane in pd.planes:
        names = [ln.name for ln in plane.lines]
        lines_seen[plane.name] = names
        if _DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    devices[plane.name] = [
                        [e.name[:NAME_CHARS], int(e.start_ns),
                         int(e.duration_ns)] for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    for evs in list(devices.values()) + [host]:
        for _, s, d in evs:
            t_lo = s if t_lo is None else min(t_lo, s)
            t_hi = s + d if t_hi is None else max(t_hi, s + d)
    if not any(devices.values()):
        raise ValueError(f"trace holds no device operations; planes and "
                         f"lines seen: {lines_seen}")
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host, "window_ns": [t_lo, t_hi]}


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _iv(events):
    return [(s, s + d) for _, s, d in events]


def busy_ns(events, window) -> int:
    """Time in which some operation ran on the device: the union of the
    operations' intervals, within the window."""
    return sum(e - s for s, e in union(_iv(events), *window))


def idle_pct(trace: dict) -> float:
    """Share of the traced slice in which no operation ran, 1 - busy /
    slice, in percent; the mean over the chips."""
    w = trace["window_ns"]
    shares = [1.0 - busy_ns(evs, w) / (w[1] - w[0])
              for evs in trace["devices"].values()]
    return 100.0 * sum(shares) / len(shares)


def idle_gaps(events, window) -> list[tuple[int, int]]:
    """The device's idle intervals within the window."""
    lo, hi = window
    gaps, cur = [], lo
    for s, e in union(_iv(events), lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def attribute_gaps(gaps, host) -> list[tuple[str, int]]:
    """Name each gap by the host span that overlaps it most (``none`` where
    the host ran no harness span)."""
    out = []
    for gs, ge in gaps:
        best, best_ov = "none", 0
        for name, s, d in host:
            if s >= ge:
                break
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append((best, ge - gs))
    return out


def op_totals(events, window) -> dict[str, int]:
    """Device time per operation name, clipped to the window."""
    lo, hi = window
    tot: dict[str, int] = {}
    for name, s, d in events:
        t = min(s + d, hi) - max(s, lo)
        if t > 0:
            tot[name] = tot.get(name, 0) + t
    return tot


def self_totals(events, window) -> dict[str, int]:
    """Device time per operation name less the time of the operations
    nested in it (a ``while`` loop's body), clipped to the window."""
    lo, hi = window
    evs = sorted(((s, s + d, n) for n, s, d in events),
                 key=lambda e: (e[0], -e[1]))
    own = [min(e, hi) - max(s, lo) for s, e, _ in evs]
    stack: list[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, hi) - max(s, lo)
        stack.append(i)
    tot: dict[str, int] = {}
    for (_, _, n), t in zip(evs, own):
        if t > 0:
            tot[n] = tot.get(n, 0) + t
    return tot


def spans_inside(host, window, name: str) -> list[tuple[int, int]]:
    """The host spans called ``name`` that lie whole inside the window."""
    lo, hi = window
    return [(s, s + d) for n, s, d in host
            if n == name and s >= lo and s + d <= hi]


def matching_ns(events, window, pattern) -> int:
    """Summed device time of the operations whose name matches."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return sum(t for n, t in op_totals(events, window).items()
               if rx.search(n))


def collective_exclusive_ns(events, window) -> int:
    """Time in collectives during which no other operation ran: the part of
    the exchange that compute does not hide."""
    coll = [(s, s + d) for n, s, d in events if COLLECTIVE.search(n)]
    comp = union([(s, s + d) for n, s, d in events
                  if not COLLECTIVE.search(n)], *window)
    total = 0
    for cs, ce in union(coll, *window):
        covered = sum(max(0, min(ce, e) - max(cs, s)) for s, e in comp)
        total += (ce - cs) - covered
    return total


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (their own time, summed
    over the chips) and the longest idle gaps, by what the host was doing
    in them."""
    window = trace["window_ns"]
    tot: dict[str, int] = {}
    gaps: list[tuple[str, int]] = []
    for evs in trace["devices"].values():
        for n, t in self_totals(evs, window).items():
            tot[n] = tot.get(n, 0) + t
        gaps += attribute_gaps(idle_gaps(evs, window), trace["host"])
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[n, t * 1e-9] for n, t in gaps]}
