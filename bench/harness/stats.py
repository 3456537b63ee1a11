"""Metric arithmetic over a window's request records and step spans.

Latency of an open-loop request is timed from when it was *due*, so a stall
also counts against every request it delays.  Percentiles are over all
requests due in the window; a request with no first token by the close
counts at the time it had waited when the window closed.  Rates are over all
the work and all the time of the window.
"""
from __future__ import annotations

import numpy as np

from .drive import Window

__all__ = ["pctl", "due_in_window", "ttft_s", "tpot_s", "queue_wait_s",
           "decode_tokens", "step_mean_s", "admit_pass_s"]


def pctl(values, q: float) -> float | None:
    """The ``q``-th percentile (linear between order statistics), or None
    for no values."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def due_in_window(win: Window):
    return [r for r in win.records.values() if 0.0 <= r.due < win.seconds]


def ttft_s(win: Window) -> list[float]:
    return [(r.first if r.first is not None else win.seconds) - r.due
            for r in due_in_window(win)]


def tpot_s(win: Window) -> list[float]:
    """(last token so far - first) / (tokens so far - 1), requests with two
    tokens or more."""
    return [(r.last - r.first) / (r.n_tok - 1)
            for r in due_in_window(win) if r.n_tok >= 2]


def queue_wait_s(win: Window) -> list[float]:
    return [(r.admit if r.admit is not None else win.seconds) - r.due
            for r in due_in_window(win)]


def decode_tokens(win: Window) -> int:
    return int(sum(n for _, _, n in win.steps))


def step_mean_s(win: Window) -> float | None:
    """The window's decode-step time over its steps (host clock, synced)."""
    if not win.steps:
        return None
    return float(sum(b - a for a, b, _ in win.steps) / len(win.steps))


def admit_pass_s(win: Window) -> list[float]:
    """Synced wall of each admission pass that admitted a request."""
    return [b - a for a, b, _ in win.admits]

