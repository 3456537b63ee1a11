"""Drive the program's scheduler with a traffic plan for one measured window.

The window opens at ``t0`` and runs ``seconds``; the loop stops at its first
check at or after the close, so a step in flight at the close is counted
whole and the window's length is the time until that step ended.  Every
request due before the close is sent (open loop); a closed loop's client
sends its next request at the step its last one finished.

A traced run serves a further ``trace_seconds`` under the profiler, outside
the window, so starting and stopping the profiler, which stalls the host
for a second or more, delays nothing the window measures.  An open loop's
slice follows the close and serves the plan's requests due after it; none
of them enters the window's records.  A closed loop's slice comes before the
window opens, with every client decoding as in the window: after the close
it would meet the first generation's completions and hold more refill
prefills than the window does.  Its tokens are not the window's; a request
it finishes leaves the records, and one it sends joins them as in flight at
the opening.

Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock when a
trace is running) name what the host does: ``bench.admit`` (admission and
prefill, synced), ``bench.step`` (one scheduler step: the fused decode step
and its host bookkeeping), ``bench.book`` (the harness's own bookkeeping)
and ``bench.idle`` (waiting for the next due request).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax

from .traffic import Plan

__all__ = ["Record", "Window", "admit_all", "run_window"]

clock = time.perf_counter


@dataclass
class Record:
    """One request, times in seconds from the window's opening."""
    rid: int  # plan id
    due: float
    prompt_len: int
    max_new: int
    admit: float | None = None
    first: float | None = None
    last: float | None = None
    n_tok: int = 0
    done: bool = False
    error: str | None = None


@dataclass
class Window:
    seconds: float  # as measured: open to the end of the last step
    records: dict[int, Record]  # scheduler id -> record
    steps: list[tuple[float, float, int]] = field(default_factory=list)
    admits: list[tuple[float, float, int]] = field(default_factory=list)
    trace: tuple[float, float] | None = None  # traced slice, from the opening
    trace_dir: str | None = None


def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


def admit_all(sched, engine) -> list[int]:
    """Admit what fits and wait for the prefills to land."""
    admitted = sched.admit()
    if admitted:
        jax.block_until_ready(engine.state)
    return admitted


def _enqueue(sched, records, req, due):
    sid = sched.enqueue(req.prompt, max_new=req.max_new)
    records[sid] = Record(rid=req.rid, due=due, prompt_len=len(req.prompt),
                          max_new=req.max_new)
    if sched.results.get(sid) is not None and sched.results[sid].error:
        records[sid].error = sched.results[sid].error
    return sid


def run_window(sched, engine, plan: Plan, seconds: float, *,
               preloaded: dict[int, Record] | None = None,
               closed_queues: dict[int, deque] | None = None,
               trace_seconds: float | None = None,
               trace_dir: str | None = None) -> Window:
    """Run one window.  ``preloaded``: closed-loop requests admitted before
    the window (scheduler id -> record, times relative to the opening);
    ``closed_queues``: each closed-loop client's remaining requests;
    ``trace_seconds``: length of the profiled slice (after the close in an
    open loop, before the opening in a closed one)."""
    records: dict[int, Record] = dict(preloaded or {})
    client_of = {sid: plan.requests[r.rid].client
                 for sid, r in records.items()}
    pending = deque(plan.requests) if plan.loop == "open" else deque()
    win = Window(seconds=seconds, records=records, trace_dir=trace_dir)
    if trace_seconds is not None and plan.loop == "closed":
        win.trace = _closed_slice(sched, engine, records, closed_queues,
                                  client_of, trace_seconds, trace_dir)
        trace_seconds = None
    end = seconds  # of the window, then of the traced slice
    in_window = True
    t0 = clock()
    while True:
        now = clock() - t0
        if not in_window and now >= end:
            jax.profiler.stop_trace()
            win.trace = (end - trace_seconds, clock() - t0)
            break
        with _span("bench.book"):
            while pending and pending[0].due_s <= now and (
                    pending[0].due_s < seconds or not in_window):
                req = pending.popleft()
                sid = _enqueue(sched, records, req, req.due_s)
                if not in_window:
                    del records[sid]
        if in_window and now >= seconds:
            win.seconds, in_window = now, False
            if trace_seconds is None:
                break
            jax.profiler.start_trace(trace_dir)
            end = clock() - t0 + trace_seconds
            continue
        if sched.pending == 0 and sched.inflight == 0:
            nxt = pending[0].due_s if pending else end
            with _span("bench.idle"):
                time.sleep(max(0.0, min(nxt, end) - now))
            continue
        ta = clock() - t0
        with _span("bench.admit"):
            admitted = admit_all(sched, engine)
        tb = clock() - t0
        if admitted and in_window:
            win.admits.append((ta, tb, len(admitted)))
            for sid in admitted:
                if sid in records:
                    records[sid].admit = ta
        with _span("bench.step"):
            events = sched.step()
        tc = clock() - t0
        n = 0
        with _span("bench.book"):
            for ev in events:
                r = records.get(ev.rid) if in_window else None
                if ev.finished and not in_window:
                    _refill(sched, records, closed_queues, client_of, ev.rid,
                            tc, keep=False)
                if r is None:
                    continue
                if ev.token is not None:
                    n += 1
                    r.n_tok += 1
                    r.last = tc
                    if r.first is None:
                        r.first = tc
                if ev.finished:
                    r.done = True
                    res = sched.results.get(ev.rid)
                    if res is not None and res.error:
                        r.error = res.error
                    _refill(sched, records, closed_queues, client_of, ev.rid,
                            tc, keep=True)
            if in_window:
                win.steps.append((tb, tc, n))
    return win


def _closed_slice(sched, engine, records, closed_queues, client_of,
                  seconds: float, trace_dir: str) -> tuple[float, float]:
    """A closed loop's profiled slice before the window; returns its
    (start, end) in seconds before the opening, as negative numbers."""
    jax.profiler.start_trace(trace_dir)
    t0 = clock()
    while clock() - t0 < seconds and (sched.pending or sched.inflight):
        with _span("bench.admit"):
            admit_all(sched, engine)
        with _span("bench.step"):
            events = sched.step()
        with _span("bench.book"):
            for ev in events:
                if ev.finished:
                    records.pop(ev.rid, None)
                    _refill(sched, records, closed_queues, client_of, ev.rid,
                            0.0, keep=True)
    jax.profiler.stop_trace()
    t_end = clock()
    return (t0 - t_end, 0.0)


def _refill(sched, records, closed_queues, client_of, sid, now, *, keep):
    """A closed-loop client sends its next request (recorded with
    ``keep``)."""
    q = (closed_queues or {}).get(client_of.get(sid))
    if q:
        req = q.popleft()
        new = _enqueue(sched, records, req, now)
        client_of[new] = req.client
        if not keep:
            del records[new]
