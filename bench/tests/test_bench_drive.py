"""Where a traced run's profiled slice lies: after the close in an open loop,
before the opening in a closed one, and in neither case inside the window
or in its counts.  A scheduler stand-in serves fixed-length requests, one
token per slot per step."""
import time
from collections import deque
from types import SimpleNamespace

import jax
import pytest

import bench_testlib  # noqa: F401  (puts the harness on the path)
from harness import drive
from harness.traffic import Plan, Request

STEP_S = 0.002


class FakeScheduler:
    def __init__(self, slots: int):
        self.slots, self.queue, self.live = slots, deque(), {}
        self.results, self.next_id = {}, 0

    @property
    def pending(self):
        return len(self.queue)

    @property
    def inflight(self):
        return len(self.live)

    def enqueue(self, prompt, max_new):
        sid, self.next_id = self.next_id, self.next_id + 1
        self.queue.append((sid, max_new))
        self.results[sid] = None
        return sid

    def admit(self):
        out = []
        while self.queue and len(self.live) < self.slots:
            sid, n = self.queue.popleft()
            self.live[sid] = n
            out.append(sid)
        return out

    def step(self):
        time.sleep(STEP_S)
        events = []
        for sid in list(self.live):
            self.live[sid] -= 1
            done = self.live[sid] == 0
            if done:
                del self.live[sid]
            events.append(SimpleNamespace(rid=sid, token=1, finished=done))
        return events


@pytest.fixture
def profiler(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", drive.clock())))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", drive.clock())))
    return calls


def _requests(n, *, client_of, due_of, max_new):
    return [Request(rid=i, client=client_of(i), due_s=due_of(i), prompt=[1],
                    max_new=max_new) for i in range(n)]


def test_closed_loop_traces_before_the_opening(profiler):
    # the first generation (10 steps) ends well inside the 0.1 s slice even
    # on a loaded host; the next (200 steps each) outlasts slice and window
    clients, per_client = 4, 3
    plan = Plan(loop="closed", requests=_requests(
        clients * per_client, client_of=lambda i: i // per_client,
        due_of=lambda i: 0.0, max_new=200))
    for r in plan.requests[::per_client]:
        r.max_new = 10
    sched = FakeScheduler(clients)
    queues = {c: deque(plan.client_queue(c)) for c in range(clients)}
    pre = {}
    for c in range(clients):
        req = queues[c].popleft()
        pre[sched.enqueue(req.prompt, req.max_new)] = drive.Record(
            rid=req.rid, due=0.0, prompt_len=1, max_new=req.max_new)
    sched.admit()
    t_call = drive.clock()
    win = drive.run_window(sched, SimpleNamespace(state=None), plan, 0.1,
                           preloaded=pre, closed_queues=queues,
                           trace_seconds=0.1, trace_dir="unused")
    (_, t_start), (_, t_stop) = profiler
    assert t_call <= t_start < t_stop
    assert win.trace[0] < 0 and win.trace[1] == 0.0
    # the slice's 10-token requests finished before the opening and left
    # the records; the window counts only its own tokens
    first_gen = {r.rid for r in pre.values()}
    assert not first_gen & {r.rid for r in win.records.values()}
    assert sum(n for *_, n in win.steps) == \
        sum(r.n_tok for r in win.records.values())
    assert all(r.n_tok <= r.max_new for r in win.records.values())
    assert win.steps[0][0] >= 0


def test_open_loop_traces_after_the_close(profiler):
    plan = Plan(loop="open", requests=_requests(
        40, client_of=lambda i: 0, due_of=lambda i: i * 0.01, max_new=5))
    sched = FakeScheduler(4)
    t_call = drive.clock()
    win = drive.run_window(sched, SimpleNamespace(state=None), plan, 0.2,
                           trace_seconds=0.1, trace_dir="unused")
    (_, t_start), (_, t_stop) = profiler
    assert t_start - t_call >= win.seconds >= 0.2
    assert win.trace[0] >= win.seconds
    assert all(r.due < 0.2 for r in win.records.values())
    assert all(tc <= win.seconds for _, tc, _ in win.steps)
