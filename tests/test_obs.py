"""Telemetry subsystem: registry semantics, Prometheus round-trip, span
lifecycle completeness (every admitted request retires exactly one span —
including cancel / error / pool-exhaustion paths), step profiler + roofline,
and a serving smoke bounding full-telemetry decode overhead at 3%.
"""
import json
import threading
import time
import types

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced_config
from repro.models import api
from repro.obs import (MetricsRegistry, RequestTracer, StepProfiler,
                       dump_metrics, merged_snapshot, parse_prometheus,
                       roofline)
from repro.pipeline.events import CompressionEvent, EventEmitter
from repro.serving.engine import ServingEngine
from repro.serving.kvpool import POOL_STAT_KEYS
from repro.serving.scheduler import Scheduler
from repro.training.trainer import record_step_metrics


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    return cfg, api.init_params(jax.random.PRNGKey(0), cfg)


# ------------------------------------------------------------------ registry


def test_histogram_bucket_edges_le_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 10.0, 99.0):
        h.observe(v)
    row = h.values()[0]
    assert row["count"] == 6
    assert row["sum"] == pytest.approx(110.65)
    # le semantics: an observation equal to an edge lands in that bucket;
    # values() is cumulative and closes with +Inf
    assert row["buckets"] == {"0.1": 2, "1": 4, "10": 5, "+Inf": 6}


def test_histogram_rejects_non_ascending_edges():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="ascend"):
        reg.histogram("bad", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError, match="ascend"):
        reg.histogram("bad2", buckets=(2.0, 1.0))
    with pytest.raises(ValueError, match="ascend"):
        reg.histogram("bad3", buckets=())
    reg.histogram("ok")  # default edges are valid


def test_registry_get_or_create_and_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("req", "requests", labels=("kind",))
    c2 = reg.counter("req", "requests", labels=("kind",))
    assert c1 is c2
    c1.inc(2, kind="a")
    c2.inc(1, kind="a")
    assert c1.get(kind="a") == 3
    with pytest.raises(ValueError):
        reg.gauge("req")  # type mismatch under the same name
    with pytest.raises(ValueError):
        reg.counter("req", labels=("other",))  # label-set mismatch
    with pytest.raises(ValueError):
        c1.inc(1, wrong="x")  # undeclared label on update
    with pytest.raises(ValueError):
        c1.inc(-1, kind="a")  # counters only go up
    g = reg.gauge("temp")
    g.set(5)
    g.dec(2)
    assert g.value == 3


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("requests_total", "by status", labels=("status",)).inc(
        3, status="ok")
    reg.counter("requests_total", labels=("status",)).inc(
        1, status='err "q"\nnewline')  # exercises label escaping
    reg.gauge("slots", "decode slots").set(8)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# TYPE lat_seconds histogram" in text
    assert 'le="+Inf"' in text
    # the parsed exposition is exactly the registry's flat view
    assert parse_prometheus(text) == reg.flat()


def test_merged_snapshot_and_dump(tmp_path):
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("only_a").inc(1)
    a.gauge("shared").set(1)
    b.gauge("shared").set(2)  # later registry wins on collision
    merged = merged_snapshot([a, b])
    assert merged["shared"]["values"][0]["value"] == 2
    out = tmp_path / "metrics.json"
    dump_metrics(str(out), [a, b], trace_summary={"completed": 4})
    payload = json.loads(out.read_text())
    assert set(payload) == {"metrics", "trace_summary"}
    assert payload["metrics"]["only_a"]["type"] == "counter"
    assert payload["trace_summary"]["completed"] == 4


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("h", buckets=(0.5, 1.5))
    g = reg.gauge("g")
    errs = []

    def work():
        try:
            for j in range(1000):
                c.inc()
                h.observe(j % 2)
                g.set(j)
                if j % 200 == 0:  # concurrent exports must stay consistent
                    reg.to_prometheus()
                    reg.snapshot()
        except Exception as e:  # pragma: no cover - only on a race
            errs.append(e)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert c.value == 8000
    row = h.values()[0]
    assert row["count"] == 8000 and row["buckets"]["+Inf"] == 8000


# -------------------------------------------------------------------- tracer


def test_tracer_lifecycle_deterministic(tmp_path):
    clk = FakeClock()
    reg = MetricsRegistry()
    tr = RequestTracer(mark_every=2, metrics=reg, clock=clk)
    sid = tr.enqueue(0, prompt_len=4)
    clk.tick(1.0)
    tr.admit(sid)
    for _ in range(3):
        clk.tick(1.0)
        tr.token(sid)
    tr.annotate(sid, cached_tokens=2, prefill_kind="paged")
    clk.tick(1.0)
    span = tr.retire(sid, status="ok")
    assert span.queue_wait_s == pytest.approx(1.0)
    assert span.ttft_s == pytest.approx(2.0)  # measured from enqueue
    assert span.tpot_s == pytest.approx(1.0)
    assert span.e2e_s == pytest.approx(5.0)
    assert span.n_tokens == 3 and span.marks == [(2, 3.0)]
    assert tr.retire(sid) is None  # idempotent: one span, one retirement
    assert len(tr.completed) == 1 and tr.open_count == 0
    with pytest.raises(ValueError):
        tr.retire(sid, status="bogus")
    d = span.to_dict()
    assert d["cached_tokens"] == 2  # meta folded into the record
    assert d["marks"] == [{"tokens": 2, "t_s": 3.0}]
    # registry side-effects of retirement
    assert reg.get("serving_requests_total").get(status="ok") == 1
    assert reg.get("serving_ttft_seconds").values()[0]["count"] == 1

    tr.enqueue(1, prompt_len=2)  # left open on purpose
    out = tmp_path / "trace.jsonl"
    assert tr.dump_jsonl(str(out)) == 1
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert [r["status"] for r in rows] == ["ok", "open"]
    summ = tr.summary()
    assert summ["by_status"] == {"ok": 1} and summ["open"] == 1
    assert summ["e2e_s"]["p50"] == pytest.approx(5.0)


# ---------------------------------------------------------------- profiler


def test_step_profiler_ring_and_summary():
    clk = FakeClock()
    prof = StepProfiler(capacity=4, clock=clk)
    for dt in (0.01, 0.02, 0.03, 0.04, 0.05):
        t0 = prof.begin()
        clk.tick(dt)
        prof.end(t0, tokens=10)
    assert len(prof) == 4 and prof.total_steps == 5  # ring dropped the oldest
    summ = prof.summary()
    assert summ["steps"] == 4 and summ["total_steps"] == 5
    assert summ["tok_s"] == pytest.approx(40 / 0.14)
    assert summ["p99_ms"] == pytest.approx(50.0)


def test_roofline_shape_from_fake_artifact():
    class _Layer:
        def __init__(self, name, base, lcc):
            self.name, self.baseline_adds = name, base
            self.stage_adds = {"lcc": lcc}

        def ratio(self, stage):
            return self.baseline_adds / self.stage_adds[stage]

    rep = types.SimpleNamespace(
        layers=[_Layer("ffn_in", 60, 30), _Layer("ffn_out", 40, 10)],
        total_baseline=lambda: 100, total_stage=lambda s: 40)
    art = types.SimpleNamespace(report=rep,
                                pipeline_stats={"padding_waste": 0.125})
    sec = roofline(art, 50.0, pallas_launches=3, n_layer_plans=3,
                   mode="live", arch="olmo-1b")
    assert sec["achieved_adds_per_s"] == 2000
    assert sec["sites"][0] == {"site": "ffn_in", "baseline_adds": 60,
                               "lcc_adds": 30, "ratio": 2.0,
                               "achieved_adds_per_s": 1500}
    assert sec["padding_waste"] == 0.125
    assert sec["pallas_launches"] == sec["n_layer_plans"] == 3


# ------------------------------------------------- pipeline / training hooks


def test_event_emitter_feeds_registry():
    reg = MetricsRegistry()
    seen = []
    em = EventEmitter(progress=seen.append, metrics=reg)
    em("plan", detail="2 units")
    em("slice_done", unit="u0", wall_s=0.2)
    em("slice_done", unit="u1", wall_s=0.3)
    ev = reg.get("pipeline_events_total")
    assert ev.get(kind="slice_done") == 2 and ev.get(kind="plan") == 1
    wall = reg.get("pipeline_job_wall_seconds").values()[0]
    assert wall["count"] == 2 and wall["sum"] == pytest.approx(0.5)
    assert len(seen) == 3 and isinstance(seen[0], CompressionEvent)


def test_record_step_metrics():
    record_step_metrics(None, {"loss": 1.0})  # registry-less: a no-op
    reg = MetricsRegistry()
    record_step_metrics(reg, {"loss": np.float32(1.5), "grad_norm": 2.0,
                              "shape": (3, 4)}, step=7)
    assert reg.get("train_steps_total").value == 1
    assert reg.get("train_step").value == 7
    assert reg.get("train_loss").value == pytest.approx(1.5)
    assert "train_shape" not in reg  # non-scalar extras stay out


# ------------------------------------------------------- serving integration


def test_span_lifecycle_serving_all_paths(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64, tracer=True)
    sched = Scheduler(eng)

    def broken_consumer(rid, tok):
        raise RuntimeError("consumer died")

    ok = [sched.enqueue([1, 2, 3, 4], max_new=6) for _ in range(3)]
    bad = sched.enqueue([], max_new=4)  # invalid prompt -> error span
    boom = sched.enqueue([5, 6, 7], max_new=32, on_token=broken_consumer)
    sched.run()

    tr = eng.tracer
    assert tr.open_count == 0  # every admitted request retired exactly once
    assert len(tr.completed) == 5
    assert len({s.sid for s in tr.completed}) == 5
    by = {st: len(tr.spans(st)) for st in ("ok", "error", "cancelled")}
    assert by == {"ok": 3, "error": 1, "cancelled": 1}
    for rid in ok:
        r = sched.take_result(rid)
        assert r.error is None and len(r.tokens) == 4 + 6
    assert "empty prompt" in sched.take_result(bad).error
    assert "streaming callback failed" in sched.take_result(boom).error
    for s in tr.spans("ok"):
        assert s.n_tokens == 6
        assert s.queue_wait_s is not None and s.ttft_s > 0 and s.tpot_s > 0
        assert s.meta["prefill_kind"] in ("paged", "bulk", "tokenwise")
    # the engine's registry saw the same lifecycle
    m = eng.metrics
    req = m.get("serving_requests_total")
    assert {st: req.get(status=st) for st in by} == {
        "ok": 3, "error": 1, "cancelled": 1}
    assert m.get("serving_decode_steps_total").value == eng.step_dispatches
    assert m.get("serving_tokens_total").value >= 3 * 6
    assert m.get("sched_pending").value == 0
    assert m.get("sched_inflight").value == 0
    ps = eng.pool_stats()
    assert m.get("serving_kv_pool").get(stat="n_blocks") == ps["n_blocks"]

    # explicit engine-side cancel mid-decode also closes the span
    rid = sched.enqueue([1, 2, 3], max_new=50)
    sched.step()
    erid = next(iter(sched._inflight))
    eng.cancel(erid)
    sched.run()
    assert sched.take_result(rid).stats.get("cancelled") is True
    assert len(tr.spans("cancelled")) == 2 and tr.open_count == 0


def test_span_pool_exhaustion_path(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=128, kv_block=8,
                        kv_blocks=7, prefix_cache=False, tracer=True)
    assert eng.paged
    sched = Scheduler(eng)
    rid = sched.enqueue(list(range(2, 50)), max_new=40)  # 6 blocks + reserve
    sched.run()
    r = sched.take_result(rid)
    assert r.error is not None and "exhausted" in r.error
    spans = eng.tracer.spans("error")
    assert len(spans) == 1 and spans[0].meta.get("exhausted") is True
    assert eng.tracer.open_count == 0
    assert eng.metrics.get("serving_pool_exhausted_total").value == 1


def test_pool_stats_unified_key_set(tiny_model):
    cfg, params = tiny_model
    paged = ServingEngine(params, cfg, n_slots=1, max_len=32)
    contig = ServingEngine(params, cfg, n_slots=1, max_len=32, kv_block=None)
    assert paged.paged and not contig.paged
    ps, cs = paged.pool_stats(), contig.pool_stats()
    assert tuple(ps) == tuple(cs) == POOL_STAT_KEYS
    assert ps["n_blocks"] > 0  # the discriminant callers branch on
    assert cs["n_blocks"] == 0
    assert all(v == 0 for v in cs.values())


def test_metrics_disabled_engine_has_no_registry(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=1, max_len=32, metrics=False)
    assert eng.metrics is None and eng.profiler is None and eng.tracer is None
    rid = eng.submit([1, 2, 3], max_new=4)
    while eng.active.any():
        eng.step()
    assert eng.results[rid].finished  # plain serving path is untouched


def test_serving_telemetry_overhead_within_bound():
    """Decode step wall with full telemetry (registry + tracer + profiler +
    span marks) within 3% of a metrics=False engine.

    Methodology: single-step alternation between two pre-primed engines
    (shared-noise windows), alternation order rotated per round (no position
    bias), compared on per-step *medians* (robust to scheduler hiccups).
    Host noise only ever inflates a measurement, so each attempt is an upper
    bound on the true overhead — the bound must hold for the best of three
    attempts, not every sample of a noisy estimator."""
    cfg = reduced_config(get_arch("olmo-1b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    n_slots, prompt_len, rounds, attempts = 8, 8, 150, 3

    def prime(**kw):
        eng = ServingEngine(params, cfg, n_slots=n_slots, max_len=512, **kw)
        sched = Scheduler(eng)
        for i in range(n_slots):
            sched.enqueue(list(range(2, 2 + prompt_len)), max_new=eng.max_len)
        for _ in range(2):  # admit + compile + settle
            sched.step()
        return eng, sched

    engines = {"on": prime(tracer=True), "off": prime(metrics=False)}

    def measure() -> float:
        walls = {k: [] for k in engines}
        order = list(engines)
        for i in range(rounds):
            for k in order[i % 2:] + order[:i % 2]:
                sched = engines[k][1]
                t0 = time.perf_counter()
                sched.step()
                walls[k].append(time.perf_counter() - t0)
        med = {k: sorted(w)[len(w) // 2] for k, w in walls.items()}
        return med["on"] / med["off"] - 1.0

    overhead = float("inf")
    for _ in range(attempts):
        overhead = min(overhead, measure())
        if overhead <= 0.03:
            break
    # neither batch drained: every timed step decoded all n_slots slots
    assert all(e.active.sum() == n_slots for e, _ in engines.values())
    assert engines["on"][0].profiler.total_steps > rounds
    assert overhead <= 0.03, (
        f"telemetry overhead {overhead:.2%} exceeds the 3% budget")
