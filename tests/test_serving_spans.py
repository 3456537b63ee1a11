"""The serving path's own spans and phase counters (``repro.obs.spans``):
every span of the map recorded under a real ``jax.profiler`` trace and
nested as the map says, phase seconds that add up without double counting,
exact prefill position counts, compiles charged to the span that caused
them, and the LCC launches of a compressed decode step named by their site
group."""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs import get_arch, reduced_config
from repro.models import api
from repro.obs import MetricsRegistry, get_global, span
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import Scheduler

REQUEST_SCOPED = ("serving.submit", "serving.submit.pool",
                  "serving.submit.prefill", "serving.submit.scatter",
                  "serving.step.release")
# each span of the map and the span it opens inside (None: outermost)
PARENT = {"serving.admit": None, "serving.submit": "serving.admit",
          "serving.submit.pool": "serving.submit",
          "serving.submit.prefill": "serving.submit",
          "serving.submit.scatter": "serving.submit",
          "serving.step": None, "serving.step.grow": "serving.step",
          "serving.step.upload": "serving.step",
          "serving.step.dispatch": "serving.step",
          "serving.step.wait": "serving.step",
          "serving.step.events": "serving.step",
          "serving.step.release": "serving.step.events",
          "serving.deliver": None}


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=128,
                         n_layers=2)
    return cfg, api.init_params(jax.random.PRNGKey(0), cfg)


def _counter(reg, name) -> dict:
    m = reg.get(name)
    return {} if m is None else {tuple(r["labels"].values()): r["value"]
                                 for r in m.values()}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("serving."):
                        out.append((e.name, float(e.start_ns),
                                    float(e.start_ns + e.duration_ns),
                                    {k: v for k, v in e.stats}))
    return out


def test_every_span_of_the_map_is_recorded_and_nested(tiny_model, tmp_path):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64, tracer=True)
    sched = Scheduler(eng)
    sched.enqueue(list(range(1, 21)), max_new=3)
    sched.enqueue(list(range(5, 15)), max_new=20)  # grows past its block
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched.run()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert {n for n, *_ in spans} == set(PARENT)
    for name, s, e, stats in spans:
        around = [o for o in spans if o[1] <= s and e <= o[2]
                  and o[2] - o[1] > e - s]
        parent = min(around, key=lambda o: o[2] - o[1])[0] if around else None
        assert parent == PARENT[name], (name, parent)
        if name in REQUEST_SCOPED:
            assert "rid" in stats, name
    submits = [st for n, *_, st in spans if n == "serving.submit"]
    assert len(submits) == 2
    assert all({"sid", "slot", "prompt_len", "cached"} <= set(st)
               for st in submits)
    steps = [st["step_num"] for n, *_, st in spans if n == "serving.step"]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)


def test_phase_own_time_leaves_nested_phases_out():
    reg = MetricsRegistry()
    phases = reg.counter("p", labels=("phase",))
    t0 = time.perf_counter()
    with span("serving.outer", phases=phases):
        time.sleep(0.01)
        with span("serving.outer.inner", phases=phases, rid=3):
            time.sleep(0.01)
    wall = time.perf_counter() - t0
    outer, inner = phases.get(phase="outer"), phases.get(phase="outer.inner")
    assert inner >= 0.01 and outer >= 0.01
    assert outer + inner <= wall  # the nested phase is not counted twice


def test_phase_seconds_stay_within_the_scheduler_step(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64)
    sched = Scheduler(eng)
    for n in (12, 30, 7):
        sched.enqueue(list(range(1, n + 1)), max_new=18)
    sched.step()  # compiles
    steps = 0
    while sched.pending or sched.inflight:
        before = _counter(eng.metrics, "serving_phase_seconds_total")
        t0 = time.perf_counter()
        sched.step()
        wall = time.perf_counter() - t0
        d = _delta(_counter(eng.metrics, "serving_phase_seconds_total"),
                   before)
        assert 0 < sum(d.values()) <= wall
        assert ("step.wait",) in d and ("deliver",) in d
        steps += 1
    assert steps > 10


def test_a_cancel_is_not_charged_to_the_step_release_phase(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64)
    name = "serving_phase_seconds_total"
    rid = eng.submit(list(range(1, 21)), max_new=8)
    eng.step()
    before = _counter(eng.metrics, name)
    assert eng.cancel(rid)
    assert ("step.release",) not in _delta(_counter(eng.metrics, name),
                                           before)
    rid = eng.submit(list(range(1, 5)), max_new=1)
    while not eng.results[rid].finished:
        eng.step()
    assert _counter(eng.metrics, name)[("step.release",)] > 0


def test_prefill_positions_cold_and_prefix_hit(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=256)
    prompt = [int(t) for t in np.arange(100) % 97 + 1]
    name = "serving_prefill_tokens_total"
    eng.submit(prompt, max_new=4)
    assert _counter(eng.metrics, name) == {
        ("prompt",): 100, ("cached",): 0, ("pad",): 28}
    before = _counter(eng.metrics, name)
    rid = eng.submit(prompt, max_new=4)  # six full blocks resident
    cached = eng.results[rid].stats["cached_tokens"]
    assert cached == 96
    assert _delta(_counter(eng.metrics, name), before) == {
        ("prompt",): 4, ("cached",): 96, ("pad",): 4}  # tail 4, bucket 8


def test_compiles_are_charged_to_the_span_open_around_them(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, n_slots=1, max_len=128)
    name = "serving_compiles_total"
    rid = eng.submit(list(range(50, 70)), max_new=4)
    eng.step()
    eng.cancel(rid)
    before = _counter(get_global(), name)
    # a new length and bucket; no prompt here shares a prefix with another
    rid = eng.submit(list(range(1, 38)), max_new=4)
    new = _delta(_counter(get_global(), name), before)
    assert new and all(k[0].startswith("serving.submit") for k in new), new
    assert new.get(("serving.submit.prefill",), 0) >= 1
    eng.cancel(rid)
    before = _counter(get_global(), name)
    eng.submit(list(range(2, 39)), max_new=4)  # the same length, cold
    assert _delta(_counter(get_global(), name), before) == {}


LCC_LAUNCHES = {"_lcc_group_matmul_qkv", "_lcc_chain_matmul_o",
                "_lcc_group_matmul_gate_up", "_lcc_chain_matmul_down"}


@pytest.fixture(scope="module")
def lcc_model():
    """A reduced olmo-1b compressed at every site, and its decode step's
    argument shapes for an 8-slot engine."""
    cfg = reduced_config(get_arch("olmo-1b"), vocab=128)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, core.CompressionConfig(
        algorithm="fp", target_snr_db=20.0))
    return cfg, art


def _step_shapes(eng):
    z = jnp.zeros(8, jnp.int32)
    args = (eng.params, eng.state, z, z, jnp.zeros(8, bool), z, z,
            jnp.zeros(8, jnp.float32), jnp.asarray(eng._keys), np.int32(-1))
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
                        args)


def test_lcc_launches_are_named_by_site_group(lcc_model):
    """The compiled formulation of a compressed decode step, lowered for
    the TPU without one: one launch name per site group, each matching
    the roofline reader's pattern."""
    _, art = lcc_model
    eng = ServingEngine(artifact=art, n_slots=8, max_len=64, interpret=False)
    text = eng._step_fn.trace(*_step_shapes(eng)).lower(
        lowering_platforms=("tpu",)).as_text()
    names = set(re.findall(r'kernel_name = "(_lcc_[a-z_]+)"', text))
    assert names == LCC_LAUNCHES
    assert all(re.search(r"_lcc_(chain|group)_matmul", n) for n in names)


@pytest.mark.parametrize("path", ["decode_step", "interpreter", "wide_first"])
def test_lcc_launches_counted_by_body(lcc_model, path):
    """``lcc_launches_total`` counts each LCC launch a trace emits under the
    body that runs it: tracing the compiled formulation of a compressed
    decode step counts every site group once a layer under the row gather
    with a one-hot first factor, as the shape rule picks for slices of at
    most ``DENSE_FIRST_MAX`` columns; a grouped launch on this host runs
    the interpreter's gather; a wider first factor is gathered too."""
    from repro.kernels import ops
    from repro.kernels.lcc_chain_matmul import (DENSE_FIRST_MAX,
                                                lcc_chain_matmul, pack_terms)

    cfg, art = lcc_model
    before = _counter(get_global(), "lcc_launches_total")
    if path == "decode_step":
        eng = ServingEngine(artifact=art, n_slots=8, max_len=64,
                            interpret=False)
        eng._step_fn.trace(*_step_shapes(eng))
        want = {(name, "row_gather+dense_first"): cfg.n_layers
                for name in LCC_LAUNCHES}
    elif path == "interpreter":
        recs = [art.records[n] for n in sorted(art.records)
                if n.startswith(("attn.q.", "attn.k."))][:2]
        pg = ops.pack_group([ops.pack_decomposition(r.decomposition)
                             for r in recs])
        ops.apply_packed_group(pg, [jnp.ones((r.decomposition.shape[1], 4))
                                    for r in recs], name="_lcc_probe")
        want = {("_lcc_probe", "interpret"): 1}
    else:  # a slice wider than the shape rule gives one-hot tiles
        w = DENSE_FIRST_MAX + 8
        idx = np.zeros((1, 1, 8, 2), np.int32)
        exp = sign = np.ones((1, 1, 8, 2), np.int8)
        lcc_chain_matmul(idx, exp, sign, jnp.ones((1, w, 8)), block_b=8,
                         first_width=w, interpret=True, use_gather=False,
                         name="_lcc_wide", terms=pack_terms(idx, exp, sign))
        want = {("_lcc_wide", "row_gather"): 1}
    new = _delta(_counter(get_global(), "lcc_launches_total"), before)
    assert new == want
