"""Serving-loop benchmark: steady-state decode throughput and prefill latency,
dense vs compressed, at n_slots in {1, 8} — emitted as machine-readable
``BENCH_serving.json`` so the perf trajectory is tracked across PRs.

Three compressed workloads exercise the site-keyed executor:
``compressed`` (FFN sites only, the historical row), ``compressed+attn``
(FFN + attention q/k/v/o through the grouped fused launches), and an MoE
section whose experts apply their chains in one grouped dispatch per layer.
Each compressed row also reports the paper's Table-1 additions metric
(``models.flops.compressed_adds``) plus the measured ``pallas_launches`` per
decode step; with the layer-plan executor active this equals ``n_layer_plans``
(one launch per identical-layer stack).  A ``roofline`` section ties each
artifact's per-site shift-add budget to the throughput it actually achieved.

Two paged-KV sections ride on the same engines:

* ``poisson`` — an arrival-trace mode: requests arrive by a Poisson process
  whose rate is calibrated to ~60% of the engine's measured service rate, and
  the scheduler admits them continuously (no drain between requests).  Reports
  sustained req/s and p50/p99 end-to-end latency, dense vs compressed, at
  ``n_slots=8``.
* ``prefix_cache`` — cold vs warm prefill for a block-aligned prompt: the warm
  repeat is a full prefix-cache hit (zero forward passes), so its latency is
  pure admission bookkeeping.

    PYTHONPATH=src python benchmarks/bench_serving.py [--smoke] [--out FILE]

CPU-container numbers measure the serving loop's dispatch/transfer overhead
(interpret-mode Pallas for the compressed path), not TPU kernel speed; the
cross-PR signal is the tok/s trend of the identical workload.
"""
from __future__ import annotations

import argparse
import gc
import json
import platform
import time

import jax


def _drive_steps(eng, n_steps: int) -> float:
    """Time n_steps fused decode steps with every slot active; returns tok/s."""
    t0 = time.time()
    for _ in range(n_steps):
        eng.step()
    dt = time.time() - t0
    return eng.n_slots * n_steps / dt


def bench_engine(make_engine, *, n_slots: int, prompt_len: int,
                 steps: int, warmup: int) -> dict:
    from repro.data.synthetic import MarkovLM

    eng = make_engine(n_slots)
    # every slot must stay active through warmup + timed steps: cap at the
    # decode headroom the KV cache leaves after the prompt
    steps = max(1, min(steps, eng.max_len - prompt_len - warmup - 1))
    lm = MarkovLM(vocab=eng.cfg.vocab, k=8, seed=0)
    prompts = [lm.sample(1, prompt_len, seed=i)[0, :prompt_len].tolist()
               for i in range(n_slots + 1)]

    # prefill: warm the bucket's compile cache with a throwaway request (one
    # generated token, then the slot frees), then time a steady-state submit
    eng.submit(prompts[0], max_new=1)
    while eng.active.any():
        eng.step()
    jax.block_until_ready(eng.state)
    t0 = time.time()
    eng.submit(prompts[1], max_new=eng.max_len)
    jax.block_until_ready(eng.state)  # async dispatch: wait for the prefill
    prefill_s = time.time() - t0
    for p in prompts[2:]:
        eng.submit(p, max_new=eng.max_len)

    for _ in range(warmup):  # compile + steady-state the fused step
        eng.step()
    tok_s = _drive_steps(eng, steps)
    assert eng.active.sum() == n_slots, "a slot finished mid-measurement"
    return {"n_slots": n_slots, "prompt_len": prompt_len,
            "steps_timed": steps,  # post-clamp, the count actually measured
            "decode_tok_s": round(tok_s, 2),
            "prefill_ms": round(prefill_s * 1e3, 2),
            "step_dispatches": eng.step_dispatches,
            # measured at the first decode trace: with layer plans active
            # these two are equal (one launch covers a whole layer stack)
            "pallas_launches": eng.pallas_launches_per_step,
            "n_layer_plans": eng.n_layer_plans,
            # why any plan fell back to the per-region route (empty = none)
            "plan_fallbacks": (eng.plan_stats()["fallbacks"]
                               if hasattr(eng, "plan_stats") else {})}


def bench_poisson(make_engine, *, n_slots: int, n_requests: int,
                  prompt_len: int, max_new: int, utilization: float = 0.6,
                  seed: int = 0) -> dict:
    """Drive a Poisson arrival trace through the continuous-batching
    scheduler; wall-clock end-to-end latency per request."""
    import numpy as np

    from repro.data.synthetic import MarkovLM
    from repro.serving.scheduler import Scheduler

    from repro.obs import RequestTracer

    eng = make_engine(n_slots)
    # span tracing drives the row's latency percentiles: the scheduler
    # opens a span per request (enqueue -> admit -> tokens -> retire), so
    # TTFT / per-token latency come from the request lifecycle itself
    # instead of ad-hoc host timestamps around the drive loop
    eng.tracer = RequestTracer(metrics=eng.metrics)
    lm = MarkovLM(vocab=eng.cfg.vocab, k=8, seed=1)
    # warm + calibrate: two full rounds through every slot — the first pays
    # compilation, the second measures the true service rate (prefill +
    # decode + host-side block bookkeeping)
    for r in range(2):
        t0 = time.time()
        for i in range(n_slots):
            p = lm.sample(1, prompt_len,
                          seed=7 + r * n_slots + i)[0, :prompt_len].tolist()
            eng.submit(p, max_new=max_new)
        while eng.active.any():
            eng.step()
        round_s = time.time() - t0
    rate = utilization * n_slots / round_s

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    prompts = [lm.sample(1, prompt_len, seed=1000 + i)[0, :prompt_len].tolist()
               for i in range(n_requests)]
    sched = Scheduler(eng)
    done_at: dict[int, float] = {}
    enq: dict[int, float] = {}  # scheduler rid -> arrival time
    batch_drains = 0
    i = 0
    t0 = time.time()
    while len(done_at) < n_requests:
        now = time.time() - t0
        while i < n_requests and arrivals[i] <= now:
            enq[sched.enqueue(prompts[i], max_new=max_new)] = arrivals[i]
            i += 1
        if not (sched.pending or sched.inflight or eng.active.any()):
            batch_drains += 1  # idle gap in the trace: sleep to next arrival
            time.sleep(max(0.0, arrivals[i] - (time.time() - t0)))
            continue
        for ev in sched.step():
            if ev.finished:
                done_at[ev.rid] = time.time() - t0
    wall = time.time() - t0
    res = [sched.take_result(r) for r in sorted(enq)]
    spans = eng.tracer.spans("ok")
    assert eng.tracer.open_count == 0, "unclosed spans after the trace drained"

    def pct(vals, q):
        a = np.array([v for v in vals if v is not None]) * 1e3
        return round(float(np.percentile(a, q)), 1) if a.size else None

    e2e = [s.e2e_s for s in spans]
    return {"n_slots": n_slots, "n_requests": n_requests,
            "prompt_len": prompt_len, "max_new": max_new,
            "offered_req_s": round(rate, 2),
            "sustained_req_s": round(n_requests / wall, 2),
            # request-span lifecycle, not ad-hoc host timing
            "latency_p50_ms": pct(e2e, 50),
            "latency_p99_ms": pct(e2e, 99),
            "latency_mean_ms": round(
                float(np.mean([v * 1e3 for v in e2e])), 1) if e2e else None,
            "ttft_p50_ms": pct([s.ttft_s for s in spans], 50),
            "ttft_p99_ms": pct([s.ttft_s for s in spans], 99),
            "tpot_p50_ms": pct([s.tpot_s for s in spans], 50),
            "tpot_p99_ms": pct([s.tpot_s for s in spans], 99),
            "queue_wait_p99_ms": pct([s.queue_wait_s for s in spans], 99),
            "errors": sum(r.error is not None for r in res),
            "batch_drains": batch_drains,
            "continuous_admissions": sched.admitted_while_running,
            "mem_stalls": sched.mem_stalls,
            "peak_kv_blocks": eng.pool_stats().get("peak_in_use_blocks")}


def bench_prefix(make_engine, *, prompt_len: int) -> dict | None:
    """Cold vs warm prefill latency for a repeated block-aligned prompt.
    The warm submit is a full prefix-cache hit — no forward pass at all."""
    from repro.data.synthetic import MarkovLM

    eng = make_engine(2)
    if eng.pool is None or not eng.pool.prefix_cache:
        return None
    lm = MarkovLM(vocab=eng.cfg.vocab, k=8, seed=2)
    bs = eng.pool.block_size
    plen = -(-prompt_len // bs) * bs  # full blocks: the repeat hits end-to-end

    def timed_submit(p):
        t0 = time.time()
        eng.submit(p, max_new=2)
        jax.block_until_ready(eng.state)
        dt = time.time() - t0
        while eng.active.any():
            eng.step()
        return dt

    timed_submit(lm.sample(1, plen, seed=5)[0, :plen].tolist())  # compile the bucket
    prompt = lm.sample(1, plen, seed=6)[0, :plen].tolist()
    cold = timed_submit(prompt)
    warm = timed_submit(prompt)
    s = eng.pool_stats()
    return {"prompt_len": plen, "block_size": bs,
            "cold_prefill_ms": round(cold * 1e3, 2),
            "warm_prefill_ms": round(warm * 1e3, 2),
            "speedup": round(cold / warm, 1),
            "prefix_hit_tokens": s["prefix_hit_tokens"],
            "leaked_blocks": s["in_use_blocks"]}


def bench_obs_overhead(make_engine, *, n_slots: int, prompt_len: int,
                       steps: int, attempts: int = 5) -> dict:
    """Decode step wall with full telemetry (metrics + tracer + profiler) vs
    everything disabled (``metrics=False``), scheduler-driven so the tracer's
    token hooks are on the measured path.

    Methodology (mirrors ``tests/test_obs.py``): single-step alternation
    between two pre-primed engines (shared-noise windows), alternation order
    rotated per round, per-step *medians* compared.  Host noise only ever
    inflates a measurement, so each attempt upper-bounds the true overhead —
    report the tightest (lowest) of ``attempts``."""
    from repro.data.synthetic import MarkovLM
    from repro.serving.scheduler import Scheduler

    def prime(**kw):
        eng = make_engine(n_slots, **kw)
        lm = MarkovLM(vocab=eng.cfg.vocab, k=8, seed=3)
        sched = Scheduler(eng)
        for i in range(n_slots):
            p = lm.sample(1, prompt_len, seed=50 + i)[0, :prompt_len].tolist()
            sched.enqueue(p, max_new=eng.max_len)
        for _ in range(2):  # admit + compile + settle the fused step
            sched.step()
        return eng, sched

    engines = {"on": prime(tracer=True), "off": prime(metrics=False)}
    eng_on, eng_off = engines["on"][0], engines["off"][0]
    # the attempts share each engine's decode headroom
    rounds = max(1, min(steps, (eng_on.max_len - prompt_len - 3) // attempts))

    def measure():
        # collector off during the timed window: allocation-triggered gen-0
        # sweeps walk the whole bench process's heap and land on arbitrary
        # steps, which is this process's garbage bill, not telemetry's
        walls = {k: [] for k in engines}
        order = list(engines)
        gc.collect()
        gc.disable()
        try:
            for i in range(rounds):
                for k in order[i % 2:] + order[:i % 2]:
                    sched = engines[k][1]
                    t0 = time.perf_counter()
                    sched.step()
                    walls[k].append(time.perf_counter() - t0)
        finally:
            gc.enable()
        return {k: sorted(w)[len(w) // 2] for k, w in walls.items()}

    meds = [measure() for _ in range(attempts)]
    best = min(meds, key=lambda m: m["on"] / m["off"])
    assert eng_on.active.sum() == eng_off.active.sum() == n_slots, \
        "a slot finished mid-measurement"
    # overhead_s_per_step is the scale-free number: telemetry's absolute
    # per-step cost (~tens of us) is fixed, so its *fraction* depends on the
    # measured engine's step time — CI judges it against the tracked
    # full-bench engine's step wall, not this smoke-sized one
    return {"n_slots": n_slots, "steps": rounds, "attempts": attempts,
            "tok_s_telemetry_on": round(n_slots / best["on"], 2),
            "tok_s_telemetry_off": round(n_slots / best["off"], 2),
            "overhead_s_per_step": round(best["on"] - best["off"], 7),
            "overhead_frac": round(best["on"] / best["off"] - 1.0, 4)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-bounded: tiny model, few steps")
    ap.add_argument("--steps", type=int, default=None,
                    help="timed decode steps (default 3 smoke / 20 full; "
                         "clamped to the KV-cache headroom)")
    args = ap.parse_args()

    from repro import core
    from repro.configs import get_arch
    from repro.configs.base import MoESpec, reduced_config
    from repro.models import api, flops
    from repro.serving.engine import ServingEngine

    if args.smoke:
        cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                             n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                             n_layers=2)
        cfg_moe = reduced_config(
            get_arch("mixtral-8x22b"), d_model=32, n_heads=2, n_kv_heads=2,
            head_dim=16, vocab=64, n_layers=1,
            moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16))
        steps = 3 if args.steps is None else max(1, args.steps)
        warmup, prompt_len, max_len = 1, 8, 64
    else:
        cfg = reduced_config(get_arch("olmo-1b"))
        cfg_moe = reduced_config(
            get_arch("mixtral-8x22b"), d_model=64, n_heads=4, n_kv_heads=4,
            head_dim=16, vocab=256, n_layers=2,
            moe=MoESpec(n_experts=4, top_k=2, d_ff_expert=32))
        steps = 20 if args.steps is None else max(1, args.steps)
        warmup, prompt_len, max_len = 3, 16, 256

    comp_cfg = core.CompressionConfig(algorithm="fp", weight_sharing=True,
                                      max_share_rel_err=0.06)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    artifact = api.compress_model(params, cfg, comp_cfg, include="ffn.")
    artifact_all = api.compress_model(params, cfg, comp_cfg)  # + attention
    params_moe = api.init_params(jax.random.PRNGKey(1), cfg_moe)
    artifact_moe = api.compress_model(params_moe, cfg_moe, comp_cfg)

    makers = {
        "dense": lambda n, **kw: ServingEngine(params, cfg, n_slots=n,
                                               max_len=max_len, **kw),
        "compressed": lambda n, **kw: ServingEngine(artifact=artifact,
                                                    n_slots=n, max_len=max_len,
                                                    **kw),
        "compressed+attn": lambda n, **kw: ServingEngine(artifact=artifact_all,
                                                         n_slots=n,
                                                         max_len=max_len, **kw),
    }

    results = []

    def run(mode, make, n_slots, *, arch):
        t0 = time.time()
        row = {"mode": mode, "arch": arch, **bench_engine(
            make, n_slots=n_slots, prompt_len=prompt_len,
            steps=steps, warmup=warmup)}
        row["wall_s"] = round(time.time() - t0, 2)
        results.append(row)
        print(f"{arch:>12} {mode:>16} n_slots={n_slots}: "
              f"{row['decode_tok_s']:>8} tok/s decode, "
              f"{row['prefill_ms']:>7} ms prefill")

    for n_slots in (1, 8):
        for mode, make in makers.items():
            run(mode, make, n_slots, arch=cfg.name)

    # Poisson arrival trace through the continuous-batching scheduler
    n_req, trace_new = (10, 6) if args.smoke else (32, 12)
    poisson = []
    for mode in ("dense", "compressed"):
        row = {"mode": mode, **bench_poisson(
            makers[mode], n_slots=8, n_requests=n_req,
            prompt_len=prompt_len, max_new=trace_new)}
        poisson.append(row)
        print(f"{cfg.name:>12} {mode:>16} poisson: "
              f"{row['sustained_req_s']} req/s sustained "
              f"(offered {row['offered_req_s']}), "
              f"p50 {row['latency_p50_ms']} ms, p99 {row['latency_p99_ms']} ms, "
              f"{row['continuous_admissions']} continuous admissions")

    # prefix cache: repeated prompt prefills from cached blocks
    prefix = bench_prefix(makers["dense"], prompt_len=4 * prompt_len)
    if prefix:
        print(f"{cfg.name:>12} {'prefix-cache':>16}: "
              f"cold {prefix['cold_prefill_ms']} ms -> "
              f"warm {prefix['warm_prefill_ms']} ms "
              f"({prefix['speedup']}x)")
    # MoE: all experts of a layer apply their chains in ONE grouped dispatch
    for mode, make in (
            ("dense", lambda n: ServingEngine(params_moe, cfg_moe, n_slots=n,
                                              max_len=max_len)),
            ("compressed", lambda n: ServingEngine(artifact=artifact_moe,
                                                   n_slots=n,
                                                   max_len=max_len))):
        run(mode, make, 8, arch=cfg_moe.name)

    # telemetry overhead A/B: full metrics + tracing vs everything off.
    # A dedicated factory with a deep KV budget keeps the measurement
    # windows long enough (hundreds of steps) that noise stays below the
    # few-percent overhead being measured, even at smoke scale.
    obs_overhead = bench_obs_overhead(
        lambda n, **kw: ServingEngine(artifact=artifact, n_slots=n,
                                      max_len=256, **kw),
        n_slots=8, prompt_len=prompt_len, steps=60)
    print(f"{cfg.name:>12} {'obs-overhead':>16}: "
          f"{obs_overhead['tok_s_telemetry_on']} tok/s on vs "
          f"{obs_overhead['tok_s_telemetry_off']} off "
          f"({obs_overhead['overhead_frac']:+.1%} at this scale, "
          f"{obs_overhead['overhead_s_per_step'] * 1e6:+.0f} us/step)")

    # Roofline: per-site shift-add cost against the throughput each artifact
    # actually achieved, so adds-vs-tok/s gaps are visible per PR.  The same
    # obs.roofline function feeds launch/serve's live-engine table.
    from repro.obs import roofline as obs_roofline

    def roofline_section(art, mode, arch):
        row8 = next((r for r in results
                     if r["mode"] == mode and r["arch"] == arch
                     and r["n_slots"] == 8), None)
        return obs_roofline(
            art, row8["decode_tok_s"] if row8 else None,
            pallas_launches=row8["pallas_launches"] if row8 else None,
            n_layer_plans=row8["n_layer_plans"] if row8 else None,
            mode=mode, arch=arch)

    roofline = [roofline_section(artifact, "compressed", cfg.name),
                roofline_section(artifact_all, "compressed+attn", cfg.name),
                roofline_section(artifact_moe, "compressed", cfg_moe.name)]

    # Segment-packed gather layout: per-stage run-length percentiles before
    # vs after the pack-time repack (recorded when each plan is built)
    segment_layout = {}
    for art, arch in ((artifact, cfg.name), (artifact_all, cfg.name + "+attn"),
                      (artifact_moe, cfg_moe.name)):
        seg = (getattr(art, "pipeline_stats", None) or {}).get(
            "segment_layout", {})
        for stage, st in seg.items():
            segment_layout[f"{arch}.{stage}"] = st

    report = {
        "bench": "serving",
        "arch": cfg.name,
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.machine(),
        "steps_requested": steps,
        "compression": {"algorithm": "fp",
                        "ratio_lcc": round(artifact.report.ratio("lcc"), 2)},
        "adds": {
            "ffn_only": flops.compressed_adds(cfg, artifact),
            "ffn+attn": flops.compressed_adds(cfg, artifact_all),
            "moe": flops.compressed_adds(cfg_moe, artifact_moe),
        },
        "results": results,
        "roofline": roofline,
        "poisson": poisson,
        "prefix_cache": prefix,
        "obs_overhead": obs_overhead,
        "segment_layout": segment_layout,
    }

    # cross-PR history: append a dated summary entry, carrying forward any
    # entries already recorded in the previous report at the same path
    history = []
    try:
        with open(args.out) as f:
            history = json.load(f).get("history", [])
    except (OSError, ValueError):
        pass

    def _tok(mode, arch, n):
        r = next((r for r in results if r["mode"] == mode and r["arch"] == arch
                  and r["n_slots"] == n), None)
        return r["decode_tok_s"] if r else None

    history.append({
        "date": time.strftime("%Y-%m-%d"),
        "smoke": args.smoke,
        "dense_tok_s_n8": _tok("dense", cfg.name, 8),
        "compressed_tok_s_n8": _tok("compressed", cfg.name, 8),
        "moe_dense_tok_s_n8": _tok("dense", cfg_moe.name, 8),
        "moe_compressed_tok_s_n8": _tok("compressed", cfg_moe.name, 8),
    })
    report["history"] = history
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
