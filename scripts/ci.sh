#!/usr/bin/env bash
# CI entry point: tier-1 test suite + a bounded smoke of the quickstart.
#
#   scripts/ci.sh            # from the repo root
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CI runs on the CPU backend, Pallas kernels in interpret mode; the chip path
# is checked by chip_smoke.py on a TPU
export JAX_PLATFORMS=cpu

echo "== tier-1 test suite =="
python -m pytest -x -q

echo "== quickstart smoke (30s budget) =="
timeout 30 python examples/quickstart.py

echo "== serving bench smoke (240s budget) =="
# /tmp output: the tracked BENCH_serving.json is refreshed deliberately per
# PR, not dirtied by every CI run's machine-dependent numbers.  The bench
# drives the site-keyed executor end-to-end: FFN-only, FFN+attention and MoE
# (grouped multi-expert launch) compressed rows must all decode.
timeout 240 python benchmarks/bench_serving.py --smoke --out /tmp/BENCH_serving.json
python -c "import json; r = json.load(open('/tmp/BENCH_serving.json')); \
modes = {(x['arch'], x['mode']) for x in r['results']}; \
assert all(x['decode_tok_s'] > 0 for x in r['results']); \
assert any(m == 'compressed+attn' for _, m in modes), modes; \
assert ('mixtral-8x22b', 'compressed') in modes, modes; \
assert all(v['ratio'] > 1 for v in r['adds'].values()), r['adds']; \
assert all(p['errors'] == 0 for p in r['poisson']), r['poisson']; \
assert r['prefix_cache']['speedup'] >= 2, r['prefix_cache']; \
assert r['prefix_cache']['leaked_blocks'] == 0, r['prefix_cache']"
# perf gate: the smoke's compressed decode must not fall below 0.8x the
# tracked full-bench number (the smoke model is far smaller, so a pass means
# the plan path engaged), and full telemetry must cost <= 3% decode tok/s.
# The one-launch-per-layer-plan invariant is gated below from a live
# engine's own metrics file (telemetry smoke), not from bench plumbing.
python - <<'EOF'
import json
r = json.load(open("/tmp/BENCH_serving.json"))
smoke = next(x["decode_tok_s"] for x in r["results"]
             if x["arch"] == "olmo-1b" and x["mode"] == "compressed"
             and x["n_slots"] == 8)
tracked = json.load(open("BENCH_serving.json"))
base = next(x["decode_tok_s"] for x in tracked["results"]
            if x["arch"] == "olmo-1b" and x["mode"] == "compressed"
            and x["n_slots"] == 8)
assert smoke >= 0.8 * base, (
    f"compressed decode regressed: smoke {smoke} tok/s < 0.8x tracked {base}")
assert r["roofline"] and all(s["sites"] for s in r["roofline"])
# whole-step MoE plan: the mixtral compressed row must decode in exactly one
# Pallas launch covering its one layer plan (attention + router + experts)
moe = next(x for x in r["results"]
           if x["arch"] == "mixtral-8x22b" and x["mode"] == "compressed"
           and x["n_slots"] == 8)
assert moe["pallas_launches"] == moe["n_layer_plans"] > 0, moe
assert not moe["plan_fallbacks"], moe["plan_fallbacks"]
# compressed-vs-dense gate on the tracked full bench: the segment-packed
# one-launch plan must keep olmo-1b compressed within 0.95x of dense at n8
t_dense = next(x["decode_tok_s"] for x in tracked["results"]
               if x["arch"] == "olmo-1b" and x["mode"] == "dense"
               and x["n_slots"] == 8)
assert base >= 0.95 * t_dense, (
    f"tracked compressed {base} tok/s < 0.95x tracked dense {t_dense}")
# cross-PR history must be tracked in the committed bench report
assert tracked["history"] and all("date" in h for h in tracked["history"])
assert tracked.get("segment_layout"), "segment_layout section missing"
# telemetry's cost is a fixed ~tens-of-us per step, so judge it against the
# tracked full-bench engine's step wall (the smoke engine's sub-ms steps
# would overstate the fraction by the model-size ratio)
ob = r["obs_overhead"]
ovh = ob["overhead_s_per_step"] / (ob["n_slots"] / base)
assert ovh <= 0.03, (
    f"telemetry overhead {ob['overhead_s_per_step'] * 1e6:.0f} us/step = "
    f"{ovh:.2%} of the tracked engine's step > 3% budget")
print(f"perf gate OK: {smoke} tok/s >= 0.8x tracked {base}, telemetry "
      f"{ob['overhead_s_per_step'] * 1e6:+.0f} us/step ({ovh:.2%} of step)")
EOF

echo "== telemetry smoke (120s budget) =="
# a compressed serve run with full telemetry: every span must close, and the
# live engine's own metrics file must show exactly one Pallas launch per
# layer plan (the executor invariant, gated from telemetry rather than bench
# internals)
timeout 120 python -m repro.launch.serve --reduced --compress --kernel \
    --requests 2 --max-new 8 --slots 2 \
    --metrics-out /tmp/obs_metrics.json --trace-out /tmp/obs_trace.jsonl
python - <<'EOF'
import json
spans = [json.loads(l) for l in open("/tmp/obs_trace.jsonl")]
assert len(spans) == 2, spans
assert all(s["status"] == "ok" for s in spans), spans
m = json.load(open("/tmp/obs_metrics.json"))["metrics"]
launches = max(v["value"]
               for v in m["serving_pallas_launches_per_step"]["values"])
plans = m["serving_layer_plans"]["values"][0]["value"]
assert launches == plans > 0, (launches, plans)
assert m["serving_requests_total"]["values"] == [
    {"labels": {"status": "ok"}, "value": 2}], m["serving_requests_total"]
assert m["pallas_launches_total"]["values"][0]["value"] > 0
roof = json.load(open("/tmp/obs_metrics.json"))["live_roofline"]
assert roof and roof["sites"] and roof["achieved_adds_per_s"] > 0, roof
print(f"telemetry smoke OK: 2/2 spans closed, {int(launches)} launches == "
      f"{int(plans)} layer plans, live roofline "
      f"{roof['achieved_adds_per_s']} adds/s")
EOF

echo "== paged KV prefix-sharing smoke (60s budget) =="
# two requests sharing a system prompt: the second must prefill from cached
# pool blocks (>= 1 prefix hit) and shutdown must leak zero blocks
timeout 60 python - <<'EOF'
import jax
from repro.configs import get_arch, reduced_config
from repro.models import api
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import Scheduler
cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2, n_kv_heads=2,
                     head_dim=16, d_ff=48, vocab=64, n_layers=2)
params = api.init_params(jax.random.PRNGKey(0), cfg)
eng = ServingEngine(params, cfg, n_slots=2, max_len=64, kv_block=8)
sched = Scheduler(eng)
system = [(5 * i + 3) % cfg.vocab for i in range(16)]  # shared system prompt
rids = [sched.enqueue(system + t, max_new=4) for t in ([7, 8], [9, 10, 11])]
sched.run()
res = [sched.take_result(r) for r in rids]
assert all(r.finished and r.error is None for r in res), res
s = eng.pool_stats()
assert s["prefix_hit_blocks"] >= 1, s
assert s["in_use_blocks"] == 0, s
print(f"prefix smoke OK: {s['prefix_hit_blocks']} blocks "
      f"({s['prefix_hit_tokens']} tokens) served from cache, zero leaks")
EOF

echo "== compression pipeline bench smoke (120s budget) =="
timeout 120 python benchmarks/bench_compress_pipeline.py --smoke \
    --out /tmp/BENCH_compress.json
python -c "import json, os; r = json.load(open('/tmp/BENCH_compress.json')); \
assert r['results'] and all(x['wall_s'] > 0 for x in r['results']); \
assert r['cache']['speedup'] > 1 and r['cache']['warm_hits'] == r['jobs']; \
assert r['cpu_count'] < 4 or r['speedup_4v1'] > 1.0, r['speedup_4v1']"

echo "== train -> compress -> recover -> serve smoke (60s budget) =="
# the paper's full Algorithm-1 loop on the MLP: prox-regularized training must
# produce dead input groups, the prune-aware planner must turn them into
# skipped/shrunk 0-add slice jobs, and recovery + fused serving must complete
timeout 60 python -m repro.launch.train --arch mlp --prox --lambda 0.12 \
    --hidden 100 --epochs 6 --train-n 2000 --test-n 500 --recover 30 \
    --compress-out /tmp/train_smoke \
    --compress-config algorithm=fp prune_tol=-1e-6 weight_sharing=false \
    snr_offset_db=-12
python -c "import json; s = json.load(open('/tmp/train_smoke/train_stats.json')); \
p = s['pipeline']; \
assert p['dead_groups'] >= 1, p; \
assert p['skipped_jobs'] + p['shrunk_jobs'] >= 1, p; \
assert s['accuracy']['compressed'] > 0.8, s['accuracy']; \
assert s['accuracy']['fused'] > 0.8, s['accuracy']; \
assert s['recover']['loss_last'] < s['recover']['loss_first'], s['recover']; \
assert 'recovered' in s['accuracy'], s['accuracy']"

echo "CI OK"
