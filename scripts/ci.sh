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

echo "== telemetry smoke (120s budget) =="
# a compressed serve run with full telemetry: every span must close, the
# fused decode step must emit Pallas launches, and the live roofline must
# join the artifact's adds with the engine's own throughput
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
assert m["serving_requests_total"]["values"] == [
    {"labels": {"status": "ok"}, "value": 2}], m["serving_requests_total"]
assert m["pallas_launches_total"]["values"][0]["value"] > 0
roof = json.load(open("/tmp/obs_metrics.json"))["live_roofline"]
assert roof and roof["sites"] and roof["achieved_adds_per_s"] > 0, roof
print(f"telemetry smoke OK: 2/2 spans closed, {int(launches)} launches "
      f"per step, live roofline {roof['achieved_adds_per_s']} adds/s")
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
