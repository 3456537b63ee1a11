"""Serving driver: scheduler-driven batched generation with optional LCC
compression, multi-device sharding and token streaming.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --requests 6 --compress --stream

    # 2-way tensor parallel on a multi-device host (e.g. under
    # XLA_FLAGS=--xla_force_host_platform_device_count=2)
    PYTHONPATH=src python -m repro.launch.serve --reduced --tp 2
"""
import argparse
import time

import jax

import repro.core as core
from repro import compat, obs
from repro.configs import get_arch, reduced_config
from repro.data.synthetic import MarkovLM
from repro.distributed import sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import Scheduler


def build_mesh(dp: int, tp: int):
    """("data", "model") mesh over the host's devices, or None for 1x1."""
    if dp * tp <= 1:
        return None
    if dp * tp > jax.device_count():
        raise SystemExit(f"--dp {dp} x --tp {tp} needs {dp * tp} devices, "
                         f"host has {jax.device_count()} "
                         "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return compat.make_mesh((dp, tp), ("data", "model"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--compress", action="store_true",
                    help="Algorithm 1 over every compressible site (any "
                         "family), served from the CompressedModel artifact")
    ap.add_argument("--kernel", action="store_true",
                    help="with --compress: decode through the site-keyed "
                         "fused-kernel executor (interpret-mode Pallas off-TPU"
                         " — slower on CPU dev boxes, the TPU hot path)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--dp", type=int, default=1, help="data-parallel mesh axis")
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel mesh axis")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are sampled")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="paged-KV block size in tokens; 0 = contiguous "
                         "per-slot slabs (attention families only)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="total usable KV pool blocks (default: one full "
                         "view per slot, i.e. contiguous-equivalent memory)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="share prefilled prompt-prefix blocks across "
                         "requests (copy-on-write; paged engines only)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the merged metrics snapshot (+ trace summary "
                         "and live roofline) as JSON at exit")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request spans as JSONL at exit")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics (Prometheus text) on this port "
                         "for the run's duration (0 = ephemeral)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.kernel and not args.compress:
        ap.error("--kernel routes a compressed artifact; pass --compress too")

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg, vocab=256)
    mesh = build_mesh(args.dp, args.tp)
    if mesh is None:
        params = api.init_params(jax.random.PRNGKey(0), cfg)
    else:  # each device builds only its shard of the weights
        params = sharding.init_sharded(
            lambda: api.init_params(jax.random.PRNGKey(0), cfg), mesh,
            sharding.params_pspecs)
    artifact = None
    if args.compress:
        artifact = api.compress_model(
            params, cfg,
            core.CompressionConfig(algorithm="fp" if args.kernel else "fs",
                                   max_share_rel_err=0.06),
            build_packed=args.kernel)
        print(artifact.report.table())

    lm = MarkovLM(vocab=cfg.vocab, k=8, seed=0)
    prompts = [lm.sample(1, 8, seed=100 + i)[0, :8].tolist()
               for i in range(args.requests)]
    kv = dict(kv_block=args.kv_block or None, kv_blocks=args.kv_blocks,
              prefix_cache=args.prefix_cache, tracer=True)
    if artifact is not None:
        eng = ServingEngine(artifact=artifact, n_slots=args.slots, max_len=128,
                            temperature=args.temperature,
                            use_kernel=args.kernel,
                            mesh=mesh, **kv)
    else:
        eng = ServingEngine(params, cfg, n_slots=args.slots, max_len=128,
                            temperature=args.temperature,
                            mesh=mesh, **kv)
    registries = [obs.get_global(), eng.metrics]
    srv = None
    if args.metrics_port is not None:
        srv = obs.start_metrics_server(registries, port=args.metrics_port)
        print(f"metrics: http://127.0.0.1:{srv.server_port}/metrics")
    sched = Scheduler(eng)
    on_token = ((lambda rid, tok: print(f"  req{rid} += {tok}", flush=True))
                if args.stream else None)
    t0 = time.time()
    rids = [sched.enqueue(p, max_new=args.max_new,
                          priority=args.requests - i,  # earlier = higher
                          on_token=on_token)
            for i, p in enumerate(prompts)]
    sched.run()
    dt = time.time() - t0
    res = [sched.take_result(r) for r in rids]
    tok = sum(len(r.tokens) - r.prompt_len for r in res)
    for i, r in enumerate(res):
        tag = f" [error: {r.error}]" if r.error else ""
        print(f"req{i}: prompt={r.tokens[:r.prompt_len]} -> "
              f"{r.tokens[r.prompt_len:]}{tag}")
    where = (f"mesh {args.dp}x{args.tp}" if args.dp * args.tp > 1
             else jax.default_backend())
    print(f"{tok} tokens in {dt:.1f}s ({tok / dt:.1f} tok/s, "
          f"{args.slots} slots, {eng.step_dispatches} dispatches, {where})")
    ps = eng.pool_stats()
    if ps["n_blocks"]:
        print(f"kv pool: {ps['n_blocks']} blocks x {ps['block_size']} tok, "
              f"peak {ps['peak_in_use_blocks']} in use, "
              f"prefix hit-rate {ps['prefix_hit_rate']:.2f} "
              f"({ps['prefix_hit_tokens']} tok), {ps['cow_copies']} COW, "
              f"{ps['evictions']} evictions, "
              f"{sched.admitted_while_running} continuous admissions, "
              f"{sched.mem_stalls} block stalls")

    # -------------------------------------------------- end-of-run telemetry
    tsum = eng.tracer.summary()
    prof = eng.profiler.summary()

    def ms(v):
        return "-" if v is None else f"{v * 1e3:8.1f}"

    print("telemetry summary")
    print(f"  {'metric':<14}{'p50 ms':>10}{'p99 ms':>10}{'n':>6}")
    for name in ("queue_wait_s", "ttft_s", "tpot_s", "e2e_s"):
        st = tsum[name]
        print(f"  {name[:-2]:<14}{ms(st['p50']):>10}{ms(st['p99']):>10}"
              f"{st['n']:>6}")
    print(f"  requests: {tsum['by_status']} ({tsum['open']} unclosed), "
          f"decode steps {prof['steps']}"
          + (f" @ {prof['tok_s']:.1f} tok/s" if prof["tok_s"] else ""))
    live = obs.live_roofline(eng)
    if live is not None:
        print(f"  live roofline: {live['total_lcc_adds']} lcc adds/token x "
              f"{live['decode_tok_s_n8']} tok/s = "
              f"{live['achieved_adds_per_s']} adds/s "
              f"({live['pallas_launches']} launches per step)")
    if args.trace_out:
        n_open = eng.tracer.dump_jsonl(args.trace_out)
        print(f"wrote {args.trace_out} ({tsum['completed']} spans, "
              f"{n_open} unclosed)")
    if args.metrics_out:
        obs.dump_metrics(args.metrics_out, registries,
                         trace_summary=tsum, profiler=prof,
                         live_roofline=live)
        print(f"wrote {args.metrics_out}")
    if srv is not None:
        srv.shutdown()


if __name__ == "__main__":
    main()
