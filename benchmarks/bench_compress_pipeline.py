"""Compression-pipeline benchmark: offline Algorithm-1 throughput (units/sec
and wall-clock) vs worker count, plus the content-addressed cache-hit
speedup — emitted as machine-readable ``BENCH_compress.json`` so the offline
path's perf trajectory is tracked across PRs like the serving loop's.

    PYTHONPATH=src python benchmarks/bench_compress_pipeline.py [--smoke] [--out F]

CPU-container numbers measure pipeline orchestration + numpy matching-pursuit
throughput on the host's cores (2 here, so the parallel ceiling is ~2x even
at 4 workers); the cross-PR signal is the wall-clock trend of the identical
workload and the cache-hit speedup.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import tempfile
import time


def bench_run(units, cfg, n_workers: int, cache_dir: str | None) -> dict:
    from repro.pipeline import run_pipeline

    t0 = time.time()
    res = run_pipeline(units, cfg, n_workers=n_workers, cache_dir=cache_dir)
    wall = time.time() - t0
    return {"n_workers": n_workers, "units": res.stats["units"],
            "jobs": res.stats["jobs"], "wall_s": round(wall, 3),
            "units_per_s": round(res.stats["units"] / wall, 3),
            "cache_hits": res.stats["cache_hits"],
            "lcc_adds": res.report.total_stage("lcc")}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_compress.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-bounded: tiny model, ffn units only")
    args = ap.parse_args()

    import jax

    from repro import core
    from repro.configs import get_arch
    from repro.configs.base import reduced_config
    from repro.models import api

    if args.smoke:
        cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                             n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                             n_layers=2)
        include = "ffn."
    else:
        cfg = reduced_config(get_arch("olmo-1b"), d_model=64, n_heads=4,
                             n_kv_heads=4, head_dim=16, d_ff=128, vocab=64,
                             n_layers=2)
        include = None  # FFN + attention projections

    params = api.init_params(jax.random.PRNGKey(0), cfg)
    from repro.models import compress_adapters
    sites = compress_adapters.sites_for(params, cfg)
    if include:
        sites = [s for s in sites if s.name.startswith(include)]
    units = compress_adapters.units_from_sites(params, sites)
    comp = core.CompressionConfig(algorithm="fp", weight_sharing=True,
                                  max_share_rel_err=0.06)

    results = []
    ref_adds = None
    with tempfile.TemporaryDirectory() as tmp:
        # boot the forkserver + 4-worker pool once so pool startup doesn't
        # skew the measured rows (the pool persists across runs)
        bench_run(units[:1], comp, 4, None)
        for n_workers in (1, 4):
            cold = os.path.join(tmp, f"cold_{n_workers}")
            row = bench_run(units, comp, n_workers, cold)
            if ref_adds is None:
                ref_adds = row["lcc_adds"]
            # parallel output must match serial output exactly
            assert row["lcc_adds"] == ref_adds, "parallel != serial adds"
            results.append(row)
            print(f"workers={n_workers}: {row['wall_s']}s "
                  f"({row['units_per_s']} units/s, {row['jobs']} jobs)")
        # cache-hit speedup: identical run over the populated cold_4 cache
        warm = bench_run(units, comp, 4, os.path.join(tmp, "cold_4"))
        assert warm["lcc_adds"] == ref_adds

    cold4 = next(r for r in results if r["n_workers"] == 4)
    cold1 = next(r for r in results if r["n_workers"] == 1)
    report = {
        "bench": "compress_pipeline",
        "arch": cfg.name,
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "cpu_count": os.cpu_count(),
        "platform": platform.machine(),
        "units": cold4["units"],
        "jobs": cold4["jobs"],
        "results": results,
        "speedup_4v1": round(cold1["wall_s"] / cold4["wall_s"], 2),
        "cache": {
            "cold_s": cold4["wall_s"],
            "warm_s": warm["wall_s"],
            "speedup": round(cold4["wall_s"] / max(warm["wall_s"], 1e-9), 2),
            "warm_hits": warm["cache_hits"],
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"speedup 4v1 workers: {report['speedup_4v1']}x   "
          f"cache-hit speedup: {report['cache']['speedup']}x")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
