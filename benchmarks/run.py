"""Benchmark harness: one module per paper table/figure + kernels + roofline.

Prints ``name,us_per_call_or_value,derived`` CSV lines (harness contract) and
writes them to benchmarks/results.csv.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (bench_fig2_mlp, bench_kernels, bench_lcc_scaling,
                            bench_table1_resnet, roofline)
    only = sys.argv[1] if len(sys.argv) > 1 else None
    suites = {
        "kernels": bench_kernels.run,
        "lcc_scaling": bench_lcc_scaling.run,
        "fig2": bench_fig2_mlp.run,
        "table1": bench_table1_resnet.run,
        "roofline": roofline.run,
    }
    rows: list[str] = ["name,value,derived"]
    t0 = time.time()
    for name, fn in suites.items():
        if only and only != name:
            continue
        print(f"== {name} ==", flush=True)
        fn(rows)
    rows.append(f"total_wall_s,{time.time() - t0:.1f},")
    with open("benchmarks/results.csv", "w") as f:
        f.write("\n".join(rows) + "\n")
    print(f"\nwrote benchmarks/results.csv ({len(rows)} rows, "
          f"{time.time() - t0:.0f}s total)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
