"""Run one benchmark cell once, on the chips of this host.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (an entry of ``workloads`` in
``BENCHMARK.json``) names its configuration and traffic; everything else is
found by name under ``bench/`` (see ``bench/harness/spec.py``).  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics: host-side ones from the window, device
ones from a profiler trace of a slice served under the same load right
after the window closes.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``: each number the output check compared,
with its limit.  The lines before it, on standard error, give the set-up's
split and the compiles counted inside the window; the last of them repeat
the checks.

A host without the TPUs the cell asks for, or whose device kind has no
published peaks in ``bench/peaks.json``, makes the command exit non-zero
with no result: there is no CPU fallback.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.cell import run_cell

    result = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
