"""Run one cell with a traced slice and write the normalised trace (see
``bench/harness/trace.py``) to a file, for reading by hand and for recording
small test traces.

    python bench/tools/trace_dump.py --workload olmo1b-chat --seed 7 \
        --seconds 20 --out chiprun_out/trace.json

Prints the run's result line as ``bench/run.py --trace 1`` does.  Run on the
chip.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness.cell import run_cell  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                      trace=True, t_start=T_START, keep_trace=args.out)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
