"""The output check's control: for each seed, run a cell's window as the
benchmark does and judge, in the program's place, the control — the tokens
that the reference computed in float8 (e4m3: weights one scale per output
channel, activations one per row) puts first — on the same sample of served
requests.  Each line's ``correct`` and ``logit_gap`` are the control's and
must read false and above the limit; ``program_gap`` is the program's own
reading on that sample.  A sound limit lies above every program reading
and below every control reading.

    python bench/tools/control.py --workload olmo1b-chat \
        --seeds 11,12,13 --seconds 51

Prints one JSON line per seed.  Run on the chip; the benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness.cell import run_cell  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = run_cell(args.workload, seed=seed, seconds=args.seconds,
                     trace=False, t_start=t0, control=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": r["checks"],
                          "metrics": r["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
