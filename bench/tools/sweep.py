"""Find a chat cell's knee: the highest offered rate it sustains without a
growing backlog.  One process sets the cell up once, then offers each rate
for a window of its own and drains between them.

    python bench/tools/sweep.py --workload olmo1b-chat --seed 7 \
        --rates 4,5,6,7,8 --seconds 20

Prints one JSON line per rate: requests due and finished in the window,
backlog (queued plus in flight) at the close, TTFT and queue-wait
percentiles, decode tokens/s and the mean step time.  Run on the chip.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import cell as hc, drive, program, stats, traffic  # noqa: E402
from harness.spec import load_module, resolve  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax

    cell = resolve(args.workload)
    hc.check_devices(cell, jax.devices())
    program.enable_cache()
    program.import_program()
    from repro.serving.scheduler import Scheduler

    vocab = int(cell.config["vocab_size"])
    system = load_module(cell.root, "systems", cell.config["system"]).build(
        cell, args.seed, jax.devices()[:cell.chips])
    eng = system.engine
    rates = [float(r) for r in args.rates.split(",")]
    plans = [traffic.generate(cell.traffic, {**cell.deployment,
                                             "rate_per_s": r},
                              vocab=vocab, seed=args.seed + i,
                              seconds=args.seconds)
             for i, r in enumerate(rates)]
    union = traffic.Plan(loop="open", requests=[
        q for p in plans for q in p.requests])
    hc._warm_up(eng, union, vocab=vocab, seed=args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate, plan in zip(rates, plans):
        sched = Scheduler(eng)
        win = drive.run_window(sched, eng, plan, args.seconds)
        due = hc.drive_due(win, plan)
        row = {"rate": rate, "due": len(due),
               "done": sum(r.done for r in due),
               "backlog": sched.pending + sched.inflight,
               "ttft_p50_ms": 1e3 * stats.pctl(stats.ttft_s(win), 50),
               "ttft_p95_ms": 1e3 * stats.pctl(stats.ttft_s(win), 95),
               "queue_p95_ms": 1e3 * stats.pctl(stats.queue_wait_s(win), 95),
               "tpot_p95_ms": 1e3 * (stats.pctl(stats.tpot_s(win), 95) or 0),
               "decode_tok_s": stats.decode_tokens(win) / win.seconds,
               "step_ms": 1e3 * (stats.step_mean_s(win) or 0),
               "prefill_p95_ms": 1e3 * (stats.pctl(stats.admit_pass_s(win),
                                                   95) or 0)}
        print(json.dumps(row), flush=True)
        for erid in list(sched._inflight):
            eng.cancel(erid)
        sched._heap.clear()
        sched.step()


if __name__ == "__main__":
    main()
