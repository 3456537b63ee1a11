"""Run one cell with a traced slice, as ``bench/run.py --trace 1`` does, and
read the program's own spans and phase counters beside the harness's
readings (``bench/harness/phases.py``): the traced slice's longest device
idle gaps named by the program phase behind them, the share of the idle
time inside ``bench.step`` and ``bench.admit`` that some ``serving.*`` span
covers, the idle time inside an admission pass, the program's host time per
decode step outside the wait for its tokens, bucket padding's share of the
prefill, the host seconds of each phase per step, and the backend compiles
by span during set-up and inside the window.

    python bench/tools/phases.py --workload olmo1b-chat --seed 7 \
        --seconds 51 --out phases-chat.json

Prints one JSON line: ``result`` (the line ``bench/run.py --trace 1``
prints) and ``program`` (the readings above).  Writes the normalised trace
with the program's spans under ``program`` to ``--out``.  Run on the chip.

The harness keeps only its own spans and takes no counter snapshots, so for
this run the tool wraps two of its functions: the trace loader, to keep the
``serving.*`` spans as well, and the window, to snapshot the program's
registries at its opening and close, outside every timed step (an open
loop's close precedes its traced slice; a closed loop's opening follows
its traced slice).  The wrappers time the snapshots by the profiler's
start and stop; a run in which they did not fire is an error, never a
reading that takes in the traced slice.  Scaffolding: once the harness
takes these snapshots and spans itself (PERF.md section 7), this tool goes.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import drive, phases, trace as tr  # noqa: E402
from harness.cell import run_cell  # noqa: E402


def _snapshot(engine) -> tuple[dict, dict]:
    from repro.obs import get_global

    return engine.metrics.snapshot(), get_global().snapshot()


def instrument(snaps: dict, program: list):
    """Wrap the harness's window and trace loader for one run; returns a
    function that restores them."""
    import jax

    run_window, load_xplane = drive.run_window, tr.load_xplane
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def window(sched, engine, plan, seconds, **kw):
        snaps["setup"] = snaps["open"] = _snapshot(engine)

        def start_trace(*a, **k):
            if plan.loop == "open":
                snaps["close"] = _snapshot(engine)
            return start(*a, **k)

        def stop_trace(*a, **k):
            out = stop(*a, **k)
            if plan.loop == "closed":
                snaps["open"] = _snapshot(engine)
            return out

        jax.profiler.start_trace = start_trace
        jax.profiler.stop_trace = stop_trace
        try:
            win = run_window(sched, engine, plan, seconds, **kw)
        finally:
            jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        if plan.loop == "open":
            if "close" not in snaps:
                raise RuntimeError("the open loop's close snapshot was not "
                                   "taken: the profiler's start_trace hook "
                                   "did not fire")
        elif snaps["open"] is snaps["setup"]:
            raise RuntimeError("the closed loop's opening snapshot was not "
                               "taken: the profiler's stop_trace hook did "
                               "not fire")
        else:
            snaps["close"] = _snapshot(engine)
        return win

    def loader(trace_dir):
        program.extend(phases.load_program(trace_dir))
        return load_xplane(trace_dir)

    drive.run_window, tr.load_xplane = window, loader

    def restore():
        drive.run_window, tr.load_xplane = run_window, load_xplane

    return restore


def read_program(trace: dict, program: list, snaps: dict) -> dict:
    """The program's readings of one traced run (see the module
    docstring)."""
    (e0, g0), (e1, g1) = snaps["open"], snaps["close"]
    idle, covered = phases.idle_covered(trace, program)
    steps = phases.counter_delta(e0, e1, "serving_decode_steps_total")
    n = sum(steps.values()) or None
    per_step = {k: v / n * 1e3 for k, v in sorted(phases.counter_delta(
        e0, e1, "serving_phase_seconds_total").items())} if n else {}
    traced = [d * 1e-6 for name, s, d in trace["host"] if name == "bench.step"
              and s >= trace["window_ns"][0]
              and s + d <= trace["window_ns"][1]]
    return {
        "program_gaps": phases.program_gaps(trace, program),
        "idle_in_bench_ms": idle * 1e-6,
        "idle_covered_share": covered / idle * 100.0 if idle else None,
        "admit_idle_ms": phases.admit_idle_ms(trace, program),
        "step_host_ms": phases.step_host_ms(e0, e1),
        "prefill_pad_share": phases.prefill_pad_share(e0, e1),
        "prefill_tokens": phases.counter_delta(
            e0, e1, "serving_prefill_tokens_total"),
        "phase_ms_per_step": per_step,
        "window_steps": n,
        "traced_step_ms_median": (statistics.median(traced)
                                  if traced else None),
        "traced_steps": len(traced),
        "compiles_setup": phases.compiles_by_span({}, snaps["setup"][1]),
        "compiles_window": phases.compiles_by_span(g0, g1),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    snaps, program = {}, []
    restore = instrument(snaps, program)
    fd, kept = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        result = run_cell(args.workload, seed=args.seed,
                          seconds=args.seconds, trace=True, t_start=T_START,
                          keep_trace=kept)
        with open(kept) as f:
            trace = json.load(f)
    finally:
        restore()
        os.unlink(kept)
    trace["program"] = program
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(trace, f)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "result": result,
                      "program": read_program(trace, program, snaps)}),
          flush=True)


if __name__ == "__main__":
    main()
