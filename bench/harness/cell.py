"""One run of one cell: set up, warm up, measure, check, report.

Set-up builds the system from the seed, then warms up exactly the shapes the
cell's traffic uses: one prompt of every distinct prompt length of this
run's plan (each prefill bucket and the host-side scatter shapes that follow
the prompt's length) and the fused decode step.  A closed loop then admits
every client's first request.  The window follows (:mod:`harness.drive`);
the compiles counted inside it are printed and should be none.  After the
window (and, in a traced run, the profiled slice: after the close in an
open loop, before the opening in a closed one) the device's peak memory is
read, the metrics are reduced, the program's state is freed, and the check
runs (:mod:`harness.correct`).
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from collections import deque
from types import SimpleNamespace

from . import correct, drive, program, trace as tr, traffic
from .peaks import peaks
from .spec import ROOT, load_module, resolve

__all__ = ["run_cell", "check_devices"]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_devices(cell, devices, root=ROOT) -> dict:
    """The cell's chips, as JAX reports them; raises unless they are TPUs
    with published peaks and there are enough of them."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX reports platform {dev.platform!r}")
    if len(devices) < cell.chips:
        raise RuntimeError(f"cell {cell.name} needs {cell.chips} chips, "
                           f"JAX finds {len(devices)}")
    return peaks(dev.device_kind, root)


def _warm_up(engine, plan, *, vocab: int, seed: int) -> int:
    """Prefill one prompt of each length the plan sends, decode one step,
    cancel; repeat in batches of the engine's slots.  Returns the number
    of warm-up prompts."""
    prompts = traffic.warmup_prompts(plan, vocab=vocab, seed=seed)
    for i in range(0, len(prompts), engine.n_slots):
        rids = [engine.submit(p, max_new=2)
                for p in prompts[i:i + engine.n_slots]]
        engine.step()
        for rid in rids:
            engine.cancel(rid)
            engine.results.pop(rid, None)
    return len(prompts)


def _preload(sched, engine, plan):
    """Closed loop: every client's first request admitted before the
    window; returns (records, each client's remaining queue)."""
    queues = {c: deque(plan.client_queue(c))
              for c in sorted({r.client for r in plan.requests})}
    records = {}
    for c, q in queues.items():
        req = q.popleft()
        sid = sched.enqueue(req.prompt, max_new=req.max_new)
        records[sid] = drive.Record(rid=req.rid, due=0.0,
                                    prompt_len=len(req.prompt),
                                    max_new=req.max_new)
    for sid in drive.admit_all(sched, engine):
        records[sid].admit = 0.0
    return records, queues


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, root=ROOT, require_tpu: bool = True,
             keep_trace: str | None = None, control: bool = False) -> dict:
    """Run ``workload`` once; returns the result object (the last line the
    command prints).  ``require_tpu=False`` exists for the CPU tests of the
    harness's functions; the command never passes it.  ``keep_trace``
    names a file for the normalised trace; ``control`` puts the control in
    the program's place: ``correct`` and ``logit_gap`` are the control's,
    and ``program_gap`` the program's own reading on the same sample
    (``bench/tools/control.py``; the command never passes it)."""
    import jax

    cell = resolve(workload, root)
    devices = jax.devices()
    pk = check_devices(cell, devices, root) if require_tpu else None
    devices = devices[:cell.chips]
    counter = program.CompileCounter().install()
    cache = program.enable_cache()
    program.import_program(root)
    from repro.serving.scheduler import Scheduler

    dep = cell.deployment
    vocab = int(cell.config["vocab_size"])
    t_proc = time.perf_counter() - t_start
    system = load_module(root, "systems", cell.config["system"]).build(
        cell, seed, devices)
    engine = system.engine
    trace_s = float(dep.get("trace_seconds", 3.0)) if trace else None
    plan = traffic.generate(cell.traffic, dep, vocab=vocab, seed=seed,
                            seconds=seconds,
                            tail=trace_s + 3.0 if trace else 0.0)
    t_w = time.perf_counter()
    c0 = counter.snapshot()
    n_warm = _warm_up(engine, plan, vocab=vocab, seed=seed)
    sched = Scheduler(engine)
    preloaded, queues = (_preload(sched, engine, plan)
                         if plan.loop == "closed" else (None, None))
    jax.block_until_ready(engine.state)
    # Set-up leaves a large heap (JAX's caches of every warm-up trace).  A
    # full collection over it inside the window would stall the host at
    # random, so it happens here and the heap is kept out of the window's
    # collections, as serving frameworks do after warm-up.
    t_gc = time.perf_counter()
    gc.collect()
    gc.freeze()
    t_gc = time.perf_counter() - t_gc
    c1 = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    split = {"process_s": t_proc, **system.split,
             "warmup_s": time.perf_counter() - t_w,
             "warmup_compile_s": c1[2] - c0[2]}
    log("setup split: " + " ".join(f"{k} {v:.3f}" for k, v in split.items())
        + f" (compile cache {cache}; {n_warm} warm-up prompts; warm-up "
        f"traces {c1[0] - c0[0]}, backend compiles {c1[1] - c0[1]}, cache "
        f"hits {c1[3] - c0[3]}; {gc.get_freeze_count()} objects frozen after "
        f"a {t_gc:.3f} s collection)")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = drive.run_window(sched, engine, plan, seconds,
                           preloaded=preloaded, closed_queues=queues,
                           trace_seconds=trace_s, trace_dir=trace_dir)
    c2 = counter.snapshot()
    counter.uninstall()
    gc.unfreeze()
    ends = [0.0] + [tc for _, tc, _ in win.steps]
    longest = max(b - a for a, b in zip(ends, ends[1:])) if win.steps else 0
    log(f"window: {win.seconds:.3f} s, {len(win.steps)} steps, "
        f"{sum(n for *_, n in win.steps)} tokens, longest step-to-step "
        f"{longest * 1e3:.1f} ms; compiles inside the window: traces "
        f"{c2[0] - c1[0]}, backend compiles {c2[1] - c1[1]}")
    mem = _memory_peak(devices)

    tdata = None
    if trace:
        try:
            tdata = tr.load_xplane(trace_dir)
            if keep_trace:
                with open(keep_trace, "w") as f:
                    json.dump(tdata, f)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    if tdata is not None:
        w = tdata["window_ns"]
        busy = [tr.busy_ns(evs, w) for evs in tdata["devices"].values()]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (w[1] - w[0]) * 1e-9

    ctx = SimpleNamespace(window=win, trace=tdata, system=system, cell=cell,
                          peaks=pk, chips=cell.chips, setup_s=setup_s)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = load_module(root, "metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    due = drive_due(win, plan)
    attempted = len(due)
    failed = sum(1 for r in due if r.error is not None)
    pairs = correct.sample(win, sched.results, seed=seed,
                           check_tokens=int(dep["check_tokens"]))
    engine.state = engine.params = None
    del sched, engine
    system.engine = None
    gc.collect()
    jax.clear_caches()

    t_c = time.perf_counter()
    params = system.reference_params()
    got = correct.widest_gaps(system.ref, params, system.spec, pairs,
                              control=control)
    del params
    limit = float(dep["checks"]["logit_gap"])
    # the control stands in the program's place: its first-ranked tokens
    # are judged as the served ones, and the program's reading is kept
    # beside them
    gap = got["control_gap"] if control else got["logit_gap"]
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "tokens_checked": {"value": got["tokens"],
                                 "limit": int(dep["check_tokens"])}}
    ok = gap <= limit and got["tokens"] >= int(dep["check_tokens"])
    log(f"check: {len(pairs)} requests, reference "
        f"{time.perf_counter() - t_c:.1f} s")
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if tdata is not None:
        result["breakdown"] = tr.breakdown(tdata)
    if control:
        checks = {"program_gap": {"value": got["logit_gap"], "limit": limit},
                  **checks}
        log(f"check program_gap {got['logit_gap']!r} limit {limit!r}")
    result["checks"] = checks
    log(f"check logit_gap {gap!r} limit {limit!r}")
    log(f"check tokens_checked {got['tokens']} limit {dep['check_tokens']}")
    return result


def drive_due(win, plan):
    """The requests the window attempted: those due in it (open loop), or
    every request in flight during it (closed loop)."""
    if plan.loop == "open":
        return [r for r in win.records.values() if r.due < win.seconds]
    return list(win.records.values())

