"""The readings of the program's own spans and counters
(``harness.phases``), on hand-made intervals and snapshots, on a CPU trace
and on a slice of a chat trace recorded on a TPU v5 lite with the program's
spans (``data/trace_chat_admit_step.json``: one admission and the decode
step after it); and the harness's existing readings of its recorded
traces, pinned as they were recorded (``data/recorded_readings.json``), so
a change to the trace reduction that moves any of them shows here."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_testlib
from harness import drive, phases, trace as tr
from harness.spec import load_module

DATA = Path(__file__).parent / "data"
RECORDED = json.loads((DATA / "recorded_readings.json").read_text())
LCC_SITES = [("attn", 187 * 6 * 2048 * 2, 2048, 2048)] * 4 + [
    ("ffn.gate_up", 158 * 6 * 8192 * 2, 2048, 8192)] * 2 + [
    ("ffn.down", 745 * 5 * 2048 * 2, 8192, 2048)]


def _metric(name):
    return load_module(bench_testlib.ROOT, "metrics", name)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_traces_read_as_recorded(name):
    t = json.loads((DATA / name).read_text())
    want = RECORDED[name]
    assert tr.idle_pct(t) == pytest.approx(want["idle_pct"], rel=1e-12)
    for m in ("device.idle_share.chat", "device.idle_share.batch"):
        assert _metric(m).read(SimpleNamespace(trace=t)) == pytest.approx(
            want["idle_pct"], rel=1e-12)
    got = tr.breakdown(t)
    assert set(got) == set(want["breakdown"])
    for key, rows in want["breakdown"].items():
        assert [n for n, _ in got[key]] == [n for n, _ in rows], key
        assert [v for _, v in got[key]] == pytest.approx(
            [v for _, v in rows], rel=1e-12), key
    assert len(tr.spans_inside(t["host"], t["window_ns"],
                               "bench.step")) == want["bench_steps"]
    if "lcc_kernels_roofline" in want:
        ctx = SimpleNamespace(
            trace=t, system=SimpleNamespace(lcc_sites=LCC_SITES),
            window=SimpleNamespace(steps=[(0.0, 1.0, 32)]),
            peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
        assert _metric("lcc_kernels_roofline").read(ctx) == pytest.approx(
            want["lcc_kernels_roofline"], rel=1e-12)


# a step whose per-slot loop leaves the device idle 30-45, inside
# serving.step.events; an admission idle 70-80 inside serving.admit with a
# serving.submit, and one 90-95 that admitted nothing; a gap 100-110 the
# program spans do not cover, inside bench.book
TRACE = {
    "devices": {"/device:TPU:0": [["a", 0, 30], ["b", 45, 25],
                                  ["c", 80, 10], ["d", 95, 5],
                                  ["e", 110, 10]]},
    "host": [["bench.step", 0, 60], ["bench.admit", 60, 35],
             ["bench.book", 95, 25]],
    "window_ns": [0, 120],
}
PROGRAM = [["serving.step", 2, 55], ["serving.step.dispatch", 3, 4],
           ["serving.step.wait", 8, 20], ["serving.step.events", 29, 17],
           ["serving.admit", 62, 26], ["serving.submit", 63, 24],
           ["serving.submit.prefill", 64, 5], ["serving.admit", 89, 5]]


def test_gaps_are_named_by_the_innermost_program_span_over_them():
    gaps = phases.program_gaps(TRACE, PROGRAM, top=3)
    assert gaps == [["serving.step.events", pytest.approx(15e-9)],
                    ["serving.submit", pytest.approx(10e-9)],
                    ["bench.book", pytest.approx(10e-9)]]
    # a gap across two phases goes to the one holding more of it, not to
    # the step around both; where no span overlaps a gap, it is ``none``
    assert phases.name_gap(5, 12, PROGRAM, TRACE["host"]) == (
        "serving.step.wait")
    assert phases.name_gap(200, 210, PROGRAM, TRACE["host"]) == "none"


def test_idle_inside_harness_spans_and_its_part_under_program_spans():
    idle, covered = phases.idle_covered(TRACE, PROGRAM)
    assert idle == 15 + 10 + 5  # bench.step and bench.admit only
    assert covered == 15 + 10 + 4  # serving.admit ends at 94


def test_admission_idle_counts_passes_that_admitted():
    assert phases.admit_idle_ms(TRACE, PROGRAM) == pytest.approx(10e-6)
    assert phases.admit_idle_ms(TRACE, PROGRAM[:4]) is None


def test_recorded_chat_slice_names_its_gaps_by_program_phase():
    t = json.loads((DATA / "trace_chat_admit_step.json").read_text())
    gaps = phases.program_gaps(t, t["program"], top=2)
    # the harness names the same two gaps only by its own spans
    assert [n for n, _ in tr.breakdown(t, top=2)["idle_gaps"]] == [
        "bench.admit", "bench.step"]
    assert [n for n, _ in gaps] == ["serving.submit.scatter",
                                    "serving.step.dispatch"]
    assert [v for _, v in gaps] == pytest.approx([0.027234722, 0.020967612],
                                                 rel=1e-9)
    idle, covered = phases.idle_covered(t, t["program"])
    assert covered / idle > 0.99
    assert phases.admit_idle_ms(t, t["program"]) == pytest.approx(
        44.504524, rel=1e-9)


def test_program_spans_are_read_from_a_profiler_trace(tmp_path):
    import jax

    from harness import program

    program.import_program()
    from repro.obs import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.step"):
            with span("serving.step", step_num=0):
                with span("serving.step.wait", rid=1):
                    pass
    finally:
        jax.profiler.stop_trace()
    got = phases.load_program(str(tmp_path))
    assert [n for n, *_ in got] == ["serving.step", "serving.step.wait"]
    (_, s0, d0), (_, s1, d1) = got
    assert s0 <= s1 and s1 + d1 <= s0 + d0
    # the harness's own loader still keeps its spans only, and wants a device
    with pytest.raises(ValueError, match="no device operations"):
        tr.load_xplane(str(tmp_path))


def _snap(steps, phase, tokens):
    def rows(d):
        return [{"labels": {"phase": k} if isinstance(k, str) else {},
                 "value": v} for k, v in d.items()]

    return {"serving_decode_steps_total": {"values": rows({(): steps})},
            "serving_phase_seconds_total": {"values": rows(phase)},
            "serving_prefill_tokens_total": {"values": [
                {"labels": {"kind": k}, "value": v}
                for k, v in tokens.items()]}}


def test_counter_readings_are_deltas_between_snapshots():
    before = _snap(10, {"step.dispatch": 1.0, "step.wait": 5.0},
                   {"prompt": 500, "cached": 0, "pad": 100})
    after = _snap(30, {"step.dispatch": 1.2, "step.wait": 9.0,
                       "step.events": 0.1, "deliver": 0.1, "admit": 3.0},
                  {"prompt": 800, "cached": 64, "pad": 200})
    assert phases.counter_delta(before, after, "serving_phase_seconds_total"
                                ) == pytest.approx({
                                    "step.dispatch": 0.2, "step.wait": 4.0,
                                    "step.events": 0.1, "deliver": 0.1,
                                    "admit": 3.0})
    # the wait for the tokens and the admissions are not the step's host time
    assert phases.step_host_ms(before, after) == pytest.approx(0.4 / 20 * 1e3)
    assert phases.prefill_pad_share(before, after) == pytest.approx(25.0)
    assert phases.step_host_ms(before, before) is None
    assert phases.prefill_pad_share(before, before) is None


def test_compiles_by_span_between_snapshots():
    def snap(n, s):
        return {"serving_compiles_total": {"values": [
                    {"labels": {"span": k}, "value": v}
                    for k, v in n.items()]},
                "serving_compile_seconds_total": {"values": [
                    {"labels": {"span": k}, "value": v}
                    for k, v in s.items()]}}

    a = snap({"serving.submit.prefill": 2}, {"serving.submit.prefill": 3.0})
    b = snap({"serving.submit.prefill": 3, "none": 1},
             {"serving.submit.prefill": 4.5, "none": 0.25})
    assert phases.compiles_by_span(a, b) == {
        "none": [1, 0.25], "serving.submit.prefill": [1, 1.5]}
    assert phases.compiles_by_span(b, b) == {}


def _phases_tool():
    spec = importlib.util.spec_from_file_location(
        "phases_tool", bench_testlib.BENCH / "tools" / "phases.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("loop", ["open", "closed"])
@pytest.mark.parametrize("hooks_fire", [True, False])
def test_phases_tool_snapshots_bracket_the_window_not_the_slice(
        monkeypatch, loop, hooks_fire):
    """The tool's counter snapshots are timed by the profiler's start and
    stop: an open loop closes before its traced slice, a closed loop opens
    after it; where the hooks do not fire, the run is an error."""
    import jax

    tool = _phases_tool()
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    done = []
    engine = SimpleNamespace(metrics=SimpleNamespace(
        snapshot=lambda: {"done": list(done)}))

    def run_window(sched, eng, plan, seconds, **kw):
        def traced_slice():
            if hooks_fire:
                jax.profiler.start_trace("trace-dir")
            done.append("slice")
            if hooks_fire:
                jax.profiler.stop_trace()

        if plan.loop == "closed":
            traced_slice()
        done.append("window")
        if plan.loop == "open":
            traced_slice()
        return "win"

    monkeypatch.setattr(drive, "run_window", run_window)
    snaps = {}
    restore = tool.instrument(snaps, [])
    try:
        plan = SimpleNamespace(loop=loop)
        if not hooks_fire:
            with pytest.raises(RuntimeError, match="hook did not fire"):
                drive.run_window(None, engine, plan, 1.0)
            return
        assert drive.run_window(None, engine, plan, 1.0) == "win"
    finally:
        restore()
    opened, closed = snaps["open"][0]["done"], snaps["close"][0]["done"]
    assert closed[len(opened):] == ["window"]
