"""Trace reduction, on hand-made intervals and on two short slices of
traces recorded on a TPU v5 lite (``data/``: a dense olmo-1b decode step
pair and one olmo-1b-lcc decode step, op names cut to their HLO name)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_testlib  # noqa: F401  (puts the harness on the path)
from harness import trace as tr
from harness.spec import load_module

DATA = Path(__file__).parent / "data"


def _load(name):
    return json.loads((DATA / name).read_text())


def test_busy_is_the_union_of_overlapping_and_nested_ops():
    evs = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["while", 40, 20],
           ["body", 45, 5]]
    assert tr.busy_ns(evs, (0, 100)) == 15 + 5 + 20
    assert tr.busy_ns(evs, (8, 42)) == 7 + 5 + 2
    assert tr.idle_gaps(evs, (0, 100)) == [(15, 30), (35, 40), (60, 100)]


def test_self_time_takes_nested_ops_out_of_their_parent():
    evs = [["while", 0, 100], ["f1", 10, 20], ["f2", 40, 30], ["f1", 80, 5]]
    tot = tr.self_totals(evs, (0, 100))
    assert tot == {"while": 45, "f1": 25, "f2": 30}
    assert tr.op_totals(evs, (0, 100))["while"] == 100


def test_kernel_time_by_name():
    evs = [["%_lcc_chain_matmul.2", 0, 7], ["%fusion.1", 7, 3],
           ["%_lcc_group_matmul.3", 10, 11]]
    assert tr.matching_ns(evs, (0, 100), r"_lcc_(chain|group)") == 18
    assert tr.matching_ns(evs, (0, 12), r"_lcc_group") == 2


def test_collective_time_without_overlapping_compute():
    evs = [["all-reduce.1", 0, 10], ["fusion.1", 5, 10],
           ["all-gather.2", 20, 10], ["fusion.2", 22, 2],
           ["collective-permute.3", 40, 4]]
    assert tr.collective_exclusive_ns(evs, (0, 100)) == 5 + 8 + 4


def test_gaps_are_named_by_the_host_span_over_them():
    evs = [["a", 0, 10], ["b", 20, 10], ["c", 50, 10]]
    host = [["bench.step", 0, 12], ["bench.admit", 12, 9],
            ["bench.book", 30, 5], ["bench.idle", 35, 15]]
    gaps = tr.attribute_gaps(tr.idle_gaps(evs, (0, 70)), host)
    assert gaps == [("bench.admit", 10), ("bench.idle", 20), ("none", 10)]


def test_recorded_decode_trace():
    t = _load("trace_decode_2steps.json")
    (evs,) = t["devices"].values()
    w = t["window_ns"]
    busy = tr.busy_ns(evs, w)
    assert 0 < busy <= w[1] - w[0]
    assert tr.idle_pct(t) == pytest.approx(100 * (1 - busy / (w[1] - w[0])))
    steps = tr.spans_inside(t["host"], w, "bench.step")
    assert len(steps) == 2
    # the device works through each step: at most a few percent idle
    for a, b in steps:
        assert tr.busy_ns(evs, (a, b)) > 0.9 * (b - a)
    bd = tr.breakdown(t, top=5)
    assert len(bd["device_ops"]) == 5
    own = sum(tr.self_totals(evs, w).values())
    assert own == pytest.approx(busy, rel=1e-6)
    assert all(s > 0 for _, s in bd["device_ops"])


def test_recorded_lcc_trace_roofline_share():
    t = _load("trace_lcc_1step.json")
    reader = load_module(bench_testlib.BENCH.parent, "metrics",
                         "lcc_kernels_roofline")
    sites = [("attn", 187 * 6 * 2048 * 2, 2048, 2048)] * 4 + [
        ("ffn.gate_up", 158 * 6 * 8192 * 2, 2048, 8192)] * 2 + [
        ("ffn.down", 745 * 5 * 2048 * 2, 8192, 2048)]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = SimpleNamespace(trace=t, system=SimpleNamespace(lcc_sites=sites),
                          window=SimpleNamespace(steps=[(0.0, 1.0, 32)]),
                          peaks=peaks)
    share = reader.read(ctx)
    (evs,) = t["devices"].values()
    (step,) = tr.spans_inside(t["host"], t["window_ns"], "bench.step")
    kernel_s = sum(d for n, s, d in evs if "_lcc_" in n
                   and step[0] <= s and s + d <= step[1]) * 1e-9
    least = sum(terms * 6 + 4 * 32 * (k + n)
                for _, terms, k, n in sites) / 819e9
    assert share == pytest.approx(100 * least / kernel_s, rel=1e-9)
    assert 0 < share < 100
    ctx.trace = None
    assert reader.read(ctx) is None
