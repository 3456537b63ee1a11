"""BENCHMARK.json resolves, file by file, and keeps to the shapes the
benchmark's readers rely on; traffic is a function of the seed."""
import json
import re

import numpy as np
import pytest

import bench_testlib
from harness import spec, traffic

BENCH = json.loads((bench_testlib.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_its_files(workload):
    cell = spec.resolve(workload, bench_testlib.ROOT)
    assert cell.config["system"] and cell.config["reference"]
    for kind, name in (("systems", cell.config["system"]),
                       ("references", cell.config["reference"])):
        assert (bench_testlib.BENCH / kind / f"{name}.py").is_file()
    for m in cell.end_to_end + cell.per_layer:
        mod = spec.load_module(bench_testlib.ROOT, "metrics", m["name"])
        assert callable(mod.read)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert {m["moves"] for m in cell.per_layer} <= names
    dep = cell.deployment
    assert dep["n_slots"] > 0 and dep["max_len"] > 0
    assert dep["checks"]["logit_gap"] > 0 and dep["check_tokens"] > 0


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    for c in BENCH["configs"]:
        cfg = json.loads((bench_testlib.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith("bench/")


def _plan(seed, loop="chat"):
    cell = spec.resolve({"chat": "olmo1b-chat",
                         "batch": "olmo1b-decode"}[loop], bench_testlib.ROOT)
    return traffic.generate(cell.traffic, cell.deployment, vocab=50304,
                            seed=seed, seconds=20.0)


@pytest.mark.parametrize("loop", ["chat", "batch"])
def test_one_seed_gives_the_same_traffic_and_two_seeds_differ(loop):
    a, b, c = _plan(3_000_000_017, loop), _plan(3_000_000_017, loop), \
        _plan(3_000_000_018, loop)
    key = lambda p: [(r.due_s, r.max_new, r.prompt) for r in p.requests]  # noqa: E731
    assert key(a) == key(b)
    assert key(a) != key(c)
    # the same set of sizes in another order
    assert sorted(len(r.prompt) for r in a.requests) == \
        sorted(len(r.prompt) for r in c.requests)
    assert sorted(r.max_new for r in a.requests) == \
        sorted(r.max_new for r in c.requests)


def test_lengths_follow_the_mix():
    p = _plan(5)
    lens = np.array([len(r.prompt) for r in p.requests])
    assert lens.min() >= 16 and lens.max() <= 1024 and (lens % 16 == 0).all()
    outs = np.array([r.max_new for r in p.requests])
    assert outs.min() >= 8 and outs.max() <= 512
    due = np.array([r.due_s for r in p.requests])
    assert (np.diff(due) >= 0).all() and due[-1] < 20.0
    assert len(p.requests) == round(20.0 * 2.0)
    warm = traffic.warmup_prompts(p, vocab=50304, seed=5)
    assert sorted(len(w) for w in warm) == sorted(set(lens.tolist()))


@pytest.mark.parametrize("n", [7, 10, 102])
def test_blocked_order_spreads_every_band_over_the_window(n):
    order = traffic.blocked_order(n, np.random.default_rng(n))
    assert sorted(order.tolist()) == list(range(n))
    band = {int(i): b for b, g in enumerate(np.array_split(np.arange(n), 10))
            for i in g}
    for j in range(0, n, 10):
        block = [band[int(i)] for i in order[j:j + 10]]
        assert len(set(block)) == len(block)
    assert not (order == traffic.blocked_order(
        n, np.random.default_rng(n + 1))).all()


def test_a_traced_runs_tail_leaves_the_window_traffic_alone():
    cell = spec.resolve("olmo1b-chat", bench_testlib.ROOT)
    kw = dict(vocab=50304, seed=3_000_000_019, seconds=20.0)
    a = traffic.generate(cell.traffic, cell.deployment, **kw)
    b = traffic.generate(cell.traffic, cell.deployment, tail=5.0, **kw)
    n = len(a.requests)
    assert [(r.due_s, r.max_new, r.prompt) for r in a.requests] == \
        [(r.due_s, r.max_new, r.prompt) for r in b.requests[:n]]
    extra = b.requests[n:]
    assert len(extra) == round(5.0 * cell.deployment["rate_per_s"])
    assert all(r.due_s >= 20.0 for r in extra)
    assert [r.rid for r in b.requests] == list(range(len(b.requests)))
