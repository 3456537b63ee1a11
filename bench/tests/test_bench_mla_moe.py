"""The DeepSeek-V2-Lite cell (configuration ``deepseek-v2-lite``, system
``mla_moe``, reference ``deepseek_v2``, cell ``dsv2lite-decode`` and its two
per-layer metrics) resolves by name, with the harness's files as they were;
a tiny cell of the same files runs through the harness on the CPU, and its
control (the reference in float8) comes out not correct."""
import json
from types import SimpleNamespace

import pytest

import bench_testlib
from harness import spec as hspec
from harness.mla_moe_cost import MLAMoEShape, matmul_params, moe_layer_cost
from harness.spec import load_module

NEW_METRICS = {"decode_step.mfu.mla_moe", "moe_experts_roofline"}

# 1 dense + 2 MoE layers, 8 experts top-3 + 1 shared, small MLA dims, YaRN on
TINY_DS = {"num_hidden_layers": 3, "hidden_size": 64, "intermediate_size": 96,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
           "v_head_dim": 16, "moe_intermediate_size": 32,
           "n_routed_experts": 8, "num_experts_per_tok": 3,
           "n_shared_experts": 1, "vocab_size": 256}


def _config() -> dict:
    return json.loads((bench_testlib.BENCH / "configs" /
                       "deepseek-v2-lite.json").read_text())


def test_cell_resolves_by_name():
    cell = hspec.resolve("dsv2lite-decode", bench_testlib.ROOT)
    assert (cell.chips, cell.config_name, cell.traffic_name) == \
        (1, "deepseek-v2-lite", "decode-batch")
    assert {m["name"] for m in cell.end_to_end} == {"decode_tok_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == NEW_METRICS
    dep = cell.deployment
    assert (dep["n_slots"], dep["max_len"], dep["check_tokens"]) == \
        (64, 1152, 1000)
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert cell.config["num_hidden_layers"] == 7
    root = bench_testlib.ROOT
    assert hasattr(load_module(root, "systems", cell.config["system"]),
                   "build")
    ref = load_module(root, "references", cell.config["reference"])
    assert ref.spec_from_config(cell.config).n_experts == 64
    for m in NEW_METRICS:
        assert callable(load_module(root, "metrics", m).read)
    # the earlier cells report neither new metric
    for w in ("olmo1b-chat", "olmo1b-decode", "olmo1b-lcc-decode"):
        per = hspec.resolve(w, root).per_layer
        assert not NEW_METRICS & {m["name"] for m in per}


def test_weights_a_token_multiplies():
    """2.45 B at the published 27 layers (2.4 B in the paper), 0.789 B at
    the cell's 7."""
    c = _config()
    assert abs(matmul_params(MLAMoEShape.from_config(
        {**c, "num_hidden_layers": 27})) / 2.45e9 - 1) < 0.005
    assert abs(matmul_params(MLAMoEShape.from_config(c)) / 0.789e9 - 1) < 0.005


def _ctx(events, steps=64.0):
    """A traced slice of two 50 ms decode steps, as the harness reduces it."""
    ms = 1_000_000
    trace = {"window_ns": [0, 100 * ms],
             "host": [["bench.step", 0, 50 * ms], ["bench.step", 50 * ms,
                                                   50 * ms]],
             "devices": {"/device:TPU:0": events}}
    return SimpleNamespace(
        trace=trace, peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9},
        cell=SimpleNamespace(config=_config()),
        window=SimpleNamespace(steps=[(0.0, 0.05, steps), (0.05, 0.1, steps)]))


def test_experts_roofline_reads_the_grouped_launches():
    metric = load_module(bench_testlib.ROOT, "metrics", "moe_experts_roofline")
    ms = 1_000_000
    # 6 MoE layers a step, each: gate/up 1.5 ms, down 0.8 ms; plus others
    evs = []
    for step in range(2):
        t = step * 50 * ms
        for layer in range(6):
            evs.append(["%_moe_group_matmul_gate_up.3 = bf16[384,1408]",
                        t, 3 * ms // 2])
            evs.append(["%_moe_group_matmul_down.2 = f32[384,2048]",
                        t + 2 * ms, 4 * ms // 5])
            t += 5 * ms
        evs.append(["%fusion.7 = bf16[64,1152,16,192]", t, 10 * ms])
    shape = MLAMoEShape.from_config(_config())
    flops, nbytes = moe_layer_cost(shape, 64.0)
    least = 6 * max(flops / 197e12, nbytes / 819e9)
    want = 100.0 * 2 * least / (2 * 6 * 2.3e-3)
    got = metric.read(_ctx(evs))
    assert got == pytest.approx(want, rel=1e-9)
    assert 40.0 < got < 100.0  # bytes bound: 64 experts x 17.3 MB a layer
    # a program without the launches (the parent) gives nothing to read
    assert metric.read(_ctx([e for e in evs if "moe" not in e[0]])) is None


def _ds_root(tmp_path):
    root = bench_testlib.tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_testlib.write(root / "bench/configs/tiny-dsv2.json",
                        {**_config(), **TINY_DS})
    bench_testlib.write(root / "bench/cells/tiny-dsv2.json", {
        "n_slots": 4, "max_len": 128, "check_tokens": 40,
        "checks": {"logit_gap": bench_testlib.TINY_LIMIT},
        "trace_seconds": 0.5})
    bench["configs"].append({"name": "tiny-dsv2", "source": "test",
                             "file": "bench/configs/tiny-dsv2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-dsv2", "config": "tiny-dsv2",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    bench_testlib.write(root / "BENCHMARK.json", bench)
    return root


@pytest.fixture(scope="module")
def ds_root(tmp_path_factory):
    return _ds_root(tmp_path_factory.mktemp("dsv2"))


def test_tiny_cell_runs_correct(ds_root, monkeypatch):
    for f in ("harness/cell.py", "harness/program.py", "harness/correct.py",
              "systems/dense.py", "references/dense_decoder.py"):
        assert (ds_root / "bench" / f).read_bytes() == \
            (bench_testlib.BENCH / f).read_bytes()
    r = bench_testlib.run_tiny(monkeypatch, ds_root, "tiny-dsv2", seed=5)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert set(r["metrics"]) == {"decode_tok_s", "setup_s"}
    assert r["checks"]["tokens_checked"]["value"] >= 40


def test_tiny_cell_control_is_not_correct(ds_root, monkeypatch):
    r = bench_testlib.run_tiny(monkeypatch, ds_root, "tiny-dsv2", seed=5,
                               control=True)
    c = r["checks"]
    assert not r["correct"]
    assert c["logit_gap"]["value"] > c["logit_gap"]["limit"]
    assert c["program_gap"]["value"] <= c["logit_gap"]["limit"]
