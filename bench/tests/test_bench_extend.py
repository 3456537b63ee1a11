"""A configuration, a traffic mix, a cell and a per-layer metric added as
new files and entries run through the harness's functions at a tiny size on
the CPU, with no edit to an existing file."""
import json

import bench_testlib
from harness import trace as tr


def test_added_cell_mix_config_and_metric_run(tmp_path, monkeypatch):
    root = bench_testlib.tiny_root(tmp_path)
    for f in ("configs/olmo-1b.json", "traffic/chat.json",
              "cells/olmo1b-chat.json", "metrics/decode_tok_s.py",
              "harness/cell.py"):
        assert (root / "bench" / f).read_bytes() == \
            (bench_testlib.BENCH / f).read_bytes()
    r = bench_testlib.run_tiny(monkeypatch, root, "tiny-chat")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 24
    assert set(r["metrics"]) == {"decode_tok_s", "ttft_p95_ms",
                                 "tpot_p95_ms", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["logit_gap"]["limit"] == bench_testlib.TINY_LIMIT
    assert r["device"]["platform"] == "cpu"
    json.dumps(r)

    # the per-layer side: the added metric is read; a trace that shows no
    # chip gives no device numbers at all
    monkeypatch.setattr(tr, "load_xplane", lambda d: None)
    r = bench_testlib.run_tiny(monkeypatch, root, "tiny-chat", trace=True)
    m = r["metrics"]
    assert r["attempted"] == 24  # the slice after the window adds nothing
    assert m["tiny.admitted"]["value"] >= 1
    assert m["tiny.admitted"]["unit"] == "requests"
    assert "device.idle_share.chat" not in m
    assert "busy_s" not in r["device"] and "breakdown" not in r
