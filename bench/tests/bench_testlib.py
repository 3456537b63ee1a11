"""Shared helpers of the benchmark's CPU tests: the harness on the path, and
a checkout in a temporary directory that adds a tiny configuration, traffic
mixes, cells and a metric as new files and entries — the way a later change
extends the benchmark — without editing an existing file."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_LIMIT = 0.1  # logit gap: tiny sound runs read 0-0.013, faults 2-5

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256}


def write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def tiny_root(tmp: Path) -> Path:
    """A checkout with tiny cells ``tiny-chat`` and ``tiny-lcc`` and a new
    per-layer metric ``tiny.admitted``, all added as files."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
    lcc = json.loads((BENCH / "configs" / "olmo-1b-lcc.json").read_text())
    write(root / "bench/configs/tiny.json", {**base, **TINY})
    lcc_tiny = {**lcc, **TINY, "num_key_value_heads": 4,
                "num_hidden_layers": 1}
    lcc_tiny["lcc"] = {**lcc["lcc"], "factors": {
        "attn": 3, "ffn.gate": 3, "ffn.up": 3, "ffn.down": 2}}
    write(root / "bench/configs/tiny-lcc.json", lcc_tiny)
    # short prompts, long replies: most of a reply's context is its own
    # served tokens, so a cache that keeps none of them shows in the check
    write(root / "bench/traffic/tiny-open.json", {
        "loop": "open",
        "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.3,
                       "min": 4, "max": 16, "grid": 4},
        "output_len": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                       "min": 16, "max": 40}})
    write(root / "bench/traffic/tiny-closed.json", {
        "loop": "closed", "requests_per_client": 3,
        "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                       "min": 8, "max": 32, "grid": 8},
        "output_len": {"dist": "uniform", "min": 6, "max": 12}})
    dep = {"n_slots": 4, "max_len": 128, "check_tokens": 40,
           "checks": {"logit_gap": TINY_LIMIT}, "trace_seconds": 0.5}
    write(root / "bench/cells/tiny-chat.json",
          {**dep, "rate_per_s": 8.0, "check_tokens": 200})
    write(root / "bench/cells/tiny-lcc.json", dep)
    write(root / "bench/metrics/tiny.admitted.py",
          '"""Requests admitted in the window."""\n\n\n'
          "def read(ctx):\n"
          "    return float(sum(r.admit is not None\n"
          "                     for r in ctx.window.records.values()))\n")
    bench["configs"] += [
        {"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-lcc", "source": "test",
         "file": "bench/configs/tiny-lcc.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] += [
        {"name": "tiny-chat", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "test"},
        {"name": "tiny-lcc", "config": "tiny-lcc", "traffic": "tiny-closed",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny-chat")
    bench["per_layer"].append(
        {"name": "tiny.admitted", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "scheduler",
         "moves": "decode_tok_s", "workloads": ["tiny-chat", "tiny-lcc"]})
    write(root / "BENCHMARK.json", bench)
    return root


def run_tiny(monkeypatch, root: Path, workload: str, *, seed: int = 3,
             seconds: float = 3.0, trace: bool = False,
             control: bool = False) -> dict:
    """``run_cell`` on the CPU, with the chip check skipped and the
    persistent compile cache left off."""
    from harness import cell, program

    monkeypatch.setattr(program, "enable_cache", lambda: "off")
    return cell.run_cell(workload, seed=seed, seconds=seconds, trace=trace,
                         t_start=time.perf_counter(), root=root,
                         require_tpu=False, control=control)
