"""Measure the chain structure the repository's FP compressor produces at a
model's site shapes, on seeded weights, so a synthetic LCC artifact can
follow it.

    PYTHONPATH=src python bench/tools/chain_sample.py --slices 4 --seed 0

For each site shape of olmo-1b (attention 2048->2048, FFN gate/up
2048->8192, FFN down 8192->2048) it draws the whole weight from the seed at
the initialiser's scale (truncated normal / sqrt(fan_in)), resolves the
compressor's fidelity target on the whole matrix (``CompressionConfig()``
defaults: ``frac_bits`` 8, ``s_terms`` 2, ``max_factors`` 24; algorithm
"fp"), decomposes a sample of its column slices with
``lcc_decompose_slice`` and prints one JSON object: per site, the factor
count, terms per row of every factor, exponent range and seconds per slice.
Runs on the host CPU; nothing here touches an accelerator.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

from repro.core.compress import CompressionConfig  # noqa: E402
from repro.core.csd import quantization_snr_db  # noqa: E402
from repro.core.lcc import lcc_decompose_slice, plan_col_slices  # noqa: E402

# (site kind, out rows N, in columns K) of one olmo-1b layer, as y = W x
SITES = (("attn", 2048, 2048), ("ffn.gate_up", 8192, 2048),
         ("ffn.down", 2048, 8192))


def sample_site(kind: str, n: int, k: int, *, slices: int, seed: int) -> dict:
    rng = np.random.default_rng([seed, n, k])
    w = np.clip(rng.standard_normal((n, k)), -2, 2) / np.sqrt(k)
    cc = CompressionConfig()
    target = quantization_snr_db(w, cc.frac_bits)
    grid = plan_col_slices(n, k, cc.slice_width)
    picks = sorted(rng.choice(len(grid), size=slices, replace=False))
    rows = []
    for e in picks:
        c0, c1 = grid[e]
        t0 = time.perf_counter()
        chain = lcc_decompose_slice(w[:, c0:c1], "fp", target,
                                    s_terms=cc.s_terms,
                                    max_factors=cc.max_factors)
        secs = time.perf_counter() - t0
        exps = np.concatenate([f.exp[f.sign != 0] for f in chain.factors])
        rows.append({
            "slice": int(e), "width": c1 - c0, "factors": len(chain.factors),
            "terms_per_row": [f.s_terms for f in chain.factors],
            "exp_min": int(exps.min()), "exp_max": int(exps.max()),
            "first_factor_exp_median": float(np.median(
                chain.factors[0].exp[chain.factors[0].sign != 0])),
            "seconds": round(secs, 2)})
    return {"site": kind, "shape": [n, k], "slices_total": len(grid),
            "target_snr_db": round(target, 2), "sampled": rows}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slices", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    out = [sample_site(kind, n, k, slices=args.slices, seed=args.seed)
           for kind, n, k in SITES]
    print(json.dumps({"algorithm": "fp", "defaults": "CompressionConfig()",
                      "seed": args.seed, "sites": out}, indent=1))


if __name__ == "__main__":
    main()
