"""LCC structural claims (paper Sec. III-A): tall matrices are LCC-friendly,
unstructured sparsity hurts, FS beats FP on small/ill-conditioned matrices."""
from __future__ import annotations

import time

import numpy as np

from repro.core.csd import adds_csd_matrix
from repro.core.lcc import lcc_decompose


def run(csv_rows: list[str]) -> None:
    t0 = time.time()
    rng = np.random.default_rng(0)

    # claim 1: compression improves with aspect ratio (N/K) at fixed N
    for k in (64, 32, 16, 8):
        w = rng.standard_normal((256, k))
        base = adds_csd_matrix(w, 8)
        d = lcc_decompose(w, algorithm="fp", frac_bits=8)
        row = f"lcc_scaling,aspect,N=256,K={k},ratio={base / max(d.num_adds(), 1):.2f}"
        print(row, flush=True)
        csv_rows.append(row)

    # claim 2: unstructured sparsity degrades LCC vs structured (column) removal
    w = rng.standard_normal((256, 32))
    w_unstruct = w * (rng.random((256, 32)) > 0.5)  # random 50% zeros
    w_struct = w[:, :16]  # drop half the columns instead
    for name, m in (("dense", w), ("unstructured_50", w_unstruct),
                    ("structured_half", w_struct)):
        base = adds_csd_matrix(m, 8)
        d = lcc_decompose(m, algorithm="fp", frac_bits=8)
        row = f"lcc_scaling,sparsity={name},ratio={base / max(d.num_adds(), 1):.2f}"
        print(row, flush=True)
        csv_rows.append(row)

    # claim 3: FS >= FP on small / not-well-behaved (rank-deficient) matrices
    small = rng.standard_normal((48, 8))
    lowrank = (rng.standard_normal((48, 3)) @ rng.standard_normal((3, 8)))
    for name, m in (("small", small), ("rank3", lowrank)):
        dfp = lcc_decompose(m, algorithm="fp", target_snr_db=40.0)
        dfs = lcc_decompose(m, algorithm="fs", target_snr_db=40.0)
        row = (f"lcc_scaling,{name},fp_adds={dfp.num_adds()},fs_adds={dfs.num_adds()},"
               f"fs_gain={dfp.num_adds() / max(dfs.num_adds(), 1):.2f}")
        print(row, flush=True)
        csv_rows.append(row)
    run_fidelity_sweep(csv_rows)
    csv_rows.append(f"lcc_scaling_wall_s,{time.time() - t0:.1f},")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run([])


def run_fidelity_sweep(csv_rows: list[str]) -> None:
    """Beyond-paper ablation: adds & stream-bytes vs fidelity target.

    The paper fixes fidelity at the CSD-quantization SNR; serving systems pick
    a point on this curve (int8-equivalent ~ 40 dB is the common deployment
    choice)."""
    rng = np.random.default_rng(1)
    w = rng.standard_normal((256, 16))
    base = adds_csd_matrix(w, 8)
    dense_bytes = 2 * 256 * 16
    for snr in (25.0, 30.0, 40.0, 50.0, 60.0):
        d = lcc_decompose(w, algorithm="fs", target_snr_db=snr)
        row = (f"lcc_fidelity,snr_target={snr:.0f}dB,adds_ratio="
               f"{base / max(d.num_adds(), 1):.2f},"
               f"stream_vs_bf16={dense_bytes / max(d.storage_bytes(), 1):.2f}")
        print(row, flush=True)
        csv_rows.append(row)
