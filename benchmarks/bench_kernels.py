"""Kernel microbench: us_per_call of Pallas kernels (interpret mode on this
CPU container — wall times validate plumbing, not TPU perf; the TPU-side
value proposition is the HBM-byte reduction quantified in the derived column)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lcc import lcc_decompose
from repro.kernels import ops
from repro.kernels.group_prox import group_prox
from repro.kernels.lcc_matmul import lcc_factor_matmul
from repro.kernels.ref import group_prox_ref, lcc_factor_matmul_ref


def _time(fn, *args, n=5):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / n * 1e6


def run(csv_rows: list[str]) -> None:
    rng = np.random.default_rng(0)
    n, k, b, s = 256, 128, 128, 2
    idx = jnp.asarray(rng.integers(0, k, (n, s)), jnp.int32)
    exp = jnp.asarray(rng.integers(-8, 8, (n, s)), jnp.int8)
    sign = jnp.asarray(rng.choice([-1, 1], (n, s)), jnp.int8)
    x = jnp.asarray(rng.standard_normal((k, b)), jnp.float32)

    us_kernel = _time(lambda: lcc_factor_matmul(idx, exp, sign, x))
    us_ref = _time(lambda: lcc_factor_matmul_ref(idx, exp, sign, x))
    compact_bytes = int(3 * n * s)
    dense_bytes = 2 * n * k
    csv_rows.append(f"lcc_factor_matmul_interp,{us_kernel:.0f},"
                    f"hbm_bytes_ratio={dense_bytes / compact_bytes:.1f}x_smaller")
    csv_rows.append(f"lcc_factor_matmul_ref,{us_ref:.0f},oracle")

    # whole-chain apply on a decomposed matrix
    w = rng.standard_normal((256, 16))
    dec = lcc_decompose(w, algorithm="fp", frac_bits=8)
    packed = ops.pack_decomposition(dec)
    xs = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    us_chain = _time(lambda: ops.apply_packed_decomposition(packed, xs))
    csv_rows.append(
        f"lcc_chain_apply,{us_chain:.0f},"
        f"stored_bytes={dec.storage_bytes()}_vs_dense_bf16={2 * 256 * 16}")

    # fused whole-chain launch vs the legacy per-factor pallas_call loop vs a
    # plain dense matmul, on a >=512-row FP decomposition (the acceptance
    # shape).  One launch holds every intermediate in VMEM scratch; the loop
    # round-trips each one through HBM (and per-launch overhead, in interpret
    # mode the dominant cost it models).
    w5 = rng.standard_normal((512, 16))
    dec5 = lcc_decompose(w5, algorithm="fp", frac_bits=8)
    packed5 = ops.pack_decomposition(dec5)
    x5 = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
    w5_dense = jnp.asarray(dec5.to_dense(), jnp.float32)
    us_fused = _time(lambda: ops.apply_packed_decomposition(packed5, x5))
    us_loop = _time(lambda: ops.apply_packed_decomposition(packed5, x5, fused=False))
    us_dense = _time(lambda: w5_dense @ x5)
    n_factors = sum(len(s.factors) for s in dec5.slices)
    csv_rows.append(f"lcc_chain_fused_512,{us_fused:.0f},"
                    f"one_launch_{len(dec5.col_slices)}slices_{n_factors}factors")
    csv_rows.append(f"lcc_chain_perfactor_512,{us_loop:.0f},"
                    f"speedup_from_fusion={us_loop / us_fused:.1f}x")
    csv_rows.append(f"lcc_chain_dense_matmul_512,{us_dense:.0f},"
                    f"xla_oracle_stored_bytes={dec5.storage_bytes()}"
                    f"_vs_{2 * 512 * 16}")
    err = float(np.abs(np.asarray(ops.apply_packed_decomposition(packed5, x5))
                       - dec5.apply(np.asarray(x5, np.float64))).max())
    csv_rows.append(f"lcc_chain_fused_max_err,{err:.2e},vs_numpy_reference")

    a = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    us_prox = _time(lambda: group_prox(a, 0.5))
    us_prox_ref = _time(lambda: group_prox_ref(a, 0.5))
    csv_rows.append(f"group_prox_interp,{us_prox:.0f},fused_1read_1write")
    csv_rows.append(f"group_prox_ref,{us_prox_ref:.0f},oracle")

    labels = jnp.asarray(rng.integers(0, 64, 256), jnp.int32)
    cents = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
    xx = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    us_sm = _time(lambda: ops.shared_matmul_tpu(cents, labels, xx))
    csv_rows.append(f"shared_matmul_interp,{us_sm:.0f},K256->C64_flop_ratio=4.0x")

    # engine prefill: ONE bulk api.prefill forward vs the legacy per-token
    # decode loop (the pre-PR-2 submit path), same 48-token prompt
    from repro.configs import get_arch
    from repro.configs.base import reduced_config
    from repro.models import api
    from repro.serving.engine import ServingEngine

    cfg = reduced_config(get_arch("olmo-1b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompt = list(rng.integers(0, cfg.vocab, 48))

    def prefill_us(bulk: bool) -> float:
        eng = ServingEngine(params, cfg, n_slots=2, max_len=64,
                            bulk_prefill=bulk)
        eng.submit(prompt)  # warm-up: compiles the prefill/decode fns
        t0 = time.time()
        eng.submit(prompt)
        jax.block_until_ready(jax.tree.leaves(eng.state)[0])
        return (time.time() - t0) * 1e6

    us_tokenwise = prefill_us(False)
    us_bulk = prefill_us(True)
    csv_rows.append(f"engine_prefill_tokenwise_48tok,{us_tokenwise:.0f},"
                    f"one_decode_launch_per_token")
    csv_rows.append(f"engine_prefill_bulk_48tok,{us_bulk:.0f},"
                    f"speedup={us_tokenwise / us_bulk:.1f}x_single_forward")
    for r in csv_rows[-12:]:
        print(r, flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run([])
