"""Pallas TPU kernel: grouped fused LCC evaluation — G decompositions, ONE launch.

``lcc_chain_matmul`` fuses every factor of every slice of *one* decomposition
into a single launch.  A decode step, however, touches many decompositions at
once: the experts of an MoE layer (each token's top-k experts apply their own
chains), the q/k/v projections of an attention layer (same input, three
compressed maps), the r/k/v/g time-mix projections of RWKV-6.  Launching one
``pallas_call`` per site brings back exactly the per-launch overhead the fused
chain kernel removed — so this kernel adds a leading *group* axis and applies
G whole decompositions in one dispatch:

  idx  [G, E, P, N_pad, S] int32   term column index (slice e of group g)
  exp  [G, E, P, N_pad, S] int8    power-of-two exponent
  sign [G, E, P, N_pad, S] int8    {-1, 0, +1}; 0 = unused slot / padding
  x    [G, E, D_pad, B_pad] f32    per-group slice inputs, zero-padded
  out  [G, N_pad, B_pad] f32       group g's output, accumulated over its e

Groups are padded to common (E, P, N_pad, S, D_pad) by
``repro.kernels.ops.pack_group``: missing slices carry sign == 0 everywhere
(they decompress to a zero factor and contribute nothing), short chains are
right-padded with identity factors, and narrow groups ride the shared D_pad
with zero rows — the same invariants ``lcc_chain_matmul`` already relies on.

Grid (G, b_blocks, E): slices innermost, so group g's output tile is revisited
across e and accumulated in place; both chain-evaluation bodies (interpreter
gather, compiled one-hot) are shared with ``lcc_chain_matmul``
(``slice_axis=2``), including the compiled body's batch-major re-layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import record_launch, resolve_interpret
from .lcc_chain_matmul import (_gather_kernel, _onehot_kernel,
                               _onehot_operands)

__all__ = ["lcc_group_matmul"]


def lcc_group_matmul(
    idx: jnp.ndarray,
    exp: jnp.ndarray,
    sign: jnp.ndarray,
    x: jnp.ndarray,
    block_b: int = 128,
    first_width: int | None = None,
    interpret: bool | None = None,
    use_gather: bool | None = None,
    name: str = "_lcc_group_matmul",
) -> jnp.ndarray:
    """y[G, N_pad, B_pad] = per-group sum_e chain_{g,e}(x[g, e]) — one launch.

    Same contract as :func:`~repro.kernels.lcc_chain_matmul.lcc_chain_matmul`
    per group; ``first_width`` is shared across groups (the max padded slice
    width — narrower groups read zero-padded columns, which contribute 0).
    ``name``: the launch's name in the compiled program and the device trace.
    """
    record_launch()  # un-jitted: counts once per pallas_call a trace emits
    return _lcc_group_matmul(idx, exp, sign, x, block_b=block_b,
                             first_width=first_width, interpret=interpret,
                             use_gather=use_gather, name=name)


@functools.partial(jax.jit, static_argnames=("block_b", "first_width",
                                             "interpret", "use_gather",
                                             "name"))
def _lcc_group_matmul(
    idx: jnp.ndarray,
    exp: jnp.ndarray,
    sign: jnp.ndarray,
    x: jnp.ndarray,
    block_b: int = 128,
    first_width: int | None = None,
    interpret: bool | None = None,
    use_gather: bool | None = None,
    name: str = "_lcc_group_matmul",
) -> jnp.ndarray:
    g_groups, e_slices, p_factors, n_pad, s_terms = idx.shape
    xg, xe, d_pad, b_pad = x.shape
    if (xg, xe) != (g_groups, e_slices):
        raise ValueError(f"group/slice mismatch: idx has {(g_groups, e_slices)},"
                         f" x has {(xg, xe)}")
    if d_pad < n_pad:
        raise ValueError(f"D_pad={d_pad} must cover N_pad={n_pad}")
    first_width = d_pad if first_width is None else min(first_width, d_pad)
    block_b = min(block_b, b_pad)
    if b_pad % block_b:
        raise ValueError(f"B_pad={b_pad} must tile by block_b={block_b}")
    run_interpret = resolve_interpret(interpret)
    if use_gather is None:
        use_gather = run_interpret
    if use_gather:
        spec = pl.BlockSpec((None, None, p_factors, n_pad, s_terms),
                            lambda g, b, e: (g, e, 0, 0, 0))
        return pl.pallas_call(
            functools.partial(_gather_kernel, p_factors=p_factors,
                              s_terms=s_terms, n_pad=n_pad, slice_axis=2),
            grid=(g_groups, b_pad // block_b, e_slices),
            in_specs=[spec, spec, spec,
                      pl.BlockSpec((None, None, d_pad, block_b),
                                   lambda g, b, e: (g, e, 0, b))],
            out_specs=pl.BlockSpec((None, n_pad, block_b),
                                   lambda g, b, e: (g, 0, b)),
            out_shape=jax.ShapeDtypeStruct((g_groups, n_pad, b_pad),
                                           jnp.float32),
            scratch_shapes=[pltpu.VMEM((d_pad, block_b), jnp.float32)],
            interpret=run_interpret,
            name=name,
        )(idx, exp, sign, x.astype(jnp.float32))
    idx_t, coef_t, x_t, bb, bt_pad = _onehot_operands(idx, exp, sign, x,
                                                     block_b)
    spec = pl.BlockSpec((None, None, p_factors * s_terms, n_pad),
                        lambda g, b, e: (g, e, 0, 0))
    y = pl.pallas_call(
        functools.partial(_onehot_kernel, p_factors=p_factors,
                          s_terms=s_terms, n_pad=n_pad,
                          first_width=first_width, d_pad=d_pad, slice_axis=2),
        grid=(g_groups, bt_pad // bb, e_slices),
        in_specs=[spec, spec,
                  pl.BlockSpec((None, None, bb, d_pad),
                               lambda g, b, e: (g, e, b, 0))],
        out_specs=pl.BlockSpec((None, bb, n_pad), lambda g, b, e: (g, b, 0)),
        out_shape=jax.ShapeDtypeStruct((g_groups, bt_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, d_pad), jnp.float32),
                        pltpu.VMEM((bb, n_pad), jnp.float32)],
        interpret=run_interpret,
        name=name,
    )(idx_t, coef_t, x_t)
    return jnp.swapaxes(y[:, :b_pad], 1, 2)
