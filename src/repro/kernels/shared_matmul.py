"""Pallas TPU kernel: weight-sharing input pre-aggregation (paper eq. (10)).

``agg[c, b] = sum_{j: labels[j]==c} x[j, b]`` — the per-cluster scalar sums
that let the centroid matrix replace the full weight matrix.  On TPU the
segment sum is realized as a one-hot(labels) x contraction so it runs on the
MXU; the one-hot tile is built in VMEM from an iota comparison (never
materialized in HBM).

Grid (c_blocks, k_blocks, b_blocks); K is contracted, accumulated in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret

__all__ = ["cluster_segment_sum"]


def _kernel(labels_ref, x_ref, o_ref, *, block_c: int):
    c_blk = pl.program_id(0)
    k_blk = pl.program_id(1)
    c0 = c_blk * block_c

    @pl.when(k_blk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    labels = labels_ref[...]  # [1, bk] int32
    bk = labels.shape[1]
    clusters = jax.lax.broadcasted_iota(jnp.int32, (block_c, bk), 0) + c0
    onehot = (labels == clusters).astype(jnp.float32)  # [bc, bk]
    x = x_ref[...].astype(jnp.float32)  # [bk, bb]
    o_ref[...] += jnp.dot(onehot, x, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_clusters", "block_c", "block_k", "block_b", "interpret"))
def cluster_segment_sum(
    labels: jnp.ndarray,
    x: jnp.ndarray,
    num_clusters: int,
    block_c: int = 128,
    block_k: int = 128,
    block_b: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """agg[C, B] = segment_sum(x[K, B], labels[K])."""
    k, b = x.shape
    c = num_clusters
    block_c = min(block_c, c)
    block_k = min(block_k, k)
    block_b = min(block_b, b)
    if c % block_c or k % block_k or b % block_b:
        raise ValueError(f"shapes (C={c},K={k},B={b}) must tile by ({block_c},{block_k},{block_b})")
    grid = (c // block_c, k // block_k, b // block_b)
    return pl.pallas_call(
        functools.partial(_kernel, block_c=block_c),
        grid=grid,
        in_specs=[
            # labels ride as a [1, K] row: a 1-D block would not match the
            # tiling XLA gives a 1-D int32 operand on TPU
            pl.BlockSpec((1, block_k), lambda i, j, p: (0, j)),
            pl.BlockSpec((block_k, block_b), lambda i, j, p: (j, p)),
        ],
        out_specs=pl.BlockSpec((block_c, block_b), lambda i, j, p: (i, p)),
        out_shape=jax.ShapeDtypeStruct((c, b), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(labels.reshape(1, k), x)
