"""Pallas TPU kernel: fused group-lasso proximal operator (paper eq. (8)).

Row-wise block soft threshold:  out_i = max(1 - t / ||a_i||_2, 0) * a_i.
Fusing the norm reduction with the rescale keeps the weight tile resident in
VMEM — one HBM read + one write per weight, instead of read(norm) + read+write
(scale) when expressed as two XLA ops.  Runs every ProxSGD step over every
regularized weight matrix, so it is on the training hot path.

Grid over row blocks; the full row (group) must fit one block — groups are
matrix rows/columns (<= a few x 10^4 elements).  The row block shrinks with
the row length so one block stays within ``_BLOCK_BYTES``: input and output
are double-buffered, so four blocks must fit the 16 MiB scoped VMEM default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret

__all__ = ["group_prox"]

_BLOCK_BYTES = 2 * 2**20


def _kernel(a_ref, t_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)  # [bg, M] — full groups
    t = t_ref[0]
    norm = jnp.sqrt(jnp.sum(a * a, axis=1, keepdims=True))
    # zero-norm rows (structurally pruned groups, or grid padding) map to
    # exactly 0 — same guard as core.group_lasso.group_prox_rows
    scale = jnp.where(norm > 0.0,
                      jnp.maximum(1.0 - t / jnp.maximum(norm, 1e-12), 0.0),
                      0.0)
    o_ref[...] = (scale * a).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_g", "interpret"))
def group_prox(
    a: jnp.ndarray,
    thresh: jnp.ndarray | float,
    block_g: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Block soft threshold over rows of ``a`` [G, M] with threshold ``thresh``.

    ``G`` need not tile by ``block_g``: extra rows are zero-padded to the next
    block multiple (safe because the zero-norm guard maps zero rows to exactly
    zero) and sliced off the output — the caller sees [G, M] in / [G, M] out
    for any G, which is what ``optim.prox_sgd`` needs for arbitrary layers.
    """
    g, m = a.shape
    rows_fit = max(8, _BLOCK_BYTES // (m * a.dtype.itemsize) // 8 * 8)
    block_g = min(block_g, rows_fit, g)
    g_pad = ((g + block_g - 1) // block_g) * block_g
    ap = jnp.pad(a, ((0, g_pad - g), (0, 0))) if g_pad != g else a
    t = jnp.asarray(thresh, jnp.float32).reshape(1)
    out = pl.pallas_call(
        _kernel,
        grid=(g_pad // block_g,),
        in_specs=[
            pl.BlockSpec((block_g, m), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_g, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g_pad, m), a.dtype),
        interpret=resolve_interpret(interpret),
    )(ap, t)
    return out[:g] if g_pad != g else out
