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
  x    [G, E, X, B_pad] f32        per-group slice inputs, zero-padded
  out  [G, N_pad, B_pad] f32       group g's output, accumulated over its e

Groups are padded to common (E, P, N_pad, S) by
``repro.kernels.ops.pack_group``: missing slices carry sign == 0 everywhere
(they decompress to a zero factor and contribute nothing), short chains are
right-padded with identity factors, and narrow groups ride the shared slab
height X (>= the widest slice, ``first_width``) with zero rows — the same
invariants ``lcc_chain_matmul`` already relies on.

Grid (G, b_blocks, E, P) for the compiled row-gather body, (G, b_blocks, E)
for the interpreter's gather: slices (and factors) innermost, so group g's
output tile stays resident across e and is accumulated in place.  Both bodies
and the launch itself live in ``lcc_chain_matmul``; a chain is the group of
one.  The row-gather body reads the feature-major ``x`` as callers pass it
and writes ``out`` directly, with no transpose on either side.
"""
from __future__ import annotations

import jax.numpy as jnp

from .lcc_chain_matmul import lcc_call

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
    terms=None,
) -> jnp.ndarray:
    """y[G, N_pad, B_pad] = per-group sum_e chain_{g,e}(x[g, e]) — one launch.

    Same contract as :func:`~repro.kernels.lcc_chain_matmul.lcc_chain_matmul`
    per group; ``first_width`` is shared across groups (the max padded slice
    width — narrower groups read zero-padded columns, which contribute 0).
    ``name``: the launch's name in the compiled program and the device trace.
    ``terms``: the group's ``pack_terms`` words, which the compiled
    row-gather body requires (see ``lcc_chain_matmul``).
    """
    return lcc_call(idx, exp, sign, x, block_b=block_b,
                    first_width=first_width, interpret=interpret,
                    use_gather=use_gather, name=name, terms=terms)
