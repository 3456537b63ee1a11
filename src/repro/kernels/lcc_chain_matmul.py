"""Pallas TPU kernel: fused whole-chain LCC evaluation  y = sum_e (F_P ... F_1) x_e.

The paper's value proposition is that an LCC factor chain is *cheaper than the
dense matmul it replaces* — but launching one ``pallas_call`` per factor (the
pre-fusion runtime) round-trips every intermediate through HBM, exactly the
memory traffic that dominates compressed-inference cost.  This kernel applies
an entire FP decomposition — every factor of every vertical slice (paper
eq. (3)) — in ONE launch, holding the running vector in VMEM scratch the whole
way; only the inputs' compact (idx, exp, sign) streams and the final output
tile touch HBM.

Packed multi-slice layout (built by ``repro.kernels.ops.pack_decomposition``):

  idx  [E, P, N_pad, S] int32  column index of term s of row n, factor p, slice e
  exp  [E, P, N_pad, S] int8   exponent (power of two)
  sign [E, P, N_pad, S] int8   {-1, 0, +1}; 0 marks an unused slot / padded row
  x    [E, D_pad, B_pad] f32   slice inputs, zero-padded rows
  out  [N_pad, B_pad] f32      accumulated over slices e

with ``D_pad = max(N_pad, max slice width, padded)`` the width of the running
vector carried in scratch.  Chains shorter than P are right-padded with
identity factors (idx[n] = n, sign = [1, 0, ...]); rows beyond a factor's true
``out_dim`` carry sign == 0 everywhere, so they decompress to zero rows and
stay exactly zero through the chain.

Grid (b_blocks, E): slices are the fastest axis, so the output tile for a
given b block is revisited across e and accumulated in place (same revisit
pattern as the contraction axis of ``lcc_matmul``).  Both formulations compute
the identical sum_s sign * 2^exp * prev[idx]:

* gather (the interpreter's, CPU/GPU): S direct reads per row.
* one-hot (compiled Mosaic): the running vector is kept batch-major
  ``[B, D]`` so features sit on the 128 lanes, and the term streams are
  re-laid out as ``[E, P*S, N_pad]`` (rows on lanes) — the native
  ``[.., N_pad, S]`` layout would pad S = 2 to 128 lanes in VMEM.  Each factor
  is decompressed one ``[col_block, row_block]`` tile at a time inside two
  ``fori_loop``s and fed to the MXU, writing a second scratch buffer, so VMEM
  holds a bounded tile instead of a dense ``[N_pad, width]`` factor (256 MiB
  at N_pad = 8192).

``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere (see
``repro.kernels.dispatch``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import record_launch, resolve_interpret

__all__ = ["lcc_chain_matmul"]

# decompress tile [COL_BLOCK, ROW_BLOCK] f32 = 512 KiB of VMEM
ROW_BLOCK = 256
COL_BLOCK = 512
_LANES = 128


def _gather_kernel(idx_ref, exp_ref, sign_ref, x_ref, o_ref, cur_ref, *,
                   p_factors: int, s_terms: int, n_pad: int, slice_axis: int):
    """Interpreter body: direct term gather, S reads per row, no dense tile.
    ``slice_axis`` names the grid axis that walks the decomposition's slices
    (1 here, 2 for the grouped launch of ``lcc_group_matmul``)."""
    e = pl.program_id(slice_axis)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    cur_ref[...] = x_ref[...]  # [D_pad, bb] slice input, zero-padded rows
    for p in range(p_factors):
        idx = idx_ref[p]  # [N_pad, S]
        val = sign_ref[p].astype(jnp.float32) * \
            jnp.exp2(exp_ref[p].astype(jnp.float32))  # 2^e exact in f32
        g = cur_ref[...][idx.reshape(-1)]  # [N_pad*S, bb]
        y = jnp.sum(val.reshape(n_pad, s_terms, 1)
                    * g.reshape(n_pad, s_terms, -1), axis=1)
        # only rows [0, N_pad) are read by later factors (factor 0 alone
        # spans the slice width), so rows past N_pad need no reset
        cur_ref[0:n_pad, :] = y  # intermediate stays resident in VMEM
    o_ref[...] += cur_ref[0:n_pad, :]


def _block(n: int, pref: int) -> int:
    """Largest lane-aligned block <= ``pref`` that tiles ``n`` (``n`` itself
    when it is not a lane multiple — then it is a full, small dimension)."""
    if n % _LANES:
        return n
    b = max(_LANES, pref - pref % _LANES)
    while n % b:
        b -= _LANES
    return b


def _onehot_kernel(idx_ref, coef_ref, x_ref, o_ref, cur_ref, nxt_ref, *,
                   p_factors: int, s_terms: int, n_pad: int, first_width: int,
                   d_pad: int, slice_axis: int):
    """Compiled body over the batch-major layout: ``x``/``cur`` [bb, D_pad],
    ``idx``/``coef`` [P*S, N_pad], ``o``/``nxt`` [bb, N_pad]."""
    e = pl.program_id(slice_axis)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    cur_ref[...] = x_ref[...]
    bb = cur_ref.shape[0]
    rb = _block(n_pad, ROW_BLOCK)
    for p in range(p_factors):
        # factor p reads only the live prefix of the running vector: the slice
        # width for p == 0 (lane-rounded; the extra columns are zero input and
        # no term addresses them), the previous factor's rows afterwards
        width = (min(-(-first_width // _LANES) * _LANES, d_pad) if p == 0
                 else n_pad)
        cb = _block(width, COL_BLOCK)
        base = p * s_terms

        def row_body(r, carry, base=base, width=width, cb=cb):
            r0 = pl.multiple_of(r * rb, rb)
            idx = [idx_ref[pl.ds(base + s, 1), pl.ds(r0, rb)]
                   for s in range(s_terms)]  # [1, rb] each
            val = [coef_ref[pl.ds(base + s, 1), pl.ds(r0, rb)]
                   for s in range(s_terms)]

            def col_body(c, acc):
                c0 = pl.multiple_of(c * cb, cb)
                cols = jax.lax.broadcasted_iota(jnp.int32, (cb, rb), 0) + c0
                tile = jnp.zeros((cb, rb), jnp.float32)
                for s in range(s_terms):
                    tile = tile + jnp.where(idx[s] == cols, val[s], 0.0)
                return acc + jnp.dot(cur_ref[:, pl.ds(c0, cb)], tile,
                                     precision=jax.lax.Precision.HIGHEST,
                                     preferred_element_type=jnp.float32)

            acc = jax.lax.fori_loop(0, width // cb, col_body,
                                    jnp.zeros((bb, rb), jnp.float32))
            nxt_ref[:, pl.ds(r0, rb)] = acc
            return carry

        jax.lax.fori_loop(0, n_pad // rb, row_body, 0)
        cur_ref[:, 0:n_pad] = nxt_ref[...]  # intermediate stays in VMEM
    o_ref[...] += cur_ref[:, 0:n_pad]


def _onehot_operands(idx, exp, sign, x, block_b: int):
    """Re-lay the packed streams for the compiled body: ``idx``/``coef``
    ``[..., P*S, N_pad]`` (coef = sign * 2^exp, exact in f32) and ``x``
    batch-major ``[..., B_pad8, D_pad]`` with the batch padded to a sublane
    multiple.  Returns (idx_t, coef_t, x_t, block_b, b_pad)."""
    lead = idx.shape[:-3]
    p_factors, n_pad, s_terms = idx.shape[-3:]
    coef = sign.astype(jnp.float32) * jnp.exp2(exp.astype(jnp.float32))
    perm = tuple(range(len(lead))) + tuple(len(lead) + i for i in (0, 2, 1))
    shape = lead + (p_factors * s_terms, n_pad)
    idx_t = jnp.transpose(idx, perm).reshape(shape)
    coef_t = jnp.transpose(coef, perm).reshape(shape)
    b_pad = x.shape[-1]
    bb = -(-min(block_b, b_pad) // 8) * 8
    b_pad = -(-b_pad // bb) * bb
    pad = [(0, 0)] * (x.ndim - 1) + [(0, b_pad - x.shape[-1])]
    x_t = jnp.swapaxes(jnp.pad(x.astype(jnp.float32), pad), -1, -2)
    return idx_t, coef_t, x_t, bb, b_pad


def lcc_chain_matmul(
    idx: jnp.ndarray,
    exp: jnp.ndarray,
    sign: jnp.ndarray,
    x: jnp.ndarray,
    block_b: int = 128,
    first_width: int | None = None,
    interpret: bool | None = None,
    use_gather: bool | None = None,
    name: str = "_lcc_chain_matmul",
) -> jnp.ndarray:
    """y[N_pad, B_pad] = sum_e chain_e(x[e]) — whole decomposition, one launch.

    ``first_width``: padded max slice width (columns the first factor of any
    chain can address); defaults to D_pad.  Tightening it shrinks the first
    factor's decompress span from D_pad to first_width columns.
    ``use_gather``: force the decompress formulation (default: gather when
    interpreting, one-hot/MXU when compiled); exposed so the compiled
    formulation stays testable under the interpreter.
    ``name``: the launch's name in the compiled program and the device trace
    (the serving executor names each by its site group).
    """
    record_launch()  # un-jitted: counts once per pallas_call a trace emits
    return _lcc_chain_matmul(idx, exp, sign, x, block_b=block_b,
                             first_width=first_width, interpret=interpret,
                             use_gather=use_gather, name=name)


@functools.partial(jax.jit, static_argnames=("block_b", "first_width",
                                             "interpret", "use_gather",
                                             "name"))
def _lcc_chain_matmul(
    idx: jnp.ndarray,
    exp: jnp.ndarray,
    sign: jnp.ndarray,
    x: jnp.ndarray,
    block_b: int = 128,
    first_width: int | None = None,
    interpret: bool | None = None,
    use_gather: bool | None = None,
    name: str = "_lcc_chain_matmul",
) -> jnp.ndarray:
    e_slices, p_factors, n_pad, s_terms = idx.shape
    xe, d_pad, b_pad = x.shape
    if xe != e_slices:
        raise ValueError(f"slice count mismatch: idx has {e_slices}, x has {xe}")
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
        spec = pl.BlockSpec((None, p_factors, n_pad, s_terms),
                            lambda b, e: (e, 0, 0, 0))
        return pl.pallas_call(
            functools.partial(_gather_kernel, p_factors=p_factors,
                              s_terms=s_terms, n_pad=n_pad, slice_axis=1),
            grid=(b_pad // block_b, e_slices),
            in_specs=[spec, spec, spec,
                      pl.BlockSpec((None, d_pad, block_b),
                                   lambda b, e: (e, 0, b))],
            out_specs=pl.BlockSpec((n_pad, block_b), lambda b, e: (0, b)),
            out_shape=jax.ShapeDtypeStruct((n_pad, b_pad), jnp.float32),
            scratch_shapes=[pltpu.VMEM((d_pad, block_b), jnp.float32)],
            interpret=run_interpret,
            name=name,
        )(idx, exp, sign, x.astype(jnp.float32))
    idx_t, coef_t, x_t, bb, bt_pad = _onehot_operands(idx, exp, sign, x,
                                                     block_b)
    ps = p_factors * s_terms
    y = pl.pallas_call(
        functools.partial(_onehot_kernel, p_factors=p_factors,
                          s_terms=s_terms, n_pad=n_pad,
                          first_width=first_width, d_pad=d_pad, slice_axis=1),
        grid=(bt_pad // bb, e_slices),
        in_specs=[
            pl.BlockSpec((None, ps, n_pad), lambda b, e: (e, 0, 0)),
            pl.BlockSpec((None, ps, n_pad), lambda b, e: (e, 0, 0)),
            pl.BlockSpec((None, bb, d_pad), lambda b, e: (e, b, 0)),
        ],
        out_specs=pl.BlockSpec((bb, n_pad), lambda b, e: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((bt_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, d_pad), jnp.float32),
                        pltpu.VMEM((bb, n_pad), jnp.float32)],
        interpret=run_interpret,
        name=name,
    )(idx_t, coef_t, x_t)
    return y[:b_pad].T
