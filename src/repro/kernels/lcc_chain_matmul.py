"""Pallas TPU kernel: fused whole-chain LCC evaluation  y = sum_e (F_P ... F_1) x_e.

The paper's value proposition is that an LCC factor chain is *cheaper than the
dense matmul it replaces* — but launching one ``pallas_call`` per factor (the
pre-fusion runtime) round-trips every intermediate through HBM, exactly the
memory traffic that dominates compressed-inference cost.  This kernel applies
an entire FP decomposition — every factor of every vertical slice (paper
eq. (3)) — in ONE launch, holding the running vector in VMEM scratch the whole
way; only the inputs' compact term streams and the final output tile touch
HBM.

Packed multi-slice layout (built by ``repro.kernels.ops.pack_decomposition``):

  idx  [E, P, N_pad, S] int32  column index of term s of row n, factor p, slice e
  exp  [E, P, N_pad, S] int8   exponent (power of two)
  sign [E, P, N_pad, S] int8   {-1, 0, +1}; 0 marks an unused slot / padded row
  x    [E, X, B_pad] f32       slice inputs, zero-padded to X >= first_width
  out  [N_pad, B_pad] f32      accumulated over slices e

with ``first_width`` the widest slice (padded): the columns a first factor
can address, and all any body reads of ``x`` (``ops`` passes X =
first_width rounded up to 8 rows).  Chains shorter than P are right-padded with identity factors
(idx[n] = n, sign = [1, 0, ...]); rows beyond a factor's true ``out_dim``
carry sign == 0 everywhere, so they decompress to zero rows and stay exactly
zero through the chain.

Two bodies compute the same sum_s sign * 2^exp * prev[idx] in float32:

* gather (the interpreter's, CPU/GPU, or ``use_gather=True``): S direct reads
  per row over the whole ``[N_pad, S]`` factor at once.
* row gather (compiled Mosaic): the running vector stays feature-major,
  ``[rows, bb]`` — features on sublanes, the batch block on the lanes — so a
  term is one row read by index from VMEM, scaled and added: the work is the
  terms, not N_pad x width.  The grid is (G, b_blocks, E, P), factors
  innermost: each step streams one factor's terms into SMEM as one int32
  word a term (``pack_terms``: the float32 bits of sign * 2^exp with the
  column index in the 23 mantissa bits, which a power of two leaves zero), so
  a term costs one scalar load.  The loop writes ``ROWS`` output rows an
  iteration, unrolled; factors ping-pong between two VMEM buffers, the first
  reads the slice input block, the last adds into the resident output tile.
  The term words are laid out once, on the host, and cached on the packed
  decomposition or group (``ops.PackedDecomposition.terms``,
  ``PackedGroup.terms``); the body reads nothing else of the streams.

One rule of shape, decided at trace time from ``first_width``: a first
factor that reads at most ``DENSE_FIRST_MAX`` slice columns is applied as
dense one-hot tiles ``[columns, rows]`` on the MXU, built on the vector unit
from the same words (held in VMEM as well).  Its tile is as narrow as the
slice, so up to that width it costs less than the gather; every later
factor reads N_pad columns, where a tile's work grows with N_pad x N_pad —
the cost of the one-hot body the row gather replaced (PERF.md, LCC
kernels).  ``lcc_launches_total`` counts each launch under the body chosen
(``dispatch.record_lcc_launch``).

``lcc_group_matmul`` is the same launch over G decompositions; a chain is the
group of one.  ``interpret=None`` auto-detects: compiled on TPU, interpreter
elsewhere (see ``repro.kernels.dispatch``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import record_launch, record_lcc_launch, resolve_interpret

__all__ = ["lcc_chain_matmul", "pack_terms"]

ROWS = 16  # output rows per unrolled iteration of the row-gather body
# A first factor that reads at most this many slice columns runs as dense
# [columns, rows] tiles on the MXU instead.  Timed on a TPU v5e (a launch of
# first factors alone, 8 groups, slices of 16 to 2048 columns over 8192 or
# 2048 inputs, N_pad 2048 and 8192, 32 columns of batch): the tiles cost
# 0.54 ns a term at 16 columns, 0.86 at 128, 2.31 at 512 and 4.3 at 1024,
# the row gather 1.55, 1.86, 2.80 and 4.1 — tiles win up to 512 columns and
# lose from 1024 on (PERF.md, LCC kernels).  Later factors read N_pad
# columns, where a tile's work (columns x rows) is what the row gather
# removed.
DENSE_FIRST_MAX = 512
_IDX_BITS = 23  # a float32 mantissa: room for the column index
_COEF_MASK = -(1 << _IDX_BITS)  # 0xFF800000: sign and exponent of the word
_LANES = 128  # lanes of a vreg, and words of an SMEM tile


def term_rows(n_pad: int) -> int:
    """Rows of a factor in the term stream: N_pad rounded up to a multiple
    of 128, so each term's stream starts on an SMEM tile (the extra rows
    carry no term and stay zero)."""
    return -(-n_pad // _LANES) * _LANES


def pack_terms(idx, exp, sign) -> np.ndarray:
    """Host-side term words of the row-gather body for ``[..., P, N_pad, S]``
    streams: int32 ``[..., P, 1, S * term_rows(N_pad)]``, term-major (term s
    of row n at ``s * rows + n``), each the float32 bits of ``sign * 2^exp``,
    built as integers, or'd with the column index.  An unused slot (sign 0)
    is word 0: row 0 times +0.0."""
    idx, exp, sign = (np.asarray(a) for a in (idx, exp, sign))
    if ((sign != 0) & ((idx >> _IDX_BITS != 0) | (exp < -126))).any():
        raise ValueError("term streams out of the word's range: column index "
                         f"in [0, 2^{_IDX_BITS}), exponent >= -126")
    words = (((exp.astype(np.int32) + 127) << 23)
             | ((sign < 0).astype(np.int32) << 31) | idx.astype(np.int32))
    words = np.where(sign != 0, words, 0)
    *lead, n_pad, s_terms = words.shape
    n_rows = term_rows(n_pad)
    pad = [(0, 0)] * len(lead) + [(0, n_rows - n_pad), (0, 0)]
    words = np.swapaxes(np.pad(words, pad), -1, -2)
    return words.reshape(*lead, 1, s_terms * n_rows)


def _gather_kernel(idx_ref, exp_ref, sign_ref, x_ref, o_ref, cur_ref, *,
                   p_factors: int, s_terms: int, n_pad: int):
    """Interpreter body: direct term gather, S reads per row, no dense tile.
    Grid (G, b_blocks, E): axis 2 walks the decomposition's slices."""
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # slice input: the first factor reads rows < first_width <= X, every
    # later one the N_pad rows its predecessor wrote
    cur_ref[0:x_ref.shape[0], :] = x_ref[...]
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


def _row_gather_kernel(w_ref, *refs, p_factors: int, s_terms: int,
                       n_rows: int, dense_first: bool):
    """Compiled body, one factor a grid step (grid (G, b_blocks, E, P)):
    ``w`` [1, S*n_rows] term words in SMEM (and, with ``dense_first``, the
    first factor's words again in VMEM), ``x`` [xw, bb] the slice input,
    ``o``/``buf0``/``buf1`` [n_rows, bb], all feature-major."""
    w0_ref, x_ref, o_ref, buf0, buf1 = refs if dense_first else (None, *refs)
    e, p = pl.program_id(2), pl.program_id(3)
    # one view per term: the loop then loads word n of view s through the
    # view's base register, one address add a row instead of one a term
    terms = [w_ref.at[0, pl.ds(s * n_rows, n_rows)] for s in range(s_terms)]

    @pl.when((e == 0) & (p == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def apply(src, dst, accumulate: bool):
        bb = dst.shape[-1]

        def rows(i, carry):
            r0 = pl.multiple_of(i * ROWS, ROWS)
            out = []
            for j in range(ROWS):
                acc = None
                for s in range(s_terms):
                    w = terms[s][r0 + j]
                    # the coefficient is decoded on the vector unit: the two
                    # scalar slots, which bound this loop, load the word,
                    # mask the index and form the row's address
                    coef = pltpu.bitcast(
                        jnp.full((1, bb), w, jnp.int32) & _COEF_MASK,
                        jnp.float32)
                    term = coef * src[pl.ds(w & ~_COEF_MASK, 1), :]
                    acc = term if acc is None else acc + term
                out.append(acc)
            tile = jnp.concatenate(out, axis=0)  # one aligned store
            if accumulate:
                dst[pl.ds(r0, ROWS), :] += tile
            else:
                dst[pl.ds(r0, ROWS), :] = tile
            return carry

        jax.lax.fori_loop(0, n_rows // ROWS, rows, 0)

    def apply_first_dense(dst, accumulate: bool):
        """The first factor as dense one-hot tiles ``[xw, rb]`` built from
        its words on the vector unit and applied on the MXU: y^T = x^T T."""
        x = x_ref[...]
        rb = next(r for r in (512, 256, 128) if n_rows % r == 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], rb), 0)

        def block(r, carry):
            r0 = pl.multiple_of(r * rb, rb)
            tile = jnp.zeros(cols.shape, jnp.float32)
            for s in range(s_terms):
                w = w0_ref[:, pl.ds(s * n_rows + r0, rb)]  # [1, rb]
                coef = pltpu.bitcast(w & _COEF_MASK, jnp.float32)
                tile = tile + jnp.where((w & ~_COEF_MASK) == cols, coef, 0.0)
            y = jax.lax.dot_general(
                x, tile, (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32).T  # [rb, bb]
            if accumulate:
                dst[pl.ds(r0, rb), :] += y
            else:
                dst[pl.ds(r0, rb), :] = y
            return carry

        jax.lax.fori_loop(0, n_rows // rb, block, 0)

    # factor q reads what q-1 wrote (the slice input for q == 0) and writes
    # buf0, buf1, buf0, ...; the last adds into the output tile.  One loop
    # is emitted per distinct (source, destination) pair: at most four.
    stages = (x_ref, buf0, buf1, o_ref)
    pairs: dict[tuple[int, int], list[int]] = {}
    for q in range(p_factors):
        src = 0 if q == 0 else 1 + (q - 1) % 2
        dst = 3 if q == p_factors - 1 else 1 + q % 2
        pairs.setdefault((src, dst), []).append(q)
    for (src, dst), qs in pairs.items():
        run = (functools.partial(apply_first_dense, stages[dst], dst == 3)
               if src == 0 and dense_first else
               functools.partial(apply, stages[src], stages[dst], dst == 3))
        pl.when(functools.reduce(jnp.logical_or, [p == q for q in qs]))(run)


def _first_rows(first_width: int | None, x_rows: int) -> int:
    """Rows of the slice input the first factor reads: ``first_width``
    (default X) rounded up to a sublane tile, at most X."""
    width = x_rows if first_width is None else min(first_width, x_rows)
    return min(-(-width // 8) * 8, x_rows)


def _body(interpret: bool, use_gather: bool | None, xw: int) -> str:
    """The body a launch runs: the gather where ``use_gather`` asks for it
    (by default, when interpreting), else the row gather, with its first
    factor as one-hot tiles where it reads at most ``DENSE_FIRST_MAX`` rows
    of the slice input."""
    if interpret if use_gather is None else use_gather:
        return "interpret"
    return "row_gather+dense_first" if xw <= DENSE_FIRST_MAX else "row_gather"


def lcc_call(idx, exp, sign, x, *, block_b, first_width, interpret,
             use_gather, name, terms=None):
    """Count and run one grouped launch (shared by the chain and the group
    wrappers): the un-jitted part, so each trace of a call site counts once."""
    run_interpret = resolve_interpret(interpret)
    xw = _first_rows(first_width, x.shape[2])
    body = _body(run_interpret, use_gather, xw)
    if body == "interpret":
        terms = None  # the gather body reads the streams themselves
    elif terms is None:
        raise ValueError("the row-gather body reads the streams' pack_terms "
                         "words: pass terms")
    record_launch()
    record_lcc_launch(name, body)
    return _lcc_launch(idx, exp, sign, x, terms, block_b=block_b, xw=xw,
                       interpret=run_interpret, body=body, name=name)


@functools.partial(jax.jit, static_argnames=("block_b", "xw", "interpret",
                                             "body", "name"))
def _lcc_launch(idx, exp, sign, x, terms, *, block_b: int, xw: int,
                interpret: bool, body: str, name: str) -> jnp.ndarray:
    """y[G, N_pad, B_pad] over idx [G, E, P, N_pad, S], x [G, E, X, B_pad];
    the first factor reads rows [0, xw) of x."""
    g_groups, e_slices, p_factors, n_pad, s_terms = idx.shape
    xg, xe, x_rows, b_pad = x.shape
    if (xg, xe) != (g_groups, e_slices):
        raise ValueError(f"group/slice mismatch: idx has {(g_groups, e_slices)},"
                         f" x has {(xg, xe)}")
    block_b = min(block_b, b_pad)
    if b_pad % block_b:
        raise ValueError(f"B_pad={b_pad} must tile by block_b={block_b}")
    x = x.astype(jnp.float32)
    if body == "interpret":
        spec = pl.BlockSpec((None, None, p_factors, n_pad, s_terms),
                            lambda g, b, e: (g, e, 0, 0, 0))
        return pl.pallas_call(
            functools.partial(_gather_kernel, p_factors=p_factors,
                              s_terms=s_terms, n_pad=n_pad),
            grid=(g_groups, b_pad // block_b, e_slices),
            in_specs=[spec, spec, spec,
                      pl.BlockSpec((None, None, x_rows, block_b),
                                   lambda g, b, e: (g, e, 0, b))],
            out_specs=pl.BlockSpec((None, n_pad, block_b),
                                   lambda g, b, e: (g, 0, b)),
            out_shape=jax.ShapeDtypeStruct((g_groups, n_pad, b_pad),
                                           jnp.float32),
            scratch_shapes=[pltpu.VMEM((max(x_rows, n_pad), block_b),
                                       jnp.float32)],
            interpret=interpret,
            name=name,
        )(idx, exp, sign, x)
    n_rows = term_rows(n_pad)
    if terms.shape != (g_groups, e_slices, p_factors, 1, s_terms * n_rows):
        raise ValueError(f"terms {terms.shape} do not match streams {idx.shape}")
    dense_first = body == "row_gather+dense_first"
    lanes = -(-block_b // _LANES) * _LANES  # VMEM pads the lanes
    vmem = 4 * lanes * (4 * n_rows + 2 * xw) + (4 << 20)
    if dense_first:  # the first factor's words, sublane-padded, twice
        vmem += 2 * 8 * 4 * s_terms * n_rows
    words = pl.BlockSpec((None, None, None, 1, s_terms * n_rows),
                         lambda g, b, e, p: (g, e, p, 0, 0),
                         memory_space=pltpu.SMEM)
    first = pl.BlockSpec((None, None, None, 1, s_terms * n_rows),
                         lambda g, b, e, p: (g, e, 0, 0, 0))
    y = pl.pallas_call(
        functools.partial(_row_gather_kernel, p_factors=p_factors,
                          s_terms=s_terms, n_rows=n_rows,
                          dense_first=dense_first),
        grid=(g_groups, b_pad // block_b, e_slices, p_factors),
        in_specs=[
            words, *([first] if dense_first else []),
            # the first factor reads only the slice's columns: xw rows of x
            pl.BlockSpec((None, None, xw, block_b),
                         lambda g, b, e, p: (g, e, 0, b)),
        ],
        out_specs=pl.BlockSpec((None, n_rows, block_b),
                               lambda g, b, e, p: (g, 0, b)),
        out_shape=jax.ShapeDtypeStruct((g_groups, n_rows, b_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_rows, block_b), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(terms, *([terms] if dense_first else []), x)
    return y if n_rows == n_pad else y[:, :n_pad]


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
    terms=None,
) -> jnp.ndarray:
    """y[N_pad, B_pad] = sum_e chain_e(x[e]) — whole decomposition, one launch.

    ``first_width``: padded max slice width (columns the first factor of any
    chain can address); defaults to X, the rows of ``x``, of which the
    bodies read only that many.
    ``use_gather``: force the interpreter's gather body (default: when
    interpreting); ``False`` runs the compiled row-gather body, also under the
    interpreter, so tests cover it on a CPU.
    ``name``: the launch's name in the compiled program and the device trace
    (the serving executor names each by its site group).
    ``terms``: the streams' :func:`pack_terms` words, laid out once on the
    host; the compiled row-gather body reads them and requires them, the
    interpreter's gather ignores them.
    """
    y = lcc_call(idx[None], exp[None], sign[None], x[None], block_b=block_b,
                 first_width=first_width, interpret=interpret,
                 use_gather=use_gather, name=name,
                 terms=None if terms is None else terms[None])
    return y[0]
