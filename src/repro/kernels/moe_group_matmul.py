"""Pallas TPU kernels: grouped expert matmuls over rows sorted by expert.

A drop-free MoE layer (``models.moe.moe_ffn``) sorts its (token, expert)
rows by expert, so expert ``g`` owns the contiguous rows
``[offsets[g], offsets[g + 1])`` of the sorted activations.  Two launches
then compute every routed row and nothing else:

* ``moe_gate_up``: ``h = silu(x @ gate[g]) * (x @ up[g])`` for each group
  (launch ``_moe_group_matmul_gate_up``), both matrices read in one pass;
* ``moe_down``: ``y = h @ down[g]`` (launch ``_moe_group_matmul_down``),
  float32 out for the gated scatter-add.

The scheme is megablox's grouped matmul (``jax.experimental.pallas.ops.tpu.
megablox.gmm``), kept here so each launch carries its own name in the device
trace: the rows are cut into ``tm``-row tiles, and the grid walks
(output column tile, (row tile, group) visit, contraction tile).  A visit
pairs a row tile with one group that has rows in it, so a tile shared by
groups is visited once per group, consecutively, each visit storing only its
group's rows; groups with no rows get no visit, and their weights are never
read.  Each expert's weights are read once a launch, plus once more for a
group that straddles a row-tile boundary (at most ``M / tm - 1`` of them).
The visit list comes from the group sizes on the device (scalar prefetch), so
one compiled launch serves every routing.

The weights may be a whole stack of layers ``[L, G, K, N]`` with the layer
to read as a (traced) index: the launch then reads that layer's blocks in
place, where slicing the layer out inside a layer scan would first copy all
its experts.

Rows are padded to a multiple of ``tm`` with zeros counted in the last group:
they compute zeros, which the caller drops.  ``interpret=None`` runs compiled
on TPU and in the Pallas interpreter elsewhere (``kernels.dispatch``).

Both launches are differentiable: their VJP is that of the same math on
``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import record_launch, record_moe_launch, resolve_interpret

__all__ = ["moe_gate_up", "moe_down"]

TM = 128  # rows a tile


def _tile(n: int, prefs: tuple[int, ...]) -> int:
    """The first preferred tile size that divides ``n``; else ``n`` whole."""
    for t in prefs:
        if n % t == 0:
            return t
    return n


def group_metadata(group_sizes, m: int, tm: int):
    """(offsets [G+1], group of each visit, row tile of each visit, number of
    visits) for ``m`` rows (a multiple of ``tm``) in groups of
    ``group_sizes`` (summing to ``m``); megablox's ``make_group_metadata``
    with empty groups skipped."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(jnp.int32)
    starts = offsets[:-1]
    # tiles each group touches: from its first row's tile to its last's
    tiles = (ends + tm - 1) // tm - starts // tm
    tiles = jnp.where(group_sizes == 0, 0, tiles)
    tiles_m = m // tm
    n_max = tiles_m + g - 1
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                           total_repeat_length=n_max)
    # a tile is visited once by the group owning its first row, and once
    # more by every other non-empty group that starts inside it
    partial = jnp.where((starts % tm == 0) | (group_sizes == 0), tiles_m,
                        starts // tm)
    visits = jnp.zeros(tiles_m, jnp.int32).at[partial].add(1, mode="drop") + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=n_max)
    return offsets, group_ids, m_tile_ids, tiles.sum().astype(jnp.int32)


def _row_mask(offsets, group_ids, m_tile_ids, v, tm: int, tn: int):
    """Rows of visit ``v``'s tile that belong to its group."""
    gid = group_ids[v]
    rows = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + m_tile_ids[v] * tm
    return (rows >= offsets[gid]) & (rows < offsets[gid + 1])


def _gate_up_kernel(offsets, group_ids, m_tile_ids, _layer, x_ref, g_ref,
                    u_ref, out_ref, acc_g, acc_u, *, tm: int, tn: int,
                    tiles_k: int):
    v, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[...]
    acc_g[...] += jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
    acc_u[...] += jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == tiles_k - 1)
    def _store():
        mask = _row_mask(offsets, group_ids, m_tile_ids, v, tm, tn)
        h = jax.nn.silu(acc_g[...]) * acc_u[...]
        out_ref[...] = jnp.where(mask, h, out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def _down_kernel(offsets, group_ids, m_tile_ids, _layer, h_ref, d_ref,
                 out_ref, acc, *, tm: int, tn: int, tiles_k: int):
    v, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(h_ref[...], d_ref[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k == tiles_k - 1)
    def _store():
        mask = _row_mask(offsets, group_ids, m_tile_ids, v, tm, tn)
        out_ref[...] = jnp.where(mask, acc[...], out_ref[...])


def _launch(kernel, name: str, x, mats, group_sizes, layer, *, n: int,
            tk: int, tn: int, tm: int, out_dtype, n_acc: int,
            interpret: bool):
    """One launch over rows ``x`` [M, K] and weight stacks ``mats``
    [L, G, K, N], reading layer ``layer``."""
    m, kdim = x.shape
    offsets, group_ids, m_tile_ids, n_visits = group_metadata(group_sizes,
                                                              m, tm)
    tiles_k = kdim // tk
    x_spec = pl.BlockSpec((tm, tk), lambda j, v, k, o, g, t, li: (t[v], k))
    w_spec = pl.BlockSpec((None, None, tk, tn),
                          lambda j, v, k, o, g, t, li: (li[0], g[v], k, j))
    return pl.pallas_call(
        functools.partial(kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_visits, tiles_k),
            in_specs=[x_spec] + [w_spec] * len(mats),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, k, o, g, t, li: (t[v], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * n_acc),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(offsets, group_ids, m_tile_ids, layer.reshape(1), x, *mats)


def _pad_rows(x, group_sizes, tm: int):
    """Rows padded to a multiple of ``tm``, the padding counted in the last
    group (zeros in, zeros out)."""
    m = x.shape[0]
    m_pad = -(-m // tm) * tm
    if m_pad == m:
        return x, group_sizes
    x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    return x, group_sizes.at[-1].add(m_pad - m)


def _body(interpret: bool) -> str:
    return "interpret" if interpret else "compiled"


def _gate_up_ref(x, gate, up, group_sizes, layer):
    g = jax.lax.ragged_dot(x, gate[layer], group_sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, up[layer], group_sizes,
                           preferred_element_type=jnp.float32)
    return (jax.nn.silu(g) * u).astype(x.dtype)


def _down_ref(h, down, group_sizes, layer):
    return jax.lax.ragged_dot(h, down[layer], group_sizes,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gate_up(x, gate, up, group_sizes, layer, interpret):
    xp, gs = _pad_rows(x, group_sizes, TM)
    f = gate.shape[-1]
    h = _launch(_gate_up_kernel, "_moe_group_matmul_gate_up", xp, (gate, up),
                gs, layer, n=f, tk=_tile(x.shape[1], (512, 256, 128)),
                tn=_tile(f, (2048, 1536, 1408, 1024, 512, 256, 128)), tm=TM,
                out_dtype=x.dtype, n_acc=2, interpret=interpret)
    return h[:x.shape[0]]


def _gate_up_fwd(x, gate, up, group_sizes, layer, interpret):
    return (_gate_up(x, gate, up, group_sizes, layer, interpret),
            (x, gate, up, group_sizes, layer))


def _gate_up_bwd(interpret, res, ct):
    x, gate, up, group_sizes, layer = res
    _, vjp = jax.vjp(lambda a, b, c: _gate_up_ref(a, b, c, group_sizes,
                                                  layer), x, gate, up)
    return (*vjp(ct), None, None)


_gate_up.defvjp(_gate_up_fwd, _gate_up_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _down(h, down, group_sizes, layer, interpret):
    hp, gs = _pad_rows(h, group_sizes, TM)
    d = down.shape[-1]
    y = _launch(_down_kernel, "_moe_group_matmul_down", hp, (down,), gs,
                layer, n=d,
                tk=_tile(h.shape[1], (1536, 1408, 1024, 512, 256, 128)),
                tn=_tile(d, (1024, 512, 256, 128)), tm=TM,
                out_dtype=jnp.float32, n_acc=1, interpret=interpret)
    return y[:h.shape[0]]


def _down_fwd(h, down, group_sizes, layer, interpret):
    return (_down(h, down, group_sizes, layer, interpret),
            (h, down, group_sizes, layer))


def _down_bwd(interpret, res, ct):
    h, down, group_sizes, layer = res
    _, vjp = jax.vjp(lambda a, b: _down_ref(a, b, group_sizes, layer),
                     h, down)
    return (*vjp(ct), None, None)


_down.defvjp(_down_fwd, _down_bwd)


def _stack(w, layer):
    """Weights as a layer stack [L, G, K, N] and the layer to read."""
    if layer is None:
        return w[None], jnp.zeros((), jnp.int32)
    return w, jnp.asarray(layer, jnp.int32)


def moe_gate_up(x, gate, up, group_sizes, *, layer=None,
                interpret: bool | None = None):
    """``silu(x @ gate[g]) * (x @ up[g])`` for the rows of each group.

    x [M, K] rows sorted by group; gate/up [G, K, F], or [L, G, K, F] read at
    ``layer``; group_sizes [G] int32 summing to M.  Returns [M, F] in x's
    dtype (float32 accumulation)."""
    interpret = resolve_interpret(interpret)
    record_launch()
    record_moe_launch("_moe_group_matmul_gate_up", _body(interpret))
    gate, li = _stack(gate, layer)
    up, _ = _stack(up, layer)
    return _gate_up(x, gate.astype(x.dtype), up.astype(x.dtype),
                    group_sizes.astype(jnp.int32), li, interpret)


def moe_down(h, down, group_sizes, *, layer=None,
             interpret: bool | None = None):
    """``h @ down[g]`` for the rows of each group: h [M, F], down [G, F, D]
    (or [L, G, F, D] read at ``layer``) -> [M, D] float32."""
    interpret = resolve_interpret(interpret)
    record_launch()
    record_moe_launch("_moe_group_matmul_down", _body(interpret))
    down, li = _stack(down, layer)
    return _down(h, down.astype(h.dtype), group_sizes.astype(jnp.int32), li,
                 interpret)
