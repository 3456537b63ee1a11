"""Sharding policy: derive a NamedSharding for every parameter / activation /
cache tensor from its pytree path and shape, with divisibility-checked
fallbacks so every assigned arch compiles on the fixed production mesh.

Strategy (DESIGN.md Sec. 5):
  * parameters: FSDP/ZeRO-3 storage — the largest dim divisible by |model|
    goes to "model"; then the largest remaining dim divisible by |data| goes
    to "data".  XLA re-gathers per-layer slices inside the layer scan, which
    is exactly the FSDP communication schedule.
  * MoE expert stacks: expert dim on "model" when divisible (EP), else the ff
    dim (TP-within-expert).
  * batch axes of inputs/activations/caches: ("pod", "data") when divisible,
    "data" when not, replicated as last resort; for batch-1 long-context the
    sequence axis takes "data" (sequence parallelism).
  * optimizer state mirrors parameter sharding (ZeRO-1/2 for free).

Everything returns PartitionSpec; mesh binding happens at the jit boundary.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["param_pspec", "params_pspecs", "batch_pspecs", "decode_state_pspecs",
           "named", "mesh_axis_size", "plan_batch_spec", "init_sharded"]


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def _assign_axes(shape: tuple[int, ...], skip: set[int], mesh: Mesh,
                 want_data: bool = True) -> list:
    """Greedy: biggest dim % model == 0 -> 'model'; biggest remaining % data -> 'data'."""
    spec: list = [None] * len(shape)
    msize = mesh_axis_size(mesh, "model")
    dsize = mesh_axis_size(mesh, "data")
    order = sorted((i for i in range(len(shape)) if i not in skip),
                   key=lambda i: -shape[i])
    mi = next((i for i in order if shape[i] % msize == 0 and shape[i] >= msize), None)
    if mi is not None:
        spec[mi] = "model"
    if want_data:
        di = next((i for i in order if i != mi and shape[i] % dsize == 0
                   and shape[i] >= dsize), None)
        if di is not None:
            spec[di] = "data"
    return spec


def param_pspec(path: str, shape: tuple[int, ...], mesh: Mesh, *,
                fsdp: bool = True) -> P:
    """PartitionSpec for one parameter given its flattened path name."""
    if len(shape) <= 1:
        return P()  # norms / biases / small vectors: replicated
    skip: set[int] = set()
    # stacked-layer leading axis is never sharded (scan slices it)
    if any(k in path for k in ("blocks", "enc_blocks", "dec_blocks")):
        skip.add(0)
    if ("gate" in path or "up" in path or "down" in path) and len(shape) - len(skip) == 3:
        # MoE expert stack [L?, E, d, f]: prefer EP on the expert dim
        e_ax = min(i for i in range(len(shape)) if i not in skip)
        msize = mesh_axis_size(mesh, "model")
        if shape[e_ax] % msize == 0 and shape[e_ax] >= msize:
            spec = [None] * len(shape)
            spec[e_ax] = "model"
            if fsdp:
                rest = sorted((i for i in range(len(shape)) if i != e_ax and i not in skip),
                              key=lambda i: -shape[i])
                dsize = mesh_axis_size(mesh, "data")
                di = next((i for i in rest if shape[i] % dsize == 0), None)
                if di is not None:
                    spec[di] = "data"
            return P(*spec)
        skip.add(e_ax)  # TP-within-expert below
    return P(*_assign_axes(shape, skip, mesh, want_data=fsdp))


def params_pspecs(params_tree: Any, mesh: Mesh, *, fsdp: bool = True):
    """Map a (possibly abstract) params pytree -> pytree of PartitionSpec."""
    paths_leaves = jax.tree_util.tree_flatten_with_path(params_tree)[0]
    flat = []
    for path, leaf in paths_leaves:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        flat.append(param_pspec(name, tuple(leaf.shape), mesh, fsdp=fsdp))
    treedef = jax.tree_util.tree_structure(params_tree)
    return jax.tree_util.tree_unflatten(treedef, flat)


def _batch_axes(mesh: Mesh) -> tuple[str, ...] | str | None:
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def plan_batch_spec(mesh: Mesh, b: int):
    """Mesh axis name(s) to split a layer plan's batch/slot axis over, or
    None (replicate).  Mirrors :func:`decode_state_pspecs`'s slot rule —
    ("pod","data") when the slot count divides the full extent, "data" alone
    when only that divides — so the plan's ``shard_map`` sees the same local
    slot partition the surrounding jitted step gives the KV cache."""
    baxes = _batch_axes(mesh)
    if baxes is None:
        return None
    bsize = int(np.prod([mesh_axis_size(mesh, a) for a in ("pod", "data")]))
    dsize = mesh_axis_size(mesh, "data")
    if bsize > 1 and b % bsize == 0 and b >= bsize:
        return baxes
    if dsize > 1 and b % dsize == 0 and b >= dsize:
        return "data"
    return None


def batch_pspecs(batch_tree: Any, mesh: Mesh):
    """Inputs: batch-major sharding over ("pod","data"); batch-1 long-context
    shards the sequence axis instead (SP)."""
    baxes = _batch_axes(mesh)
    bsize = int(np.prod([mesh_axis_size(mesh, a) for a in ("pod", "data")]))
    dsize = mesh_axis_size(mesh, "data")

    def one(leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        spec: list = [None] * len(shape)
        # positions3 [3, B, S] style: batch is axis 1
        b_ax = 1 if (len(shape) >= 2 and shape[0] == 3) else 0
        if shape[b_ax] % bsize == 0 and shape[b_ax] >= bsize:
            spec[b_ax] = baxes
        elif shape[b_ax] % dsize == 0 and shape[b_ax] >= dsize:
            spec[b_ax] = "data"
        elif len(shape) > b_ax + 1 and shape[b_ax + 1] % dsize == 0:
            spec[b_ax + 1] = "data"  # SP fallback (e.g. long_500k batch=1)
        return P(*spec)

    return jax.tree_util.tree_map(one, batch_tree)


def decode_state_pspecs(state_tree: Any, mesh: Mesh):
    """KV caches / SSM states: batch (serving: slot) axis over ("pod","data")
    when divisible, head/feature dims over "model"; layer-stack leading axis
    skipped.

    The "model" pick prefers trailing head/feature axes (axis >= 3) over the
    sequence axis (axis 2): head-parallel attention keeps the per-shard cache
    contiguous in time, while a time-sharded cache forces a collective on
    every decode-step append.  Integer leaves (kpos-style position maps) stay
    replicated beyond the batch axis — they are tiny and feed mask math on
    every shard.

    Paged pools keep the same rule by construction: their layout is
    [L, n_blocks, bs, ...], so axis 1 — the pool axis, padded to a multiple
    of 8 — shards over ("pod","data") exactly the way slots do.  The shared
    ``block_tbl`` [B, view_blocks] is the one path-keyed exception: every
    shard's gather needs the full table, so it is replicated."""
    baxes = _batch_axes(mesh)
    bsize = int(np.prod([mesh_axis_size(mesh, a) for a in ("pod", "data")]))
    dsize = mesh_axis_size(mesh, "data")
    msize = mesh_axis_size(mesh, "model")

    def one(name, leaf):
        shape = tuple(leaf.shape)
        if "block_tbl" in name or len(shape) <= 1:
            return P()
        spec: list = [None] * len(shape)
        b_ax = 1  # [L, B, ...] / paged [L, Nb, ...] layout everywhere
        if shape[b_ax] % bsize == 0 and shape[b_ax] >= bsize:
            spec[b_ax] = baxes
        elif shape[b_ax] % dsize == 0 and shape[b_ax] >= dsize:
            spec[b_ax] = "data"
        if np.issubdtype(np.dtype(leaf.dtype), np.integer):
            return P(*spec)
        order = (sorted(range(3, len(shape)), key=lambda i: -shape[i])
                 + ([2] if len(shape) > 2 else []))
        mi = next((i for i in order if shape[i] % msize == 0 and shape[i] >= msize), None)
        if mi is not None:
            spec[mi] = "model"
        return P(*spec)

    paths_leaves = jax.tree_util.tree_flatten_with_path(state_tree)[0]
    flat = [one("/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path),
                leaf) for path, leaf in paths_leaves]
    treedef = jax.tree_util.tree_structure(state_tree)
    return jax.tree_util.tree_unflatten(treedef, flat)


def named(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), spec_tree,
                                  is_leaf=lambda x: isinstance(x, P))


def init_sharded(init_fn, mesh: Mesh, pspecs_fn):
    """Run the initializer ``init_fn()`` already partitioned: jit it with
    ``out_shardings`` from ``pspecs_fn(abstract_tree, mesh)`` (e.g.
    :func:`params_pspecs`, :func:`decode_state_pspecs`), so every device
    materialises only its shard — a model larger than one device never
    lands whole on the first one."""
    abstract = jax.eval_shape(init_fn)
    return jax.jit(init_fn,
                   out_shardings=named(mesh, pspecs_fn(abstract, mesh)))()
