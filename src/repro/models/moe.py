"""Mixture-of-experts FFN, drop-free by construction.

The router (softmax over the experts, greedy top-k) gives each token its k
experts.  The (token, expert) rows are sorted by expert, with per-expert
group sizes, and two grouped launches (``kernels.moe_group_matmul``) compute
gate/up/SwiGLU and down for exactly the routed rows, reading each expert's
weights once.  The rows, weighted by their gates, are scatter-added back to
their tokens.  No row is ever dropped, so a token's output does not depend on
which other tokens share its batch; prefill and decode run the same routing.

The compressed path (``executor``/``site_tag``) keeps an ``[E, T, d]`` buffer
that each expert's LCC chains read in one grouped launch: with capacity T
every row fits (a token picks an expert at most once), so it is exact too.

Shared (always-on) experts are folded into one SwiGLU with concatenated ff
(mathematically identical: the down projection is linear).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.distributed.act_shard import constrain, get_mesh
from repro.kernels.moe_group_matmul import moe_down, moe_gate_up

from .layers import dense_init, site_linear, site_linear_group, swiglu

__all__ = ["init_moe", "moe_ffn", "moe_ffn_manual", "route", "ExpertStack"]


class ExpertStack(NamedTuple):
    """One layer's routed-expert matrix as a view into the stacked
    ``[L, E, in, out]`` weights of all layers: the grouped launches read layer
    ``layer`` in place (a layer scan that sliced it out would copy every
    expert of the layer first)."""
    stack: jax.Array
    layer: jax.Array

    def get(self):
        return self.stack[self.layer]


def _stack_and_layer(w):
    return (w.stack, w.layer) if isinstance(w, ExpertStack) else (w, None)


def init_moe(key, d_model: int, d_ff: int, n_experts: int, n_shared: int, dtype):
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d_model).astype(jnp.float32)
    p = {
        "router": (jax.random.truncated_normal(ks[0], -2, 2, (d_model, n_experts)) * scale
                   ).astype(jnp.float32),  # router stays f32 for stable top-k
        "gate": (jax.random.truncated_normal(ks[1], -2, 2, (n_experts, d_model, d_ff)) * scale).astype(dtype),
        "up": (jax.random.truncated_normal(ks[2], -2, 2, (n_experts, d_model, d_ff)) * scale).astype(dtype),
        "down": (jax.random.truncated_normal(ks[3], -2, 2, (n_experts, d_ff, d_model))
                 * (1.0 / jnp.sqrt(d_ff).astype(jnp.float32))).astype(dtype),
    }
    if n_shared > 0:
        p["shared"] = {
            "gate": dense_init(jax.random.fold_in(ks[4], 0), d_model, n_shared * d_ff, dtype),
            "up": dense_init(jax.random.fold_in(ks[4], 1), d_model, n_shared * d_ff, dtype),
            "down": dense_init(jax.random.fold_in(ks[4], 2), n_shared * d_ff, d_model, dtype),
        }
    return p


def route(router, xt, *, top_k: int, norm_topk: bool):
    """Softmax router, greedy top-k: (probs [T, E], gates [T, k] f32,
    experts [T, k])."""
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ router, axis=-1)
    gates, sel = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, sel


def _routed_grouped(p, xt, gates, sel, n_experts: int):
    """Routed experts over rows sorted by expert: [T, d] f32."""
    t, d = xt.shape
    flat = sel.reshape(-1)  # [T*k], row r is token r // k
    order = jnp.argsort(flat, stable=True)
    tok = order // sel.shape[1]
    sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    gate, li = _stack_and_layer(p["gate"])
    up, _ = _stack_and_layer(p["up"])
    down, _ = _stack_and_layer(p["down"])
    h = moe_gate_up(jnp.take(xt, tok, axis=0), gate, up, sizes, layer=li)
    out = moe_down(h, down, sizes, layer=li)  # [T*k, d] f32
    w = jnp.take(gates.reshape(-1), order)[:, None]
    return jnp.zeros((t, d), jnp.float32).at[tok].add(out * w)


def _routed_buffer(p, xt, gates, sel, n_experts: int, executor, site_tag):
    """Routed experts through the executor's grouped LCC launches over an
    [E, T, d] buffer (capacity T: exact): [T, d] in xt's dtype."""
    t, d = xt.shape
    top_k = sel.shape[1]
    # rank of each (token, slot) within its expert's rows
    sel_oh = jax.nn.one_hot(sel, n_experts, dtype=jnp.int32)  # [T, k, E]
    flat_oh = sel_oh.reshape(t * top_k, n_experts)
    ranks = (jnp.cumsum(flat_oh, axis=0) - flat_oh).reshape(t, top_k, n_experts)
    slot = sel * t + jnp.sum(ranks * sel_oh, axis=-1)  # [T, k] into E*T
    buf = jnp.zeros((n_experts * t, d), xt.dtype)
    for j in range(top_k):
        buf = buf.at[slot[:, j]].set(xt)
    buf = buf.reshape(n_experts, t, d)

    def expert_mm(proj, z):
        """z [E, T, d_in] @ p[proj] -> [E, T, d_out]: ONE grouped fused
        launch over all experts when the executor covers every
        ``moe.{proj}.{site_tag}.e{e}`` site, dense batched einsum otherwise."""
        fused = executor.grouped(tuple(
            f"moe.{proj}.{site_tag}.e{e}" for e in range(n_experts)))
        if fused is None:
            w = p[proj]
            w = w.get() if isinstance(w, ExpertStack) else w
            return jnp.einsum("ecd,edf->ecf", z, w)
        ys = fused([z[e].astype(jnp.float32).T for e in range(n_experts)])
        return jnp.stack([y.T for y in ys]).astype(z.dtype)

    h = jax.nn.silu(expert_mm("gate", buf)) * expert_mm("up", buf)
    out_buf = expert_mm("down", h).reshape(n_experts * t, d)
    y = jnp.zeros((t, d), xt.dtype)
    for j in range(top_k):
        y = y + (gates[:, j].astype(xt.dtype)[:, None]
                 * jnp.take(out_buf, slot[:, j], axis=0))
    return y


def moe_ffn(p, x, *, n_experts: int, top_k: int, norm_topk: bool = True,
            executor=None, site_tag: str | None = None):
    """x [B, S, d] -> (y [B, S, d], aux dict with router stats).

    ``executor``/``site_tag`` (compressed serving): each projection's
    per-expert matmuls run as ONE grouped fused launch over all experts
    (sites ``moe.{proj}.{site_tag}.e{e}``), and shared experts route through
    their own sites (``moe.shared.{proj}.{site_tag}``).  The routing is the
    same.
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    probs, gates, sel = route(p["router"], xt, top_k=top_k,
                              norm_topk=norm_topk)
    if executor is not None and site_tag is not None:
        y = _routed_buffer(p, xt, gates, sel, n_experts, executor, site_tag)
    else:
        y = _routed_grouped(p, xt, gates, sel, n_experts).astype(x.dtype)
    y = constrain(y, "batch", None)
    if "shared" in p:
        if executor is not None and site_tag is not None:
            sp = p["shared"]
            sg, su = site_linear_group(
                executor, (f"moe.shared.gate.{site_tag}",
                           f"moe.shared.up.{site_tag}"),
                (sp["gate"], sp["up"]), xt)
            # identical TP annotations to the dense-path swiglu
            sg = constrain(sg, "batch", None, "model")
            su = constrain(su, "batch", None, "model")
            y = y + constrain(
                site_linear(executor, f"moe.shared.down.{site_tag}",
                            sp["down"], jax.nn.silu(sg) * su),
                "batch", None, None)
        else:
            y = y + swiglu(p["shared"], xt)

    aux = {"router_probs_mean": probs.mean(0), "sel": sel}
    return y.reshape(b, s, d), aux


def moe_ffn_manual(p, x, *, n_experts: int, top_k: int,
                   norm_topk: bool = True, mesh=None):
    """MoE block as a fully-manual shard_map: local dispatch, EP or
    TP-within-expert compute, one psum over "model" for combine.  Each
    shard's buffer holds every local token for every expert (capacity T_loc),
    so no row is dropped.

    Rationale (measured, EXPERIMENTS.md §Perf iteration 3): under pure GSPMD
    the dispatch scatter/gather cannot be partitioned along tokens, so XLA
    replicates the [E*C, d] buffer and all-reduces hundreds of GB per layer.
    Making dispatch local to each (pod, data) shard removes those collectives;
    the surviving communication is the combine psum over "model" (plus the
    FSDP weight gathers at the shard_map boundary).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        mesh = get_mesh()
    b, s, d = x.shape
    t = b * s
    if mesh is None:
        return moe_ffn(p, x, n_experts=n_experts, top_k=top_k,
                       norm_topk=norm_topk)
    msize = mesh.shape.get("model", 1)
    token_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    tshard = int(np.prod([mesh.shape[a] for a in token_axes])) if token_axes else 1
    if t % max(tshard, 1):
        return moe_ffn(p, x, n_experts=n_experts, top_k=top_k,
                       norm_topk=norm_topk)
    ep = n_experts % msize == 0 and n_experts >= msize
    e_loc = n_experts // msize if ep else n_experts

    def route_local(xt, router):
        tl = xt.shape[0]
        _probs, gates, sel = route(router, xt, top_k=top_k,
                                   norm_topk=norm_topk)
        sel_oh = jax.nn.one_hot(sel, n_experts, dtype=jnp.int32)
        flat_oh = sel_oh.reshape(tl * top_k, n_experts)
        ranks = (jnp.cumsum(flat_oh, axis=0) - flat_oh).reshape(tl, top_k, n_experts)
        rank = jnp.sum(ranks * sel_oh, axis=-1)
        return gates, sel, rank, tl

    def body(xt, router, gate, up, down, sg, su, sd):
        # xt [T_loc, d]; weight args are this shard's slices (EP: expert
        # slice; TP: d_ff slice). Local except the final psum over "model".
        gates, sel, rank, cap = route_local(xt, router)
        slot = sel * cap + rank
        buf = jnp.zeros((n_experts * cap, d), xt.dtype)
        for j in range(top_k):
            buf = buf.at[slot[:, j]].set(xt)
        buf = buf.reshape(n_experts, cap, d)

        if ep:
            midx = jax.lax.axis_index("model")
            my = jax.lax.dynamic_slice_in_dim(buf, midx * e_loc, e_loc, 0)
        else:
            my = buf
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", my, gate)) * \
            jnp.einsum("ecd,edf->ecf", my, up)
        out = jnp.einsum("ecf,efd->ecd", h, down)

        y = jnp.zeros((xt.shape[0], d), jnp.float32)
        if ep:
            flat = out.reshape(e_loc * cap, d)
            for j in range(top_k):
                e_l = sel[:, j] - midx * e_loc
                owned = (e_l >= 0) & (e_l < e_loc)
                idx = jnp.clip(e_l * cap + rank[:, j], 0, e_loc * cap - 1)
                g = jnp.take(flat, idx, axis=0).astype(jnp.float32)
                y = y + jnp.where(owned[:, None], gates[:, j:j + 1] * g, 0.0)
        else:
            flat = out.reshape(n_experts * cap, d)
            for j in range(top_k):
                g = jnp.take(flat, slot[:, j], axis=0).astype(jnp.float32)
                y = y + gates[:, j:j + 1] * g
        if sg is not None:  # shared experts, TP over their d_ff
            hs = jax.nn.silu(xt @ sg) * (xt @ su)
            y = y + (hs @ sd).astype(jnp.float32)
        y = jax.lax.psum(y, "model")
        return y.astype(xt.dtype)

    xt = x.reshape(t, d)
    tok_spec = P(token_axes if len(token_axes) > 1 else
                 (token_axes[0] if token_axes else None))
    gate_spec = P("model", None, None) if ep else P(None, None, "model")
    down_spec = P("model", None, None) if ep else P(None, "model", None)
    has_shared = "shared" in p
    if has_shared:
        extra = (p["shared"]["gate"]["w"], p["shared"]["up"]["w"],
                 p["shared"]["down"]["w"])
        extra_specs = (P(None, "model"), P(None, "model"), P("model", None))
    else:
        dummy = jnp.zeros((1, 1), x.dtype)
        extra = (dummy, dummy, dummy)
        extra_specs = (P(), P(), P())

        def body_noshared(xt, router, gate, up, down, _sg, _su, _sd):
            return body(xt, router, gate, up, down, None, None, None)
        body_fn = body_noshared
    body_fn = body if has_shared else body_noshared
    fn = compat.shard_map(
        body_fn, mesh=mesh,
        in_specs=(tok_spec, P(), gate_spec, gate_spec, down_spec) + extra_specs,
        out_specs=tok_spec,
        check_vma=False)
    y = fn(xt, p["router"], p["gate"], p["up"], p["down"], *extra)
    aux = {"router_probs_mean": jnp.zeros((n_experts,), jnp.float32),
           "sel": None}
    return y.reshape(b, s, d), aux
