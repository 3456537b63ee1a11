"""Unified decoder backbone covering all assigned LM families.

Block layout per family:
  dense / vlm:   x += attn(norm(x));   x += swiglu(norm(x))
  moe:           x += attn|mla(norm(x)); x += moe(norm(x)) [+ shared experts];
                 the first ``n_dense_layers`` blocks (``params["dense_blocks"]``,
                 DeepSeek-V2's ``first_k_dense_replace``) have a dense SwiGLU of
                 width ``d_ff`` instead and run before the MoE stack.  Their
                 decode-cache rows are the first rows of the same state leaves.
  ssm (rwkv6):   x += timemix(norm(x)); x += channelmix(norm(x))
  hybrid(zamba): groups of ``hybrid_period`` mamba2 blocks, one *weight-shared*
                 attention+MLP block between groups (the zamba2 trick: depth
                 reuses one attention block's parameters).

Layers are scanned (stacked [L, ...] params) for compact HLO and FSDP-friendly
per-layer weight gathering; ``unroll`` switches to a static python loop for
the roofline cost pass.  Loss is sequence-chunked so [B, S, V] logits are never
materialized.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.act_shard import constrain

from .attention import (
    KVCache,
    MLACache,
    PagedKVCache,
    PagedMLACache,
    attention_decode,
    attention_extend,
    attention_prefill,
    init_attention,
    init_kv_cache,
    init_mla,
    mla_decode,
    mla_extend,
    mla_prefill,
    mla_rope,
)
from .layers import (dense_init, linear, non_parametric_ln, rms_norm,
                     site_linear, site_linear_group, swiglu)
from .mamba2 import Mamba2State, init_mamba2, mamba2_decode, mamba2_prefill
from .moe import ExpertStack, init_moe, moe_ffn, moe_ffn_manual
from .rwkv6 import (
    RWKV6State,
    init_rwkv6,
    init_rwkv6_channelmix,
    rwkv6_channelmix,
    rwkv6_timemix_decode,
    rwkv6_timemix_prefill,
)

__all__ = ["init_params", "forward", "decode_step", "init_decode_state",
           "forward_extend", "paged_layout", "loss_fn"]


def _scan(body, init, xs, unroll: bool):
    """lax.scan or a static python loop (roofline cost pass — while bodies are
    undercounted by HLO cost analysis, DESIGN.md Sec. 6)."""
    if not unroll:
        return jax.lax.scan(body, init, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked


def _norm(cfg: ArchConfig, p, x):
    if cfg.norm == "nonparam":
        return non_parametric_ln(x)
    return rms_norm(x, p)


def _norm_param(cfg: ArchConfig, d):
    # non-parametric LN keeps a (frozen, unused) scale so pytree structure is uniform
    return jnp.ones((d,), cfg.pdtype)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ArchConfig, dense_ffn: bool = False):
    """One block; ``dense_ffn`` gives an MoE config's leading dense block."""
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": _norm_param(cfg, cfg.d_model),
                         "ln2": _norm_param(cfg, cfg.d_model)}
    if cfg.family == "ssm":  # rwkv6
        p["tm"] = init_rwkv6(ks[0], cfg.d_model, head_dim=cfg.hd, dtype=cfg.pdtype)
        p["cm"] = init_rwkv6_channelmix(ks[1], cfg.d_model, cfg.d_ff, cfg.pdtype)
        return p
    if cfg.family == "hybrid":  # zamba2 mamba block (attention is shared, separate)
        p.pop("ln2")
        p["mamba"] = init_mamba2(ks[0], cfg.d_model, d_inner=cfg.ssm.d_inner,
                                 d_state=cfg.ssm.d_state, head_dim=cfg.ssm.head_dim,
                                 d_conv=cfg.ssm.d_conv, dtype=cfg.pdtype)
        return p
    if cfg.mla is not None:
        p["attn"] = init_mla(ks[0], cfg.d_model, cfg.n_heads, kv_lora=cfg.mla.kv_lora,
                             qk_nope=cfg.mla.qk_nope, qk_rope=cfg.mla.qk_rope,
                             v_dim=cfg.mla.v_dim, dtype=cfg.pdtype)
    else:
        p["attn"] = init_attention(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.hd, cfg.pdtype, qkv_bias=cfg.qkv_bias)
    if cfg.moe is not None and not dense_ffn:
        p["ffn"] = init_moe(ks[1], cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.n_experts,
                            cfg.moe.n_shared, cfg.pdtype)
    else:
        p["ffn"] = {
            "gate": dense_init(ks[1], cfg.d_model, cfg.d_ff, cfg.pdtype),
            "up": dense_init(ks[2], cfg.d_model, cfg.d_ff, cfg.pdtype),
            "down": dense_init(ks[3], cfg.d_ff, cfg.d_model, cfg.pdtype),
        }
    return p


def _init_shared_attn(key, cfg: ArchConfig):
    """Zamba2's weight-shared attention + MLP block."""
    ks = jax.random.split(key, 4)
    return {
        "ln1": _norm_param(cfg, cfg.d_model),
        "ln2": _norm_param(cfg, cfg.d_model),
        "attn": init_attention(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.hd, cfg.pdtype),
        "ffn": {
            "gate": dense_init(ks[1], cfg.d_model, cfg.d_ff, cfg.pdtype),
            "up": dense_init(ks[2], cfg.d_model, cfg.d_ff, cfg.pdtype),
            "down": dense_init(ks[3], cfg.d_ff, cfg.d_model, cfg.pdtype),
        },
    }


def init_params(key, cfg: ArchConfig):
    k_emb, k_blocks, k_head, k_shared = jax.random.split(key, 4)
    scale = cfg.d_model**-0.5
    params: dict[str, Any] = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab, cfg.d_model)) * scale).astype(cfg.pdtype),
        "final_ln": _norm_param(cfg, cfg.d_model),
    }
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    nd = cfg.n_dense_layers
    if nd:
        params["dense_blocks"] = jax.vmap(
            lambda k: _init_block(k, cfg, dense_ffn=True))(block_keys[:nd])
    params["blocks"] = jax.vmap(lambda k: _init_block(k, cfg))(block_keys[nd:])
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab, cfg.pdtype)
    if cfg.family == "hybrid":
        params["shared_attn"] = _init_shared_attn(k_shared, cfg)
    return params


def _layer_groups(params, cfg: ArchConfig):
    """The stacked layer groups in order: ``(blocks, first layer, layers,
    dense_ffn)`` — the leading dense blocks, then the main stack."""
    nd = cfg.n_dense_layers
    groups = [(params["dense_blocks"], 0, nd, True)] if nd else []
    if cfg.n_layers > nd:
        groups.append((params["blocks"], nd, cfg.n_layers - nd, False))
    return groups


def _rows(a, lo: int, n: int, total: int):
    """Layer rows ``[lo, lo + n)`` of a stacked leaf (the leaf itself when
    that is all of it)."""
    return a if (lo, n) == (0, total) else a[lo:lo + n]


def _concat_layers(parts):
    """Per-group stacked outputs joined along the layer axis."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a), *parts)


_EXPERTS = ("gate", "up", "down")


def _split_experts(gblocks, dense_ffn: bool):
    """(the blocks without their routed-expert stacks, the stacks) of an MoE
    group, else (blocks, None).  A layer scan then hands the grouped launches
    the whole stacks and the layer index (``moe.ExpertStack``) instead of
    slicing — and so copying — every expert of each layer."""
    ffn = gblocks.get("ffn", {})
    if dense_ffn or "router" not in ffn:
        return gblocks, None
    rest = {k: v for k, v in ffn.items() if k not in _EXPERTS}
    return {**gblocks, "ffn": rest}, {k: ffn[k] for k in _EXPERTS}


def _with_experts(bp, layer, stacks):
    """Block ``bp`` of a scan with its experts read from ``stacks`` at
    ``layer``."""
    views = {k: ExpertStack(v, layer) for k, v in stacks.items()}
    return {**bp, "ffn": {**bp["ffn"], **views}}


def _mla_rope(cfg: ArchConfig):
    return mla_rope(cfg.mla.qk_nope, cfg.mla.qk_rope, cfg.rope_theta, cfg.yarn)


def _moe_or_swiglu(cfg: ArchConfig, p, x, dense_ffn: bool, **kw):
    """The block's FFN: routed experts, or the dense SwiGLU of a dense block
    (or of a config without experts)."""
    if cfg.moe is None or dense_ffn:
        return swiglu(p, x)
    moe_fn = moe_ffn_manual if cfg.moe_manual else moe_ffn
    y, _aux = moe_fn(p, x, n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
                     norm_topk=cfg.moe.norm_topk, **kw)
    return y


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _block_fwd(cfg: ArchConfig, p, x, positions, *, unroll: bool, state=None,
               mrope_positions=None, dense_ffn: bool = False):
    """One block forward. Returns (x, per-layer state-out or aux)."""
    if cfg.family == "ssm":
        tm_in = _norm(cfg, p["ln1"], x)
        y, st = rwkv6_timemix_prefill(p["tm"], tm_in, head_dim=cfg.hd,
                                      chunk=cfg.ssm_chunk, unroll_chunks=unroll,
                                      state=None)
        x = x + y
        cm_in = _norm(cfg, p["ln2"], x)
        y, cm_last = rwkv6_channelmix(p["cm"], cm_in)
        x = x + y
        return x, RWKV6State(wkv=st.wkv, x_prev=st.x_prev), cm_last
    if cfg.family == "hybrid":
        y, st = mamba2_prefill(p["mamba"], _norm(cfg, p["ln1"], x),
                               d_inner=cfg.ssm.d_inner, d_state=cfg.ssm.d_state,
                               head_dim=cfg.ssm.head_dim, d_conv=cfg.ssm.d_conv,
                               chunk=cfg.ssm_chunk, unroll_chunks=unroll)
        return x + y, st, None
    # attention family
    attn_in = _norm(cfg, p["ln1"], x)
    if cfg.mla is not None:
        y, c_kv, k_rope = mla_prefill(p["attn"], attn_in, positions, n_heads=cfg.n_heads,
                                      kv_lora=cfg.mla.kv_lora, qk_nope=cfg.mla.qk_nope,
                                      qk_rope=cfg.mla.qk_rope, v_dim=cfg.mla.v_dim,
                                      rope=_mla_rope(cfg), q_chunk=cfg.q_chunk,
                                      unroll_chunks=unroll,
                                      causal_skip=cfg.causal_chunk_skip)
        kv = (c_kv, k_rope)
    else:
        y, k, v = attention_prefill(
            p["attn"], attn_in, positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.hd, causal=True, window=cfg.attn_window,
            rope_theta=None if cfg.pos in ("none", "mrope") else cfg.rope_theta,
            mrope_sections=cfg.mrope_sections if cfg.pos == "mrope" else None,
            mrope_positions=mrope_positions, q_chunk=cfg.q_chunk, unroll_chunks=unroll,
            causal_skip=cfg.causal_chunk_skip)
        kv = (k, v)
    x = x + y
    y = _moe_or_swiglu(cfg, p["ffn"], _norm(cfg, p["ln2"], x), dense_ffn)
    return x + y, kv, None


def _shared_attn_fwd(cfg: ArchConfig, p, x, positions, *, unroll: bool):
    y, k, v = attention_prefill(p["attn"], _norm(cfg, p["ln1"], x), positions,
                                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                                causal=True, window=cfg.attn_window,
                                rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
                                unroll_chunks=unroll, causal_skip=cfg.causal_chunk_skip)
    x = x + y
    x = x + swiglu(p["ffn"], _norm(cfg, p["ln2"], x))
    return x, (k, v)


def forward(params, cfg: ArchConfig, *, tokens=None, embeds=None, positions=None,
            positions3=None, unroll: bool = False, collect_cache: bool = False):
    """Train/prefill forward -> (hidden [B,S,d], caches or None)."""
    if embeds is not None:
        x = embeds.astype(cfg.cdtype)
        b, s = x.shape[:2]
    else:
        b, s = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    x = constrain(x, "batch", None, None)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if cfg.pos == "mrope" and positions3 is None:
        positions3 = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s))

    blocks = params["blocks"]

    def one(x, bp, dense_ffn=False):
        return _block_fwd(cfg, bp, x, positions, unroll=unroll,
                          mrope_positions=positions3, dense_ffn=dense_ffn)

    if cfg.family == "hybrid":
        period = cfg.hybrid_period
        n_groups = cfg.n_layers // period
        tail = cfg.n_layers - n_groups * period
        if unroll or collect_cache:  # python loop: roofline pass / serving prefill
            caches = {"mamba": [], "attn": []}
            li = 0
            for g in range(n_groups):
                for _ in range(period):
                    bp = jax.tree.map(lambda a: a[li], blocks)
                    x, st, _ = one(x, bp)
                    caches["mamba"].append(st)
                    li += 1
                x, kv = _shared_attn_fwd(cfg, params["shared_attn"], x, positions,
                                         unroll=unroll)
                caches["attn"].append(kv)
            for _ in range(tail):
                bp = jax.tree.map(lambda a: a[li], blocks)
                x, st, _ = one(x, bp)
                caches["mamba"].append(st)
                li += 1
            cache_out = caches if collect_cache else None
        else:  # production path: scan over groups, inner scan over mamba layers
            main = jax.tree.map(
                lambda a: a[: n_groups * period].reshape(n_groups, period, *a.shape[1:]),
                blocks)

            def layer_body(x, bp):
                x, _st, _ = one(x, bp)
                return x, None

            def group_body(x, gp):
                x, _ = jax.lax.scan(layer_body, x, gp)
                x, _kv = _shared_attn_fwd(cfg, params["shared_attn"], x, positions,
                                          unroll=False)
                return x, None

            if cfg.remat:
                group_body = jax.checkpoint(group_body)
            x, _ = jax.lax.scan(group_body, x, main)
            if tail:
                tailb = jax.tree.map(lambda a: a[n_groups * period:], blocks)
                body = jax.checkpoint(layer_body) if cfg.remat else layer_body
                x, _ = jax.lax.scan(body, x, tailb)
            cache_out = None
    elif unroll:
        cache_list = []
        for gblocks, _lo, n, dense in _layer_groups(params, cfg):
            if collect_cache:
                for li in range(n):
                    bp = jax.tree.map(lambda a: a[li], gblocks)
                    x, c, _extra = one(x, bp, dense)
                    cache_list.append(c)
            else:
                xonly = lambda x, bp, d=dense: one(x, bp, d)[0]  # noqa: E731
                fn = jax.checkpoint(xonly) if cfg.remat else xonly
                for li in range(n):
                    bp = jax.tree.map(lambda a: a[li], gblocks)
                    x = fn(x, bp)
        cache_out = cache_list if collect_cache else None
    else:
        parts = []
        for gblocks, _lo, n, dense in _layer_groups(params, cfg):
            # serving prefill reads the expert stacks in place; training
            # slices them (the gradient of a whole stack per layer is not
            # worth the copy it saves)
            gblocks, stacks = (_split_experts(gblocks, dense) if collect_cache
                               else (gblocks, None))

            def body(x, xs, dense=dense, stacks=stacks):
                bp = xs if stacks is None else _with_experts(*xs, stacks)
                x, c, _extra = one(x, bp, dense)
                return x, c if collect_cache else None

            if cfg.remat:
                body = jax.checkpoint(body)
            x, stacked = jax.lax.scan(
                body, x, gblocks if stacks is None else (gblocks,
                                                         jnp.arange(n)))
            parts.append(stacked)
        cache_out = _concat_layers(parts) if collect_cache else None

    x = _norm(cfg, params["final_ln"], x)
    return x, cache_out


def logits_from_hidden(params, cfg: ArchConfig, h):
    if cfg.tie_embeddings:
        return h @ params["embed"].T.astype(h.dtype)
    return linear(params["lm_head"], h)


def loss_fn(params, cfg: ArchConfig, batch, *, unroll: bool = False,
            seq_chunk: int = 512):
    """Sequence-chunked cross-entropy; logits [B,S,V] never materialized."""
    h, _ = forward(params, cfg,
                   tokens=batch.get("tokens"), embeds=batch.get("embeds"),
                   positions3=batch.get("positions3"), unroll=unroll)
    labels = batch["labels"]
    b, s = labels.shape
    c = min(seq_chunk, s)
    while s % c:
        c //= 2
    nc = s // c
    hch = h.reshape(b, nc, c, cfg.d_model)
    lch = labels.reshape(b, nc, c)

    def chunk_loss(hc, lc):
        logits = logits_from_hidden(params, cfg, hc).astype(jnp.float32)  # [B,c,V]
        logits = constrain(logits, "batch", None, "model")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return (lse - gold).sum()

    if unroll or nc == 1:
        tot = 0.0
        for i in range(nc):
            tot += chunk_loss(hch[:, i], lch[:, i])
    else:
        def body(acc, xs):
            hc, lc = xs
            return acc + chunk_loss(hc, lc), None

        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                              (jnp.moveaxis(hch, 1, 0), jnp.moveaxis(lch, 1, 0)))
    return tot / (b * s)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def paged_layout(cfg: ArchConfig, smax: int, kv_block: int,
                 kv_blocks: int | None = None, n_slots: int = 1):
    """Resolve paged-KV geometry -> ``(block_size, view_blocks, pool_entries)``.

    Windowed attention shrinks the block so it divides the ring exactly
    (``gcd``), keeping the logical view the same length as the ring — the
    ``pos % eff`` slot arithmetic is unchanged.  ``pool_entries`` counts the
    reserved null block (id 0) and is rounded up to a multiple of 8 so the
    pool axis shards evenly over small meshes; without ``kv_blocks`` the pool
    matches the contiguous layout's token capacity (one full view per slot).
    """
    w = cfg.attn_window
    eff = min(smax, w) if w is not None else smax
    bs = math.gcd(int(kv_block), eff) if w is not None else min(int(kv_block), eff)
    mb = -(-eff // bs)
    usable = kv_blocks if kv_blocks is not None else n_slots * mb
    if w is not None:
        usable = max(usable, mb)  # a ring slot needs its whole view resident
    entries = -(-(usable + 1) // 8) * 8
    return bs, mb, entries


def init_decode_state(cfg: ArchConfig, batch: int, smax: int, *,
                      kv_block: int | None = None,
                      kv_blocks: int | None = None):
    """Abstract-init-friendly per-layer decode caches (call under eval_shape too).

    ``kv_block`` switches the attention families (dense GQA, MLA) to a paged
    layout: per-layer block *pools* ``[L, pool, bs, ...]`` plus one shared
    block table ``[batch, view_blocks]`` (see ``serving.kvpool``).  Families
    whose state is not a KV sequence (ssm, hybrid) and encoder-decoder models
    ignore it — they keep the contiguous layout.
    """
    L = cfg.n_layers
    cd = cfg.cdtype
    if cfg.family == "ssm":
        h = cfg.d_model // cfg.hd
        return {
            "wkv": jnp.zeros((L, batch, h, cfg.hd, cfg.hd), jnp.float32),
            "x_prev_tm": jnp.zeros((L, batch, cfg.d_model), cd),
            "x_prev_cm": jnp.zeros((L, batch, cfg.d_model), cd),
        }
    if cfg.family == "hybrid":
        hh = cfg.ssm.d_inner // cfg.ssm.head_dim
        n_attn = cfg.n_layers // cfg.hybrid_period
        conv_dim = cfg.ssm.d_inner + 2 * cfg.ssm.d_state
        return {
            "ssm": jnp.zeros((L, batch, hh, cfg.ssm.d_state, cfg.ssm.head_dim), jnp.float32),
            "conv": jnp.zeros((L, batch, conv_dim, cfg.ssm.d_conv - 1), cd),
            "attn_k": jnp.zeros((n_attn, batch, smax, cfg.n_kv_heads, cfg.hd), cd),
            "attn_v": jnp.zeros((n_attn, batch, smax, cfg.n_kv_heads, cfg.hd), cd),
            "attn_kpos": jnp.full((n_attn, batch, smax), -1, jnp.int32),
        }
    if cfg.mla is not None:
        if kv_block is not None:
            bs, mb, nb = paged_layout(cfg, smax, kv_block, kv_blocks, n_slots=batch)
            return {
                "c_kv": jnp.zeros((L, nb, bs, cfg.mla.kv_lora), cd),
                "k_rope": jnp.zeros((L, nb, bs, cfg.mla.qk_rope), cd),
                "kpos": jnp.full((L, batch, mb * bs), -1, jnp.int32),
                "block_tbl": jnp.zeros((batch, mb), jnp.int32),
            }
        return {
            "c_kv": jnp.zeros((L, batch, smax, cfg.mla.kv_lora), cd),
            "k_rope": jnp.zeros((L, batch, smax, cfg.mla.qk_rope), cd),
            "kpos": jnp.full((L, batch, smax), -1, jnp.int32),
        }
    w = cfg.attn_window
    eff = min(smax, w) if w is not None else smax
    if kv_block is not None:
        bs, mb, nb = paged_layout(cfg, smax, kv_block, kv_blocks, n_slots=batch)
        return {
            "k": jnp.zeros((L, nb, bs, cfg.n_kv_heads, cfg.hd), cd),
            "v": jnp.zeros((L, nb, bs, cfg.n_kv_heads, cfg.hd), cd),
            "kpos": jnp.full((L, batch, mb * bs), -1, jnp.int32),
            "block_tbl": jnp.zeros((batch, mb), jnp.int32),
        }
    return {
        "k": jnp.zeros((L, batch, eff, cfg.n_kv_heads, cfg.hd), cd),
        "v": jnp.zeros((L, batch, eff, cfg.n_kv_heads, cfg.hd), cd),
        "kpos": jnp.full((L, batch, eff), -1, jnp.int32),
    }


def _sites_swiglu(executor, tag: str):
    """SwiGLU routed through compressed sites: gate/up (shared input) as ONE
    grouped fused launch, down through its own chain; uncovered sites dense."""
    def ffn(p, x):
        g, u = site_linear_group(executor, (tag.format("gate"), tag.format("up")),
                                 (p["gate"], p["up"]), x)
        g = constrain(g, "batch", None, "model")
        u = constrain(u, "batch", None, "model")
        y = site_linear(executor, tag.format("down"), p["down"],
                        jax.nn.silu(g) * u)
        return constrain(y, "batch", None, None)

    return ffn


def _unrolled_layers(body_for, x, xs_all, n_layers: int):
    """Static per-layer loop so layer ``li`` binds its own kernel buffers
    (the executor's fused chains are per-site constants, which a lax.scan
    cannot carry)."""
    per_layer = []
    for li in range(n_layers):
        xs_li = jax.tree.map(lambda a: a[li], xs_all)
        x, out = body_for(li)(x, xs_li)
        per_layer.append(out)
    outs = jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
    return x, outs


def _decode_layers(step_for, x, state, groups, caches, *, unrolled: bool,
                   unroll: bool):
    """The attention families' decode layer loop over ``groups`` of
    ``(blocks, expert stacks or None, first layer, layers, dense_ffn)``.

    A paged state's pools (``[L, Nb, bs, ...]``) ride in the carry whole,
    next to ``x``: each layer scatters its B new rows into them at its own
    index and gathers its view back, so under the engine's donation nothing
    else writes the pools and the step's output aliases its input.  The other
    per-layer leaves (``kpos``; all of a contiguous cache) are scanned as
    xs/ys.  ``caches`` is ``(contiguous cache type, paged cache type)``;
    ``step_for(li, dense_ffn)`` gives ``step(x, block, cache) -> (x, cache)``
    for layer ``li`` — the absolute index on the ``unrolled`` path (the
    executor's per-layer kernels), else None.
    """
    flat, paged = caches
    tbl = state.get("block_tbl")
    pools = ({} if tbl is None else
             {k: state[k] for k in paged._fields
              if k not in ("kpos", "tbl", "layer")})

    def body_for(li, lo, dense, stacks):
        step = step_for(li, dense)

        def body(carry, xs):
            (x, pools), (bp, per, i) = carry, xs
            if stacks is not None:
                bp = _with_experts(bp, i, stacks)
            layer = lo + i if li is None else li
            cache = (flat(**per) if tbl is None
                     else paged(**pools, **per, tbl=tbl, layer=layer))
            x, c2 = step(x, bp, cache)
            out = c2._asdict()
            return (x, {k: out[k] for k in pools}), {k: out[k] for k in per}
        return body

    parts = []
    for gblocks, stacks, lo, n, dense in groups:
        per = {k: _rows(a, lo, n, a.shape[0]) for k, a in state.items()
               if k not in pools and k != "block_tbl"}
        xs = (gblocks, per, jnp.arange(n))
        if unrolled:
            (x, pools), outs = _unrolled_layers(
                lambda i, lo=lo, d=dense, st=stacks: body_for(lo + i, lo, d, st),
                (x, pools), xs, n)
        else:
            (x, pools), outs = _scan(body_for(None, lo, dense, stacks),
                                     (x, pools), xs, unroll)
        parts.append(outs)
    new = {**pools, **_concat_layers(parts)}
    if tbl is not None:
        new["block_tbl"] = tbl
    return x, new


def decode_step(params, cfg: ArchConfig, state, token, pos, *, unroll: bool = False,
                executor=None):
    """One decode step: (logits [B, V], new state). token [B,1], pos [B].

    ``executor`` (compressed serving): a site-keyed registry — see
    ``repro.serving.executor.CompressedExecutor`` — consulted for EVERY
    compressible site of the family (attention q/k/v/o or MLA projections,
    FFN gate/up/down, per-expert MoE matrices, RWKV-6 time/channel mixes,
    Mamba2 in/out, the zamba2 shared block).  Covered sites execute their LCC
    chains through fused Pallas launches *inside* this (jitted) step; sites
    the executor does not cover fall back to the dense weights.  The layer
    loop is unrolled when an executor is present so each layer binds its own
    kernel buffers.
    """
    x = jnp.take(params["embed"], token, axis=0).astype(cfg.cdtype)
    blocks = params["blocks"]

    if cfg.family == "ssm":
        def body_for(li):
            ex = executor if li is not None else None

            def body(x, xs):
                bp, wkv, xp_tm, xp_cm = xs
                tm_in = _norm(cfg, bp["ln1"], x)
                y, st = rwkv6_timemix_decode(
                    bp["tm"], tm_in, RWKV6State(wkv=wkv, x_prev=xp_tm),
                    head_dim=cfg.hd, executor=ex,
                    site=f"tm.{{}}.l{li}" if ex is not None else None)
                x = x + y
                cm_in = _norm(cfg, bp["ln2"], x)
                y, _cm_last = rwkv6_channelmix(
                    bp["cm"], cm_in, x_prev_last=xp_cm, executor=ex,
                    site=f"cm.{{}}.l{li}" if ex is not None else None)
                x = x + y
                return x, (st.wkv, st.x_prev, cm_in[:, 0])
            return body

        xs_all = (blocks, state["wkv"], state["x_prev_tm"], state["x_prev_cm"])
        if executor is None:
            x, outs = _scan(body_for(None), x, xs_all, unroll)
        else:
            x, outs = _unrolled_layers(body_for, x, xs_all, cfg.n_layers)
        new = {"wkv": outs[0], "x_prev_tm": outs[1], "x_prev_cm": outs[2]}
    elif cfg.family == "hybrid":
        period = cfg.hybrid_period
        n_groups = cfg.n_layers // period
        tail = cfg.n_layers - n_groups * period
        nmain = n_groups * period
        sp = params["shared_attn"]

        def mamba_body_for(li):
            ex = executor if li is not None else None

            def mamba_body(x, xs):
                bp, ssm, conv = xs
                st = Mamba2State(ssm=ssm, conv=conv)
                y, st2 = mamba2_decode(
                    bp["mamba"], _norm(cfg, bp["ln1"], x), st,
                    d_inner=cfg.ssm.d_inner, d_state=cfg.ssm.d_state,
                    head_dim=cfg.ssm.head_dim, d_conv=cfg.ssm.d_conv,
                    executor=ex,
                    site=f"mamba.{{}}.l{li}" if ex is not None else None)
                return x + y, (st2.ssm, st2.conv)
            return mamba_body

        def shared_attn_step(x, ak, av, akp):
            cache = KVCache(k=ak, v=av, kpos=akp)
            y, c2 = attention_decode(
                sp["attn"], _norm(cfg, sp["ln1"], x), cache, pos,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                window=cfg.attn_window, rope_theta=cfg.rope_theta,
                executor=executor,
                site="shared_attn.attn.{}" if executor is not None else None)
            x = x + y
            if executor is not None:
                ffn = _sites_swiglu(executor, "shared_attn.ffn.{}")
                x = x + ffn(sp["ffn"], _norm(cfg, sp["ln2"], x))
            else:
                x = x + swiglu(sp["ffn"], _norm(cfg, sp["ln2"], x))
            return x, c2

        if executor is None:
            def group_body(x, xs):
                gb, gssm, gconv, ak, av, akp = xs
                x, (ssm2, conv2) = _scan(mamba_body_for(None), x,
                                         (gb, gssm, gconv), unroll)
                x, c2 = shared_attn_step(x, ak, av, akp)
                return x, (ssm2, conv2, c2.k, c2.v, c2.kpos)

            regroup = lambda a: a[:nmain].reshape(n_groups, period, *a.shape[1:])  # noqa: E731
            main_b = jax.tree.map(regroup, blocks)
            x, outs = _scan(group_body, x,
                            (main_b, regroup(state["ssm"]), regroup(state["conv"]),
                             state["attn_k"], state["attn_v"], state["attn_kpos"]),
                            unroll)
            ssm2 = outs[0].reshape(nmain, *state["ssm"].shape[1:])
            conv2 = outs[1].reshape(nmain, *state["conv"].shape[1:])
            ak2, av2, akp2 = outs[2], outs[3], outs[4]
            if tail:
                tail_b = jax.tree.map(lambda a: a[nmain:], blocks)
                x, touts = _scan(mamba_body_for(None), x,
                                 (tail_b, state["ssm"][nmain:], state["conv"][nmain:]),
                                 unroll)
                ssm2 = jnp.concatenate([ssm2, touts[0]])
                conv2 = jnp.concatenate([conv2, touts[1]])
        else:
            # unrolled: each mamba layer / the shared block bind their chains
            ssm_l, conv_l, ak_l, av_l, akp_l = [], [], [], [], []
            li = 0
            for g in range(n_groups):
                for _ in range(period):
                    xs_li = (jax.tree.map(lambda a: a[li], blocks),
                             state["ssm"][li], state["conv"][li])
                    x, (s2, c2) = mamba_body_for(li)(x, xs_li)
                    ssm_l.append(s2)
                    conv_l.append(c2)
                    li += 1
                x, kv2 = shared_attn_step(x, state["attn_k"][g],
                                          state["attn_v"][g],
                                          state["attn_kpos"][g])
                ak_l.append(kv2.k)
                av_l.append(kv2.v)
                akp_l.append(kv2.kpos)
            for _ in range(tail):
                xs_li = (jax.tree.map(lambda a: a[li], blocks),
                         state["ssm"][li], state["conv"][li])
                x, (s2, c2) = mamba_body_for(li)(x, xs_li)
                ssm_l.append(s2)
                conv_l.append(c2)
                li += 1
            ssm2 = jnp.stack(ssm_l)
            conv2 = jnp.stack(conv_l)
            ak2, av2, akp2 = (jnp.stack(ak_l), jnp.stack(av_l),
                              jnp.stack(akp_l))
        new = {"ssm": ssm2, "conv": conv2, "attn_k": ak2, "attn_v": av2,
               "attn_kpos": akp2}
    elif cfg.mla is not None:
        rope = _mla_rope(cfg)

        def step_for(li, dense_ffn):
            ex = executor if li is not None else None

            def step(x, bp, cache):
                y, c2 = mla_decode(
                    bp["attn"], _norm(cfg, bp["ln1"], x), cache, pos,
                    n_heads=cfg.n_heads, kv_lora=cfg.mla.kv_lora,
                    qk_nope=cfg.mla.qk_nope, qk_rope=cfg.mla.qk_rope,
                    v_dim=cfg.mla.v_dim, rope=rope,
                    executor=ex,
                    site=f"attn.{{}}.l{li}" if ex is not None else None)
                x = x + y
                ffn_in = _norm(cfg, bp["ln2"], x)
                if ex is not None and (cfg.moe is None or dense_ffn):
                    y = _sites_swiglu(ex, f"ffn.{{}}.l{li}")(bp["ffn"], ffn_in)
                else:
                    kw = ({"executor": ex, "site_tag": f"l{li}"}
                          if ex is not None and not cfg.moe_manual else {})
                    y = _moe_or_swiglu(cfg, bp["ffn"], ffn_in, dense_ffn, **kw)
                return x + y, c2
            return step

        # the scan reads routed experts in place from their stacks; the
        # unrolled path binds each layer's own sites
        groups = [((gb, None) if executor is not None
                   else _split_experts(gb, dense)) + (lo, n, dense)
                  for gb, lo, n, dense in _layer_groups(params, cfg)]
        x, new = _decode_layers(step_for, x, state, groups,
                                (MLACache, PagedMLACache),
                                unrolled=executor is not None, unroll=unroll)
    else:
        if cfg.n_dense_layers:
            raise NotImplementedError(
                "leading dense layers are served with MLA attention only")
        # whole-step layer plan: when the executor can express the full layer
        # stack as one stacked-grid launch, the per-layer loop (and all its
        # per-region dispatches) is replaced by a single pallas_call
        plan = (executor.step_plan(cfg)
                if executor is not None and hasattr(executor, "step_plan")
                else None)
        if plan is not None:
            x, new = plan.decode_layers(state, x, pos)
        else:
            def step_for(li, _dense_ffn):
                ex = executor if li is not None else None

                def ffn_fn(p, ffn_in):
                    if cfg.moe is not None:
                        kw = ({"executor": ex, "site_tag": f"l{li}"}
                              if ex is not None and not cfg.moe_manual else {})
                        return _moe_or_swiglu(cfg, p, ffn_in, False, **kw)
                    if ex is not None:
                        return _sites_swiglu(ex, f"ffn.{{}}.l{li}")(p, ffn_in)
                    return swiglu(p, ffn_in)

                def step(x, bp, cache):
                    y, c2 = attention_decode(
                        bp["attn"], _norm(cfg, bp["ln1"], x), cache, pos,
                        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                        window=cfg.attn_window,
                        rope_theta=None if cfg.pos in ("none", "mrope") else cfg.rope_theta,
                        mrope_sections=cfg.mrope_sections if cfg.pos == "mrope" else None,
                        mrope_positions=jnp.broadcast_to(pos[None, :, None], (3, pos.shape[0], 1))
                        if cfg.pos == "mrope" else None,
                        executor=ex,
                        site=f"attn.{{}}.l{li}" if ex is not None else None)
                    x = x + y
                    ffn_in = _norm(cfg, bp["ln2"], x)
                    y = ffn_fn(bp["ffn"], ffn_in)
                    return x + y, c2
                return step

            # unrolled layer loop with an executor: each layer binds its own
            # kernel buffers
            x, new = _decode_layers(step_for, x, state,
                                    [(blocks, None, 0, cfg.n_layers, False)],
                                    (KVCache, PagedKVCache),
                                    unrolled=executor is not None, unroll=unroll)

    h = _norm(cfg, params["final_ln"], x)
    logits = logits_from_hidden(params, cfg, h)[:, 0]
    return logits, new


def forward_extend(params, cfg: ArchConfig, tokens, positions, past, last, *,
                   unroll: bool = False):
    """Prefix-cache tail prefill: run ``tokens`` [B,T] at absolute
    ``positions`` [B,T] attending to a resident per-layer KV prefix.

    ``past`` holds the *gathered* pool views for the cached prefix —
    dense: ``{"k","v": [L,B,C,Hkv,hd], "kpos": [L,B,C]}``; MLA:
    ``{"c_kv","k_rope","kpos"}`` — masked by ``kpos == -1`` (so padding the
    prefix view is harmless).  Padded tail entries carry position ``-1``:
    they are excluded from every real query's key set and their own garbage
    activations stay confined to their row.  ``last`` [B] indexes the final
    real tail token.  Returns ``(logits [B,V] at ``last``, tail caches with
    [L,B,T,...] leaves)`` — only the tail K/V, for scatter into freshly
    allocated blocks.
    """
    b, t = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    names = ("c_kv", "k_rope") if cfg.mla is not None else ("k", "v")

    def body_for(dense_ffn, stacks):
        def body(x, xs):
            bp, pa, pb, pkp = xs[:4]
            if stacks is not None:
                bp = _with_experts(bp, xs[4], stacks)
            h = _norm(cfg, bp["ln1"], x)
            if cfg.mla is not None:
                y, ta, tb = mla_extend(
                    bp["attn"], h, positions, pa, pb, pkp,
                    n_heads=cfg.n_heads, qk_nope=cfg.mla.qk_nope,
                    qk_rope=cfg.mla.qk_rope, v_dim=cfg.mla.v_dim,
                    rope=_mla_rope(cfg))
            else:
                y, ta, tb = attention_extend(
                    bp["attn"], h, positions, pa, pb, pkp,
                    n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                    rope_theta=None if cfg.pos == "none" else cfg.rope_theta)
            x = x + y
            x = x + _moe_or_swiglu(cfg, bp["ffn"], _norm(cfg, bp["ln2"], x),
                                   dense_ffn)
            return x, (ta, tb)
        return body

    parts = []
    for gblocks, lo, n, dense in _layer_groups(params, cfg):
        gblocks, stacks = _split_experts(gblocks, dense)
        xs = (gblocks,) + tuple(_rows(past[k], lo, n, cfg.n_layers)
                                for k in names + ("kpos",))
        if stacks is not None:
            xs += (jnp.arange(n),)
        x, outs = _scan(body_for(dense, stacks), x, xs, unroll)
        parts.append(outs)
    outs = _concat_layers(parts)
    tails = {names[0]: outs[0], names[1]: outs[1]}

    h = x[jnp.arange(b), last][:, None]  # [B,1,d]
    h = _norm(cfg, params["final_ln"], h)
    logits = logits_from_hidden(params, cfg, h)[:, 0]
    return logits, tails
