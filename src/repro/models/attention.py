"""Attention: GQA (full / sliding-window / bidirectional / cross) and MLA.

Prefill uses query-chunked attention (memory O(S * chunk) instead of O(S^2))
with two lowering modes:
  * ``unroll_chunks=False`` — lax.scan over chunks (compact HLO; production).
  * ``unroll_chunks=True``  — static python loop; used by the roofline pass
    (while-bodies are undercounted by HLO cost analysis, see DESIGN.md Sec. 6)
    and enables *causal chunk skipping*: a query chunk statically attends only
    to keys at positions <= its end, which removes the upper-triangle FLOPs —
    one of the beyond-paper optimizations measured in EXPERIMENTS.md §Perf.

KV caches are plain pytrees. Sliding-window attention uses a ring buffer of
size ``window`` so the 500k-token decode cell runs with bounded memory.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.act_shard import constrain
from repro.kernels.dispatch import record_mla_decode_route

from .layers import (apply_mrope, apply_rope, apply_rope_pairs, dense_init,
                     linear, rms_norm, rope_inv_freq, site_fmt, site_linear,
                     site_linear_group, yarn_mscale)

__all__ = [
    "AttnParams",
    "init_attention",
    "attention_prefill",
    "attention_decode",
    "attention_extend",
    "KVCache",
    "PagedKVCache",
    "init_kv_cache",
    "init_mla",
    "mla_prefill",
    "mla_decode",
    "mla_extend",
    "MLACache",
    "PagedMLACache",
    "MLARope",
    "mla_rope",
]

_NEG = -1e30


class KVCache(NamedTuple):
    k: jnp.ndarray  # [B, Smax, Hkv, Dh]  (ring buffer if windowed)
    v: jnp.ndarray  # [B, Smax, Hkv, Dh]
    kpos: jnp.ndarray  # [B, Smax] absolute positions (-1 = empty)


class PagedKVCache(NamedTuple):
    """Paged KV: every layer's block pool plus per-row block tables.

    ``k``/``v`` are the whole stack's pools, which the layer loop carries;
    layer ``layer`` writes its new rows into them in place and reads its view
    back.  ``tbl[b, j]`` names the pool block backing row ``b``'s logical
    blocks (0 = the reserved null block — unallocated, masked out via
    ``kpos == -1``).  The logical view (``tbl`` gathered and flattened) has
    exactly the contiguous cache's layout, so attention math — and its
    numerics — are unchanged."""
    k: jnp.ndarray  # [L, Nb, bs, Hkv, Dh] block pools (all layers)
    v: jnp.ndarray  # [L, Nb, bs, Hkv, Dh]
    kpos: jnp.ndarray  # [B, S] this layer's positions (-1 = empty), S = mb * bs
    tbl: jnp.ndarray  # [B, mb] int32 block ids
    layer: jnp.ndarray | int  # this layer's index into the pools


class PagedMLACache(NamedTuple):
    c_kv: jnp.ndarray  # [L, Nb, bs, dc] latent block pools (all layers)
    k_rope: jnp.ndarray  # [L, Nb, bs, Dr]
    kpos: jnp.ndarray  # [B, S]
    tbl: jnp.ndarray  # [B, mb]
    layer: jnp.ndarray | int


def paged_view(pool: jnp.ndarray, tbl: jnp.ndarray, layer) -> jnp.ndarray:
    """Gather layer ``layer`` of the pools ``[L, Nb, bs, ...]`` through block
    tables ``[B, mb]`` into the contiguous logical view ``[B, mb * bs, ...]``.

    The barrier keeps XLA from moving the scores' f32 upcast of the view
    above this reshape, onto the gather's own output: there the TPU compiler
    fuses it into neither side and writes an f32 copy of the view a layer."""
    b, mb = tbl.shape
    bs = pool.shape[2]
    view = pool[layer, tbl].reshape(b, mb * bs, *pool.shape[3:])
    return jax.lax.optimization_barrier(view)


def _paged_scatter(pool: jnp.ndarray, tbl: jnp.ndarray, layer, slot: jnp.ndarray,
                   vals: jnp.ndarray) -> jnp.ndarray:
    """Write one token per row into layer ``layer`` of the pools at logical
    view position ``slot`` ([B], -1 = no write -> routed to the null block)."""
    bs = pool.shape[2]
    w = jnp.maximum(slot, 0)
    bidx = jnp.take_along_axis(tbl, (w // bs)[:, None], axis=1)[:, 0]
    bidx = jnp.where(slot >= 0, bidx, 0)  # inactive rows sink to null block 0
    return pool.at[layer, bidx, w % bs].set(vals)


def init_kv_cache(batch: int, smax: int, n_kv: int, head_dim: int, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, smax, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, smax, n_kv, head_dim), dtype),
        kpos=jnp.full((batch, smax), -1, jnp.int32),
    )


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   dtype, qkv_bias: bool = False):
    ks = jax.random.split(key, 4)
    return {
        "q": dense_init(ks[0], d_model, n_heads * head_dim, dtype, bias=qkv_bias),
        "k": dense_init(ks[1], d_model, n_kv * head_dim, dtype, bias=qkv_bias),
        "v": dense_init(ks[2], d_model, n_kv * head_dim, dtype, bias=qkv_bias),
        "o": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }


def _project_qkv(p, x, n_heads, n_kv, head_dim):
    b, s, _ = x.shape
    q = linear(p["q"], x).reshape(b, s, n_heads, head_dim)
    k = linear(p["k"], x).reshape(b, s, n_kv, head_dim)
    v = linear(p["v"], x).reshape(b, s, n_kv, head_dim)
    return q, k, v


def _sdpa(q, k, v, mask, scale=None):
    """q [B,Sq,Hkv,G,D], k/v [B,Sk,Hkv,D], additive mask [B,1,1,Sq,Sk] or None;
    ``scale`` on the scores defaults to 1/sqrt(D)."""
    if scale is None:
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out


def attention_prefill(
    p, x, positions, *, n_heads: int, n_kv: int, head_dim: int,
    causal: bool = True, window: int | None = None,
    rope_theta: float | None = 10000.0, mrope_sections=None, mrope_positions=None,
    q_chunk: int = 1024, unroll_chunks: bool = False, causal_skip: bool = False,
    kv_x: jnp.ndarray | None = None,
):
    """Returns (out [B,S,d_model], k, v). ``kv_x`` switches to cross-attention."""
    b, s, _ = x.shape
    g = n_heads // n_kv
    q = constrain(linear(p["q"], x).reshape(b, s, n_heads, head_dim),
                  "batch", None, "model", None)
    src = x if kv_x is None else kv_x
    sk = src.shape[1]
    k = constrain(linear(p["k"], src).reshape(b, sk, n_kv, head_dim),
                  "batch", None, "model", None)
    v = constrain(linear(p["v"], src).reshape(b, sk, n_kv, head_dim),
                  "batch", None, "model", None)

    if mrope_sections is not None:
        q = apply_mrope(q, mrope_positions, mrope_sections)
        k = apply_mrope(k, mrope_positions, mrope_sections)
    elif rope_theta is not None:
        kpos = positions if kv_x is None else jnp.broadcast_to(jnp.arange(sk)[None], (b, sk))
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, kpos, rope_theta)

    qg = q.reshape(b, s, n_kv, g, head_dim)
    kpos_all = jnp.arange(sk)

    def chunk_out(q_c, qpos_c, k_c, v_c, kpos_c):
        if causal and kv_x is None:
            m = (kpos_c[None, :] <= qpos_c[:, None]).astype(jnp.float32)
            if window is not None:
                m = m * (kpos_c[None, :] > qpos_c[:, None] - window)
            mask = jnp.where(m > 0, 0.0, _NEG)[None, None, None]
        else:
            mask = None
        return _sdpa(q_c, k_c, v_c, mask)

    n_chunks = max(1, s // q_chunk) if s % q_chunk == 0 else 1
    if n_chunks == 1:
        out = chunk_out(qg, positions[0], k, v, kpos_all)
    elif unroll_chunks:
        outs = []
        cq = s // n_chunks
        for i in range(n_chunks):
            q_c = qg[:, i * cq:(i + 1) * cq]
            qpos_c = positions[0, i * cq:(i + 1) * cq]
            # static causal/window chunk skipping: only keys that can be seen
            lo, hi = 0, sk
            if causal_skip and causal and kv_x is None:
                hi = min(sk, (i + 1) * cq)
                if window is not None:
                    lo = max(0, i * cq - int(window))
            outs.append(chunk_out(q_c, qpos_c, k[:, lo:hi], v[:, lo:hi], kpos_all[lo:hi]))
        out = jnp.concatenate(outs, axis=1)
    else:
        cq = s // n_chunks
        qg_r = qg.reshape(b, n_chunks, cq, n_kv, g, head_dim)
        qpos_r = positions[0].reshape(n_chunks, cq)

        def body(_, qc):
            q_c, qpos_c = qc
            return None, chunk_out(jnp.moveaxis(q_c, 0, 0), qpos_c, k, v, kpos_all)

        _, outs = jax.lax.scan(body, None, (jnp.moveaxis(qg_r, 1, 0), qpos_r))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, n_kv, g, head_dim)
        out = out.reshape(b, s, n_heads * head_dim)
        y = constrain(linear(p["o"], out.astype(x.dtype)), "batch", None, None)
        return y, k, v

    out = out.reshape(b, s, n_heads * head_dim)
    y = constrain(linear(p["o"], out.astype(x.dtype)), "batch", None, None)
    return y, k, v


def attention_decode(
    p, x, cache: KVCache, pos, *, n_heads: int, n_kv: int, head_dim: int,
    window: int | None = None, rope_theta: float | None = 10000.0,
    mrope_sections=None, mrope_positions=None, cross: bool = False,
    executor=None, site: str | None = None,
):
    """One-token decode. x [B,1,d]; pos [B] absolute position of this token.

    Returns (out [B,1,d], new_cache). With ``window`` the cache is a ring
    buffer (slot = pos % window). ``cross=True`` reads a static cross-attention
    cache (no update, no causal mask).

    ``executor``/``site`` (compressed serving): q/k/v/o route through the
    executor's fused LCC kernels — q/k/v as ONE grouped launch (they share the
    input) — for sites named ``site.format(proj)``; uncovered sites stay
    dense.

    ``cache`` may be a :class:`PagedKVCache`: keys/values then live in block
    pools indexed through per-row block tables.  The new token is scattered
    into its pool block first and the view is gathered from the updated pool,
    so the view holds it with no merge; the view has the contiguous layout
    (same positions, same mask math)."""
    b = x.shape[0]
    paged = isinstance(cache, PagedKVCache)
    sn = site_fmt(site)
    if cross:
        q_raw = site_linear(executor, sn("q"), p["q"], x)
    else:
        q_raw, k_raw, v_raw = site_linear_group(
            executor, (sn("q"), sn("k"), sn("v")),
            (p["q"], p["k"], p["v"]), x)
    q = constrain(q_raw.reshape(b, 1, n_heads, head_dim),
                  "batch", None, "model", None)
    if mrope_sections is not None:
        q = apply_mrope(q, mrope_positions, mrope_sections)
    elif rope_theta is not None:
        q = apply_rope(q, pos[:, None], rope_theta)

    if cross:
        new_cache = cache
        k, v, kpos = cache.k, cache.v, cache.kpos
    else:
        k_new = k_raw.reshape(b, 1, n_kv, head_dim)
        v_new = v_raw.reshape(b, 1, n_kv, head_dim)
        if rope_theta is not None and mrope_sections is None:
            k_new = apply_rope(k_new, pos[:, None], rope_theta)
        elif mrope_sections is not None:
            k_new = apply_mrope(k_new, mrope_positions, mrope_sections)
        smax = cache.kpos.shape[1]
        # negative pos (serving's inactive-slot sentinel) must stay out of the
        # ring too: plain pos would wrap -1 % smax onto a live cache entry
        slot = jnp.where(pos >= 0, pos % smax, -1) if window is not None else pos
        hit = jnp.arange(smax)[None] == slot[:, None]  # [B, Smax]
        kpos = jnp.where(hit, pos[:, None], cache.kpos)
        if paged:
            new_cache = cache._replace(
                k=_paged_scatter(cache.k, cache.tbl, cache.layer, slot, k_new[:, 0]),
                v=_paged_scatter(cache.v, cache.tbl, cache.layer, slot, v_new[:, 0]),
                kpos=kpos)
            k = paged_view(new_cache.k, cache.tbl, cache.layer)
            v = paged_view(new_cache.v, cache.tbl, cache.layer)
        else:
            onehot = hit.astype(cache.k.dtype)[..., None, None]
            k = cache.k * (1 - onehot) + onehot * k_new
            v = cache.v * (1 - onehot) + onehot * v_new
            new_cache = KVCache(k=k, v=v, kpos=kpos)
    g = n_heads // n_kv
    qg = q.reshape(b, 1, n_kv, g, head_dim)
    if cross:
        mask = None
    else:
        valid = (kpos >= 0) & (kpos <= pos[:, None])
        if window is not None:
            valid = valid & (kpos > (pos[:, None] - window))
        mask = jnp.where(valid, 0.0, _NEG)[:, None, None, None, :]  # [B,1,1,1,Smax]
    out = _sdpa(qg, k, v, mask)
    out = out.reshape(b, 1, n_heads * head_dim)
    return site_linear(executor, sn("o"), p["o"], out.astype(x.dtype)), new_cache


def attention_extend(p, x, positions, past_k, past_v, past_kpos, *,
                     n_heads: int, n_kv: int, head_dim: int,
                     rope_theta: float | None = 10000.0):
    """Prefill continuation against a resident KV prefix (prefix-cache hit).

    ``x`` [B,T,d] are the unmatched tail tokens at absolute ``positions``
    [B,T]; ``past_k``/``past_v`` [B,C,Hkv,Dh] is the gathered prefix (already
    rotary-encoded at its own positions, exactly as the pool stores it) with
    validity mask ``past_kpos`` [B,C] (-1 = padding).  Returns
    ``(out [B,T,d], k_tail, v_tail)`` — only the tail K/V, for scatter into
    freshly allocated blocks.  Causal, non-windowed."""
    b, t, _ = x.shape
    g = n_heads // n_kv
    q = linear(p["q"], x).reshape(b, t, n_heads, head_dim)
    k_t = linear(p["k"], x).reshape(b, t, n_kv, head_dim)
    v_t = linear(p["v"], x).reshape(b, t, n_kv, head_dim)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k_t = apply_rope(k_t, positions, rope_theta)
    k = jnp.concatenate([past_k, k_t], axis=1)
    v = jnp.concatenate([past_v, v_t], axis=1)
    kpos = jnp.concatenate([past_kpos, positions], axis=1)  # [B, C+T]
    valid = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= positions[:, :, None])
    mask = jnp.where(valid, 0.0, _NEG)[:, None, None]  # [B,1,1,T,C+T]
    qg = q.reshape(b, t, n_kv, g, head_dim)
    out = _sdpa(qg, k, v, mask).reshape(b, t, n_heads * head_dim)
    return linear(p["o"], out.astype(x.dtype)), k_t, v_t


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV cache
# ---------------------------------------------------------------------------


class MLACache(NamedTuple):
    c_kv: jnp.ndarray  # [B, Smax, dc]     compressed KV latents
    k_rope: jnp.ndarray  # [B, Smax, Dr]   shared rotary key branch
    kpos: jnp.ndarray  # [B, Smax]


class MLARope(NamedTuple):
    """Static rope of the MLA rotary branch: inverse frequencies [Dr/2], the
    factor on cos/sin, and the softmax scale of the scores."""
    inv_freq: tuple
    cos_scale: float
    softmax_scale: float


def mla_rope(qk_nope: int, qk_rope: int, rope_theta: float, yarn=None
             ) -> MLARope:
    """DeepSeek-V2's MLA rope: YaRN frequencies over the ``qk_rope`` dims,
    cos/sin times mscale(mscale) / mscale(mscale_all_dim), and the softmax
    scale (qk_nope + qk_rope)^-1/2 times mscale(mscale_all_dim)^2 (1.5896 at
    factor 40, mscale_all_dim 0.707)."""
    inv = tuple(float(f) for f in rope_inv_freq(qk_rope, rope_theta, yarn))
    scale = (qk_nope + qk_rope) ** -0.5
    cos_scale = 1.0
    if yarn is not None:
        m_all = yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        cos_scale = yarn_mscale(yarn.factor, yarn.mscale) / m_all
        scale *= m_all * m_all
    return MLARope(inv, cos_scale, scale)


def init_mla(key, d_model: int, n_heads: int, *, kv_lora: int, qk_nope: int,
             qk_rope: int, v_dim: int, dtype):
    ks = jax.random.split(key, 6)
    return {
        "q": dense_init(ks[0], d_model, n_heads * (qk_nope + qk_rope), dtype),
        "dkv": dense_init(ks[1], d_model, kv_lora, dtype),
        # kv_a_layernorm: RMS norm of the latent before it is cached/expanded
        "kv_norm": jnp.ones((kv_lora,), dtype),
        "kr": dense_init(ks[2], d_model, qk_rope, dtype),
        "uk": dense_init(ks[3], kv_lora, n_heads * qk_nope, dtype),
        "uv": dense_init(ks[4], kv_lora, n_heads * v_dim, dtype),
        "o": dense_init(ks[5], n_heads * v_dim, d_model, dtype),
    }


def _mla_q(p, x, positions, n_heads, qk_nope, qk_rope, rope: MLARope,
           executor=None, site=None):
    """The queries' two parts: ``q_nope`` [B,S,H,qk_nope] and the rotated
    ``q_rope`` [B,S,H,Dr]."""
    b, s, _ = x.shape
    q = site_linear(executor, site_fmt(site)("q"), p["q"], x).reshape(
        b, s, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    return q_nope, apply_rope_pairs(q_rope, positions, rope.inv_freq,
                                    rope.cos_scale)


def _mla_qkv(p, x, c_kv, k_rope_src, positions, kpositions, n_heads, qk_nope, qk_rope, v_dim,
             rope: MLARope, executor=None, site=None):
    """``c_kv`` is the normed latent (as cached); ``k_rope_src`` the rotary
    key branch before rope (as cached)."""
    b, s, _ = x.shape
    sk = c_kv.shape[1]
    sn = site_fmt(site)
    q_nope, q_rope = _mla_q(p, x, positions, n_heads, qk_nope, qk_rope, rope,
                            executor, site)
    # uk/uv share the latent-cache input: one grouped launch when compressed
    uk, uv = site_linear_group(executor, (sn("uk"), sn("uv")),
                               (p["uk"], p["uv"]), c_kv)
    k_nope = constrain(uk.reshape(b, sk, n_heads, qk_nope),
                       "batch", None, "model", None)
    v = constrain(uv.reshape(b, sk, n_heads, v_dim),
                  "batch", None, "model", None)
    k_rope = apply_rope_pairs(k_rope_src[:, :, None, :], kpositions,
                              rope.inv_freq, rope.cos_scale)  # [B,Sk,1,Dr]
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, sk, n_heads, qk_rope))], axis=-1)
    return q_full, k_full, v


def mla_prefill(p, x, positions, *, n_heads, kv_lora, qk_nope, qk_rope, v_dim,
                rope: MLARope, q_chunk: int = 1024, unroll_chunks: bool = False,
                causal_skip: bool = False):
    b, s, _ = x.shape
    c_kv = rms_norm(linear(p["dkv"], x), p["kv_norm"])  # [B,S,dc]
    k_rope_src = linear(p["kr"], x)  # [B,S,Dr]
    q, k, v = _mla_qkv(p, x, c_kv, k_rope_src, positions, positions, n_heads,
                       qk_nope, qk_rope, v_dim, rope)
    # MLA heads are full multi-head (n_kv == n_heads): reuse the GQA kernel path
    qg = q.reshape(b, s, n_heads, 1, qk_nope + qk_rope)
    kpos = jnp.arange(s)

    def chunk_out(q_c, qpos_c, k_c, v_c, kpos_c):
        m = (kpos_c[None, :] <= qpos_c[:, None])
        mask = jnp.where(m, 0.0, _NEG)[None, None, None]
        return _sdpa(q_c, k_c, v_c, mask, rope.softmax_scale)

    n_chunks = max(1, s // q_chunk) if s % q_chunk == 0 else 1
    if n_chunks == 1:
        out = chunk_out(qg, positions[0], k, v, kpos)
    elif unroll_chunks:
        cq = s // n_chunks
        outs = []
        for i in range(n_chunks):
            hi = (i + 1) * cq if causal_skip else s
            outs.append(chunk_out(qg[:, i * cq:(i + 1) * cq], positions[0, i * cq:(i + 1) * cq],
                                  k[:, :hi], v[:, :hi], kpos[:hi]))
        out = jnp.concatenate(outs, axis=1)
    else:
        cq = s // n_chunks
        qg_r = jnp.moveaxis(qg.reshape(b, n_chunks, cq, n_heads, 1, -1), 1, 0)
        qpos_r = positions[0].reshape(n_chunks, cq)

        def body(_, qc):
            q_c, qpos_c = qc
            return None, chunk_out(q_c, qpos_c, k, v, kpos)

        _, outs = jax.lax.scan(body, None, (qg_r, qpos_r))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, n_heads, 1, v_dim)

    out = out.reshape(b, s, n_heads * v_dim)
    return linear(p["o"], out.astype(x.dtype)), c_kv, k_rope_src


def mla_decode(p, x, cache: MLACache, pos, *, n_heads, kv_lora, qk_nope, qk_rope,
               v_dim, rope: MLARope, executor=None, site: str | None = None):
    """``cache`` may be a :class:`PagedMLACache` — the new latent is
    scattered into its pool block first and the contiguous logical view is
    gathered from the updated pools (cf. attention_decode).

    The heads attend to the latent directly (:func:`_mla_absorbed`) unless
    ``executor`` binds ``uk`` or ``uv`` as compressed chains: the latent is
    then expanded to per-head keys and values through them, as prefill
    does.  Each trace counts its route in ``mla_decode_routes_total``."""
    b = x.shape[0]
    paged = isinstance(cache, PagedMLACache)
    sn = site_fmt(site)
    c_new, kr_new = site_linear_group(executor, (sn("dkv"), sn("kr")),
                                      (p["dkv"], p["kr"]), x)  # [B,1,dc/Dr]
    c_new = rms_norm(c_new, p["kv_norm"])
    hit = jnp.arange(cache.kpos.shape[1])[None] == pos[:, None]
    kpos = jnp.where(hit, pos[:, None], cache.kpos)
    if paged:
        new_cache = cache._replace(
            c_kv=_paged_scatter(cache.c_kv, cache.tbl, cache.layer, pos, c_new[:, 0]),
            k_rope=_paged_scatter(cache.k_rope, cache.tbl, cache.layer, pos,
                                  kr_new[:, 0]),
            kpos=kpos)
        c_kv = paged_view(new_cache.c_kv, cache.tbl, cache.layer)
        k_rope = paged_view(new_cache.k_rope, cache.tbl, cache.layer)
    else:
        onehot = hit.astype(cache.c_kv.dtype)[..., None]
        c_kv = cache.c_kv * (1 - onehot) + onehot * c_new
        k_rope = cache.k_rope * (1 - onehot) + onehot * kr_new
        new_cache = MLACache(c_kv=c_kv, k_rope=k_rope, kpos=kpos)

    kpositions = jnp.maximum(kpos, 0)
    valid = (kpos >= 0) & (kpos <= pos[:, None])
    # a chain bound to uk or uv has no transposed form to fold into the query
    if executor is None or (executor.matvec(sn("uk")) is None
                            and executor.matvec(sn("uv")) is None):
        record_mla_decode_route("absorbed")
        out = _mla_absorbed(p, x, c_kv, k_rope, pos, kpositions, valid,
                            n_heads, qk_nope, qk_rope, v_dim, rope,
                            executor, site)
    else:
        record_mla_decode_route("expanded")
        q, k, v = _mla_qkv(p, x, c_kv, k_rope, pos[:, None], kpositions,
                           n_heads, qk_nope, qk_rope, v_dim, rope,
                           executor=executor, site=site)
        qg = q.reshape(b, 1, n_heads, 1, qk_nope + qk_rope)
        mask = jnp.where(valid, 0.0, _NEG)[:, None, None, None, :]
        out = _sdpa(qg, k, v, mask, rope.softmax_scale)
    out = out.reshape(b, 1, n_heads * v_dim)
    return site_linear(executor, sn("o"), p["o"], out.astype(x.dtype)), new_cache


def _mla_absorbed(p, x, c_kv, k_rope_src, pos, kpositions, valid, n_heads,
                  qk_nope, qk_rope, v_dim, rope: MLARope, executor, site):
    """One decode token's attention on the latent itself (DeepSeek-V2,
    arXiv:2405.04434 §2.1.3): ``q_nope · (c W_uk)ᵀ = (q_nope W_ukᵀ) · cᵀ``
    and ``probs · (c W_uv) = (probs · c) W_uv``, so no per-head key or value
    over the cache is formed and the rotary term scores the one shared
    rotary key.  The two contractions over the cache read the view ``c_kv``
    [B,T,dc] and the rotary keys in their own dtype and accumulate in f32.
    Returns the heads' outputs [B,H,v_dim] in f32."""
    dc, dt, f32 = c_kv.shape[-1], c_kv.dtype, jnp.float32
    q_nope, q_rope = _mla_q(p, x, pos[:, None], n_heads, qk_nope, qk_rope,
                            rope, executor, site)
    w_uk = p["uk"]["w"].reshape(dc, n_heads, qk_nope)
    w_uv = p["uv"]["w"].reshape(dc, n_heads, v_dim)
    k_rope = apply_rope_pairs(k_rope_src[:, :, None, :], kpositions,
                              rope.inv_freq, rope.cos_scale)[:, :, 0]
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk,
                       preferred_element_type=f32)
    scores = (jnp.einsum("bhc,btc->bht", q_lat.astype(dt), c_kv,
                         preferred_element_type=f32)
              + jnp.einsum("bhr,btr->bht", q_rope[:, 0].astype(dt), k_rope,
                           preferred_element_type=f32))
    scores = scores * rope.softmax_scale + jnp.where(valid, 0.0, _NEG)[:, None]
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bht,btc->bhc", probs.astype(dt), c_kv,
                       preferred_element_type=f32)
    return jnp.einsum("bhc,che->bhe", o_lat.astype(w_uv.dtype), w_uv,
                      preferred_element_type=f32)


def mla_extend(p, x, positions, past_c, past_kr, past_kpos, *, n_heads,
               qk_nope, qk_rope, v_dim, rope: MLARope):
    """MLA prefill continuation against a resident latent prefix.

    ``past_c`` [B,C,dc] / ``past_kr`` [B,C,Dr] are the gathered compressed-KV
    prefix (pool layout: pre-rope rotary branch, latent as stored), masked by
    ``past_kpos`` [B,C].  Returns ``(out, c_tail, kr_tail)``."""
    b, t, _ = x.shape
    c_t = rms_norm(linear(p["dkv"], x), p["kv_norm"])  # [B,T,dc]
    kr_t = linear(p["kr"], x)  # [B,T,Dr]
    c_all = jnp.concatenate([past_c, c_t], axis=1)
    kr_all = jnp.concatenate([past_kr, kr_t], axis=1)
    kpos = jnp.concatenate([past_kpos, positions], axis=1)  # [B, C+T]
    q, k, v = _mla_qkv(p, x, c_all, kr_all, positions, jnp.maximum(kpos, 0),
                       n_heads, qk_nope, qk_rope, v_dim, rope)
    valid = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= positions[:, :, None])
    mask = jnp.where(valid, 0.0, _NEG)[:, None, None]  # [B,1,1,T,C+T]
    qg = q.reshape(b, t, n_heads, 1, qk_nope + qk_rope)
    out = _sdpa(qg, k, v, mask, rope.softmax_scale).reshape(b, t, n_heads * v_dim)
    return linear(p["o"], out.astype(x.dtype)), c_t, kr_t
