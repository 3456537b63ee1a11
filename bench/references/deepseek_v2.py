"""Plain reference of DeepSeek-V2 (arXiv:2405.04434): multi-head latent
attention with YaRN rope, a leading dense SwiGLU layer, then mixture-of-
experts layers with shared experts.

Written from the published description and the model's public
``config.json``, in straightforward ``jax.numpy`` at float32 and highest
matmul precision, with no kernel, cache, batching or paging, and nothing
imported from the program under test:

    x = E[tokens]
    per layer:  h = rms(x) * ln1
                q = h Wq                     [T, H, nope + rope]
                c = rms(h Wdkv) * kv_norm    the latent (kv_a_layernorm)
                k_pe = h Wkr                 [T, rope], one for all heads
                k_nope = c Wuk, v = c Wuv    [T, H, nope], [T, H, v]
                q_pe, k_pe = rope(q_pe), rope(k_pe)
                s = (q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-1/2 * m^2
                x += softmax(s + causal) v  Wo
                h = rms(x) * ln2
                dense layer:  x += (silu(h Wg) * (h Wu)) Wd
                MoE layer:    p = softmax(h Wr) over the E experts; the top-k
                              of p keep their weights (not renormalised), the
                              rest weigh 0; every token runs through all E
                              experts, x += sum_e w_e (silu(h Wg_e) *
                              (h Wu_e)) Wd_e + shared SwiGLU(h)
    logits = (rms(x) * final_ln) W_head

rope is DeepSeek-V2's: the 64 rotary channels are read as interleaved pairs
(2i, 2i+1), permuted to halves and rotated with ``rotate_half`` at YaRN's
frequencies (``DeepseekV2YarnRotaryEmbedding``): theta^(-2i/64), divided by
``factor`` where a band turns fewer than ``beta_slow`` times over the original
context, kept where it turns more than ``beta_fast`` times, a linear ramp
between; cos and sin are scaled by mscale(mscale) / mscale(mscale_all_dim),
and m = mscale(mscale_all_dim) with mscale(s) = 0.1 s ln(factor) + 1.  RMS
norms use eps 1e-6.

``make_params`` builds seeded weights in the program's layout
(``dense_blocks`` stacked ``[n_dense, ...]``, ``blocks`` stacked over the MoE
layers, experts ``[L, E, in, out]``, ``w`` stored ``[in, out]``, the router in
float32), one leading layer at a time on the device.  ``forward_logits``
reads them as float32 one layer at a time, so a bf16 model that fills most of
a chip can be checked on it.  With ``quant="fp8"`` every projection,
experts and router included, computes in float8 e4m3 — its weight rounded
with one scale per output channel, its input with one scale per row — and
accumulates in float32: the lower precision the benchmark's control computes
in.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Spec", "spec_from_config", "make_params", "forward_logits",
           "quantize_fp8", "yarn_inv_freq"]

EPS = 1e-6


@dataclass(frozen=True)
class Spec:
    layers: int
    n_dense: int
    d_model: int
    heads: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    d_ff: int
    d_expert: int
    n_experts: int
    top_k: int
    n_shared: int
    norm_topk: bool
    vocab: int
    rope_theta: float
    # YaRN: (factor, original_max_position, beta_fast, beta_slow, mscale,
    # mscale_all_dim), or None
    yarn: tuple | None


def spec_from_config(c: dict) -> Spec:
    """From the model's ``config.json`` keys."""
    rs = c.get("rope_scaling")
    yarn = None
    if rs is not None:
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling {rs!r}: only yarn is described")
        yarn = (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
                float(rs["beta_fast"]), float(rs["beta_slow"]),
                float(rs["mscale"]), float(rs["mscale_all_dim"]))
    if c.get("q_lora_rank") is not None:
        raise ValueError("q_lora_rank: the query projection is described "
                         "without a low-rank factor")
    return Spec(layers=int(c["num_hidden_layers"]),
                n_dense=int(c["first_k_dense_replace"]),
                d_model=int(c["hidden_size"]),
                heads=int(c["num_attention_heads"]),
                kv_lora=int(c["kv_lora_rank"]),
                qk_nope=int(c["qk_nope_head_dim"]),
                qk_rope=int(c["qk_rope_head_dim"]),
                v_dim=int(c["v_head_dim"]),
                d_ff=int(c["intermediate_size"]),
                d_expert=int(c["moe_intermediate_size"]),
                n_experts=int(c["n_routed_experts"]),
                top_k=int(c["num_experts_per_tok"]),
                n_shared=int(c["n_shared_experts"]),
                norm_topk=bool(c["norm_topk_prob"]),
                vocab=int(c["vocab_size"]),
                rope_theta=float(c["rope_theta"]), yarn=yarn)


# ---------------------------------------------------------------- weights


def _normal(key, shape, fan_in):
    """truncated normal(-2, 2) / sqrt(fan_in), float32."""
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) / math.sqrt(fan_in)


@partial(jax.jit, static_argnames=("shape", "fan_in", "dtype", "kind"))
def _stack(key, shape, fan_in, dtype, kind="w"):
    """A ``[n, ...]`` stack made one leading row at a time (lax.map), so no
    more than one row is ever in float32."""
    n = shape[0]

    def row(k):
        if kind == "norm":  # RMS-norm scales near 1, not all 1
            return (1.0 + 0.1 * jax.random.truncated_normal(
                k, -2.0, 2.0, shape[1:], jnp.float32)).astype(dtype)
        return _normal(k, shape[1:], fan_in).astype(dtype)

    return jax.lax.map(row, jax.random.split(key, n))


def _block_params(key, spec: Spec, n: int, dense: bool, dtype):
    d, h = spec.d_model, spec.heads
    qd = h * (spec.qk_nope + spec.qk_rope)
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in, dt=dtype):
        return {"w": _stack(next(ks), (n,) + shape, fan_in, dt)}

    def norm(dim):
        return _stack(next(ks), (n, dim), 1, dtype, kind="norm")

    p = {"ln1": norm(d), "ln2": norm(d),
         "attn": {"q": w((d, qd), d), "dkv": w((d, spec.kv_lora), d),
                  "kr": w((d, spec.qk_rope), d),
                  "kv_norm": norm(spec.kv_lora),
                  "uk": w((spec.kv_lora, h * spec.qk_nope), spec.kv_lora),
                  "uv": w((spec.kv_lora, h * spec.v_dim), spec.kv_lora),
                  "o": w((h * spec.v_dim, d), h * spec.v_dim)}}
    if dense:
        ff = spec.d_ff
        p["ffn"] = {"gate": w((d, ff), d), "up": w((d, ff), d),
                    "down": w((ff, d), ff)}
        return p
    e, fe, fs = spec.n_experts, spec.d_expert, spec.n_shared * spec.d_expert
    p["ffn"] = {
        "router": _stack(next(ks), (n, d, e), d, jnp.float32),
        "gate": _stack(next(ks), (n, e, d, fe), d, dtype),
        "up": _stack(next(ks), (n, e, d, fe), d, dtype),
        "down": _stack(next(ks), (n, e, fe, d), fe, dtype),
    }
    if spec.n_shared:
        p["ffn"]["shared"] = {"gate": w((d, fs), d), "up": w((d, fs), d),
                              "down": w((fs, d), fs)}
    return p


@partial(jax.jit, static_argnames=("shape", "fan_in", "dtype"))
def _whole(key, shape, fan_in, dtype):
    return _normal(key, shape, fan_in).astype(dtype)


def make_params(spec: Spec, seed_u32: int, dtype=jnp.bfloat16):
    """Seeded weights, program layout, made on the device."""
    ks = jax.random.split(jax.random.PRNGKey(seed_u32), 5)
    d, v = spec.d_model, spec.vocab
    p = {"embed": _whole(ks[0], (v, d), d, dtype),
         "final_ln": _stack(ks[1], (1, d), 1, dtype, kind="norm")[0],
         "lm_head": {"w": _whole(ks[2], (d, v), d, dtype)}}
    if spec.n_dense:
        p["dense_blocks"] = _block_params(ks[3], spec, spec.n_dense, True,
                                          dtype)
    if spec.layers > spec.n_dense:
        p["blocks"] = _block_params(ks[4], spec, spec.layers - spec.n_dense,
                                    False, dtype)
    return p


# ---------------------------------------------------------------- forward


def quantize_fp8(w, axis: int):
    """Round ``w`` to float8 e4m3 with one scale per slice along every axis
    but ``axis`` (the reduced, input axis); returns float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, yarn) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies [dim/2]."""
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return freq_extra
    factor, orig, beta_fast, beta_slow = yarn[:4]
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float64) / dim))

    def find_dim(num_rot):
        return (dim * math.log(orig / (num_rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return freq_inter * (1.0 - mask) + freq_extra * mask


def _rms(x, w):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, cos, sin):
    """x [T, H, D] interleaved pairs -> halves, then x cos + rot(x) sin."""
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(t, h, d)
    return x * cos[:, None, :] + _rotate_half(x) * sin[:, None, :]


@partial(jax.jit, static_argnames=("spec", "quant"))
def forward_logits(params, spec: Spec, tokens, quant: str | None = None):
    """Logits [T, V] (float32) of every position of ``tokens`` [T]."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown reference precision {quant!r}")
    f32 = jnp.float32

    def mm(x, w):
        """x @ w, both rounded to float8 under ``quant``."""
        if quant == "fp8":
            return quantize_fp8(x, axis=-1) @ quantize_fp8(w, axis=-2)
        return x @ w.astype(f32)

    def mm_experts(x, w):
        """x [T, in] through every expert's w [E, in, out] -> [T, E, out]."""
        if quant == "fp8":
            return jnp.einsum("ti,eio->teo", quantize_fp8(x, axis=-1),
                              quantize_fp8(w, axis=-2))
        return jnp.einsum("ti,eio->teo", x, w.astype(f32))

    h_, dn, dr = spec.heads, spec.qk_nope, spec.qk_rope
    t = tokens.shape[0]
    pos = jnp.arange(t)
    inv = jnp.asarray(yarn_inv_freq(dr, spec.rope_theta, spec.yarn), f32)
    scale = (dn + dr) ** -0.5
    cs = 1.0
    if spec.yarn is not None:
        m_all = yarn_mscale(spec.yarn[0], spec.yarn[5])
        scale *= m_all * m_all
        cs = yarn_mscale(spec.yarn[0], spec.yarn[4]) / m_all
    freqs = pos[:, None].astype(f32) * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb) * cs, jnp.sin(emb) * cs
    causal = pos[None, :] <= pos[:, None]

    def attention(x, a):
        h = x
        q = mm(h, a["q"]["w"]).reshape(t, h_, dn + dr)
        c = _rms(mm(h, a["dkv"]["w"]), a["kv_norm"].astype(f32))
        k_pe = mm(h, a["kr"]["w"]).reshape(t, 1, dr)
        k_nope = mm(c, a["uk"]["w"]).reshape(t, h_, dn)
        v = mm(c, a["uv"]["w"]).reshape(t, h_, spec.v_dim)
        q_pe = _rope(q[..., dn:], cos, sin)
        k_pe = _rope(k_pe, cos, sin)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe[:, 0])) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        return mm(o.reshape(t, -1), a["o"]["w"])

    def swiglu(h, f):
        return mm(jax.nn.silu(mm(h, f["gate"]["w"])) * mm(h, f["up"]["w"]),
                  f["down"]["w"])

    def experts(h, f):
        probs = jax.nn.softmax(mm(h, f["router"]), axis=-1)  # [T, E]
        top, sel = jax.lax.top_k(probs, spec.top_k)
        if spec.norm_topk:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        w = jnp.zeros_like(probs).at[pos[:, None], sel].set(top)
        hid = (jax.nn.silu(mm_experts(h, f["gate"]))
               * mm_experts(h, f["up"]))  # [T, E, fe]: all experts, densely
        if quant == "fp8":
            out = jnp.einsum("tef,efd->ted", quantize_fp8(hid, axis=-1),
                             quantize_fp8(f["down"], axis=-2))
        else:
            out = jnp.einsum("tef,efd->ted", hid, f["down"].astype(f32))
        y = jnp.einsum("te,ted->td", w, out)
        if "shared" in f:
            y = y + swiglu(h, f["shared"])
        return y

    def layer(dense):
        def body(x, bp):
            x = x + attention(_rms(x, bp["ln1"].astype(f32)), bp["attn"])
            h = _rms(x, bp["ln2"].astype(f32))
            x = x + (swiglu(h, bp["ffn"]) if dense else experts(h, bp["ffn"]))
            return x, None
        return body

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32)
        if "dense_blocks" in params:
            x, _ = jax.lax.scan(layer(True), x, params["dense_blocks"])
        if "blocks" in params:
            x, _ = jax.lax.scan(layer(False), x, params["blocks"])
        x = _rms(x, params["final_ln"].astype(f32))
        return mm(x, params["lm_head"]["w"])
