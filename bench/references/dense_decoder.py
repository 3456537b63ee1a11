"""Plain reference of a dense decoder-only transformer (OLMo, Llama, Yi).

Written from the published description, in straightforward ``jax.numpy`` at
float32 and highest matmul precision, with no kernel, cache, batching or
paging, and nothing imported from the program under test:

    x = E[tokens]
    per layer:  h = norm(x)
                q, k, v = h Wq, h Wk, h Wv        (heads of head_dim; GQA
                                                   query head i reads key/
                                                   value head i // (H / Hkv))
                q, k = rope(q), rope(k)           (rotate-half, theta^(-j/half))
                x += softmax(q k^T / sqrt(head_dim) + causal) v  Wo
                h = norm(x)
                x += (silu(h Wg) * (h Wu)) Wd
    logits = norm(x) E^T   (tied; else norm(x) W_head)

``norm`` is OLMo's non-parametric layer norm (mean and variance, no affine,
eps 1e-5) or RMS norm with a scale (eps 1e-6), as the configuration says.

``make_params`` builds seeded weights in the layout the serving program
takes them in (stacked ``[L, ...]`` blocks, ``w`` stored ``[in, out]``), in
one jitted call on the device.  ``forward_logits`` reads them as float32.
With ``quant="fp8"`` every projection computes in float8 e4m3 — its weight
rounded with one scale per output channel, its input with one scale per
row — and accumulates in float32: the lower precision the benchmark's
control computes in.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["Spec", "spec_from_config", "make_params", "forward_logits",
           "quantize_fp8"]


@dataclass(frozen=True)
class Spec:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm: str  # "nonparametric" | "rms"
    rope_theta: float


def spec_from_config(c: dict) -> Spec:
    h = int(c["num_attention_heads"])
    d = int(c["hidden_size"])
    return Spec(layers=int(c["num_hidden_layers"]), d_model=d, heads=h,
                kv_heads=int(c.get("num_key_value_heads", h)),
                head_dim=int(c.get("head_dim") or d // h),
                d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
                tied=bool(c.get("tie_word_embeddings", False)),
                norm=c["norm"], rope_theta=float(c.get("rope_theta", 1e4)))


def _dense(key, shape, fan_in, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w / math.sqrt(fan_in)).astype(dtype)


@partial(jax.jit, static_argnames=("spec", "dtype"))
def _make(key, spec: Spec, dtype):
    L, d, ff = spec.layers, spec.d_model, spec.d_ff
    qd, kvd = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    ks = jax.random.split(key, 9)
    ones = lambda *s: jnp.ones(s, dtype)  # noqa: E731
    p = {
        "embed": (jax.random.normal(ks[0], (spec.vocab, d), jnp.float32)
                  * d ** -0.5).astype(dtype),
        "final_ln": ones(d),
        "blocks": {
            "ln1": ones(L, d), "ln2": ones(L, d),
            "attn": {"q": {"w": _dense(ks[1], (L, d, qd), d, dtype)},
                     "k": {"w": _dense(ks[2], (L, d, kvd), d, dtype)},
                     "v": {"w": _dense(ks[3], (L, d, kvd), d, dtype)},
                     "o": {"w": _dense(ks[4], (L, qd, d), qd, dtype)}},
            "ffn": {"gate": {"w": _dense(ks[5], (L, d, ff), d, dtype)},
                    "up": {"w": _dense(ks[6], (L, d, ff), d, dtype)},
                    "down": {"w": _dense(ks[7], (L, ff, d), ff, dtype)}},
        },
    }
    if not spec.tied:
        p["lm_head"] = {"w": _dense(ks[8], (d, spec.vocab), d, dtype)}
    return p


def make_params(spec: Spec, seed_u32: int, dtype=jnp.bfloat16):
    """Seeded weights, program layout, made on the device in one call."""
    return _make(jax.random.PRNGKey(seed_u32), spec, dtype)


def quantize_fp8(w, axis: int):
    """Round ``w`` to float8 e4m3 with one scale per slice along every axis
    but ``axis`` (the reduced, input axis); returns float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _norm(spec: Spec, x, w):
    if spec.norm == "nonparametric":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * w


def _rope(x, pos, theta: float):
    """x [T, H, D]: rotate the two halves of D by pos * theta^(-j / (D/2))."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@partial(jax.jit, static_argnames=("spec", "quant"))
def forward_logits(params, spec: Spec, tokens, quant: str | None = None):
    """Logits [T, V] (float32) of every position of ``tokens`` [T]."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown reference precision {quant!r}")

    def mm(x, w):
        """x @ w, both rounded to float8 under ``quant``."""
        if quant == "fp8":
            return quantize_fp8(x, axis=-1) @ quantize_fp8(w, axis=-2)
        return x @ w.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        emb = params["embed"].astype(jnp.float32)
        t = tokens.shape[0]
        pos = jnp.arange(t)
        x = emb[tokens]
        causal = pos[None, :] <= pos[:, None]
        g = spec.heads // spec.kv_heads

        def layer(x, bp):
            a, f = bp["attn"], bp["ffn"]
            h = _norm(spec, x, bp["ln1"].astype(jnp.float32))
            q = mm(h, a["q"]["w"]).reshape(t, spec.heads, spec.head_dim)
            k = mm(h, a["k"]["w"]).reshape(t, spec.kv_heads, spec.head_dim)
            v = mm(h, a["v"]["w"]).reshape(t, spec.kv_heads, spec.head_dim)
            q, k = _rope(q, pos, spec.rope_theta), _rope(k, pos, spec.rope_theta)
            k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(spec.head_dim)
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = x + mm(o.reshape(t, -1), a["o"]["w"])
            h = _norm(spec, x, bp["ln2"].astype(jnp.float32))
            up = jax.nn.silu(mm(h, f["gate"]["w"])) * mm(h, f["up"]["w"])
            return x + mm(up, f["down"]["w"]), None

        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = _norm(spec, x, params["final_ln"].astype(jnp.float32))
        return mm(x, emb.T if spec.tied else params["lm_head"]["w"])
