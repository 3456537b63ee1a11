"""Shared model primitives: norms, rotary embeddings, FFNs, initializers.

Pure-functional JAX; parameters are nested dicts of arrays. Compute dtype and
accumulation dtype are explicit everywhere (bf16 compute / f32 accumulation by
default, matching the TPU deployment target).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.act_shard import constrain

__all__ = [
    "dense_init",
    "linear",
    "matvec_acts",
    "site_fmt",
    "site_linear",
    "site_linear_group",
    "rms_norm",
    "layer_norm",
    "non_parametric_ln",
    "apply_rope",
    "apply_mrope",
    "yarn_mscale",
    "rope_inv_freq",
    "apply_rope_pairs",
    "swiglu",
    "gelu_mlp",
]


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float | None = None,
               bias: bool = False):
    """Truncated-normal fan-in init (the standard for all projections here)."""
    if scale is None:
        scale = 1.0 / np.sqrt(in_dim)
    p = {"w": (jax.random.truncated_normal(key, -2, 2, (in_dim, out_dim)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def site_fmt(site):
    """Site-name binder for a format template like ``"attn.{}.l3"`` — returns
    a key -> site-name function (None template => every projection dense)."""
    return (lambda k: site.format(k)) if site is not None else (lambda k: None)


def matvec_acts(fn, x):
    """Run a features-major matvec (x [K, B] -> [N, B]) on [..., d] acts."""
    lead = x.shape[:-1]
    y = fn(x.reshape(-1, x.shape[-1]).astype(jnp.float32).T)
    return y.T.reshape(*lead, -1).astype(x.dtype)


def site_linear(executor, name, p, x):
    """``linear(p, x)``, routed through the compressed executor's fused-kernel
    matvec when it covers site ``name`` (dense weights otherwise).

    ``executor`` is duck-typed (see ``repro.serving.executor``): any object
    with ``matvec(name) -> callable | None``.  Bias (whisper projections) is
    applied on top of the compressed map — only ``w`` is a compressible site.
    """
    fn = executor.matvec(name) if executor is not None else None
    if fn is None:
        return linear(p, x)
    y = matvec_acts(fn, x)
    if "b" in p:
        y = y + p["b"]
    return y


def site_linear_group(executor, names, ps, xs):
    """Several projections of one *fused region* (same batch of activations:
    attention q/k/v, SwiGLU gate/up, RWKV r/k/v/g) in ONE grouped kernel
    launch when the executor covers every site; per-site
    :func:`site_linear` fallback otherwise.

    ``xs`` is either one shared activation array or a per-site list; returns
    the per-site outputs in order.
    """
    xlist = list(xs) if isinstance(xs, (list, tuple)) else [xs] * len(names)
    fused = executor.grouped(tuple(names)) if executor is not None else None
    if fused is None:
        return [site_linear(executor, n, p, x)
                for n, p, x in zip(names, ps, xlist)]
    lead = xlist[0].shape[:-1]
    flat = [x.reshape(-1, x.shape[-1]).astype(jnp.float32).T for x in xlist]
    ys = fused(flat)
    outs = []
    for y, p, x in zip(ys, ps, xlist):
        o = y.T.reshape(*lead, -1).astype(x.dtype)
        if "b" in p:
            o = o + p["b"]
        outs.append(o)
    return outs


def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * w + b


def non_parametric_ln(x, eps: float = 1e-5):
    """OLMo-style LayerNorm without learnable affine parameters."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def _rope_sincos(positions, dim: int, theta: float):
    """positions [...]: sin/cos [..., dim/2] in f32."""
    half = dim // 2
    freqs = jnp.exp(-np.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, positions, theta: float = 10000.0):
    """Rotary embedding. x [B, S, H, D], positions [B, S] (absolute)."""
    d = x.shape[-1]
    sin, cos = _rope_sincos(positions, d, theta)  # [B, S, d/2]
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, ``0.1 * mscale * ln(factor) + 1``
    (1 for no scaling)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * float(np.log(factor)) + 1.0


def rope_inv_freq(dim: int, theta: float, yarn=None) -> np.ndarray:
    """Inverse frequencies [dim/2] (float64) of a rotary branch of ``dim``
    channels: ``theta^(-2i/dim)``, blended by YaRN (``yarn``: a
    ``configs.base.YarnSpec``) between the original frequency and it divided
    by ``factor``.  Bands that turn more than ``beta_fast`` times over the
    original context keep their frequency, bands that turn fewer than
    ``beta_slow`` times are interpolated, with a linear ramp between (DeepSeek-
    V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    half = dim // 2
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return extra

    def corr_dim(rotations):
        return (dim * np.log(yarn.original_max_position
                             / (rotations * 2 * np.pi))) / (2 * np.log(theta))

    low = max(int(np.floor(corr_dim(yarn.beta_fast))), 0)
    high = min(int(np.ceil(corr_dim(yarn.beta_slow))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp  # 1: original frequency, 0: interpolated
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def apply_rope_pairs(x, positions, inv_freq, scale: float = 1.0):
    """Rotary embedding in DeepSeek-V2's layout: x [B, S, H, D] is read as
    interleaved pairs (2i, 2i+1), permuted to halves (i, i + D/2) and rotated
    by ``positions`` [B, S] x ``inv_freq`` [D/2]; ``scale`` multiplies cos and
    sin (YaRN's mscale ratio).  The output stays in the halves layout: rotated
    queries and keys only meet in dot products, which the permutation leaves
    unchanged."""
    b, s, h, d = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, h, d // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)  # [B, S, D/2]
    cos = (jnp.cos(ang) * scale)[:, :, None, :]
    sin = (jnp.sin(ang) * scale)[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections: tuple[int, int, int], theta: float = 10000.0):
    """Qwen2-VL multimodal RoPE. x [B, S, H, D], positions3 [3, B, S]
    (temporal / height / width position ids); ``sections`` split D/2 rotary
    frequencies among the three axes (sum(sections) == D // 2)."""
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = jnp.exp(-np.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    # choose which positional axis drives each frequency band
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=half)  # [half]
    pos = positions3.astype(jnp.float32)  # [3, B, S]
    pos_sel = pos[sec_id]  # [half, B, S] — gather the driving axis per band
    ang = jnp.moveaxis(pos_sel, 0, -1) * freqs  # [B, S, half]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(p, x):
    """SwiGLU FFN: down( silu(gate(x)) * up(x) ). TP: d_ff sharded on "model"."""
    g = constrain(linear(p["gate"], x), "batch", None, "model")
    u = constrain(linear(p["up"], x), "batch", None, "model")
    y = linear(p["down"], jax.nn.silu(g) * u)
    return constrain(y, "batch", None, None)


def gelu_mlp(p, x):
    """Two-layer GELU MLP (whisper-style). TP: d_ff sharded on "model"."""
    h = constrain(linear(p["fc1"], x), "batch", None, "model")
    return constrain(linear(p["fc2"], jax.nn.gelu(h)), "batch", None, None)
