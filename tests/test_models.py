"""Per-arch smoke tests (reduced configs) + prefill/decode consistency.

Smoke: one forward/train step on CPU, output shapes + no NaNs — required per
assigned architecture.  Consistency: decoding token-by-token from an empty
cache must reproduce the teacher-forced forward logits — this validates every
cache/recurrence implementation (GQA, SWA ring, MLA, Mamba2, RWKV6, whisper
cross-attention) against the chunked prefill math.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduced_config
from repro.models import api, transformer

ALL_ARCHS = sorted(ARCHS)


def _batch_for(cfg, b=2, s=32):
    if cfg.enc_layers:
        return {"frames": jnp.asarray(np.random.default_rng(0).standard_normal(
                    (b, s, cfg.d_model)), cfg.cdtype),
                "tokens": jnp.zeros((b, 8), jnp.int32),
                "labels": jnp.ones((b, 8), jnp.int32)}
    if cfg.inputs == "embeds":
        return {"embeds": jnp.asarray(np.random.default_rng(0).standard_normal(
                    (b, s, cfg.d_model)), cfg.cdtype),
                "labels": jnp.ones((b, s), jnp.int32)}
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (b, s)), jnp.int32)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    cfg = reduced_config(get_arch(arch))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(cfg)
    loss, grads = jax.value_and_grad(lambda p: api.train_loss(p, cfg, batch))(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_decode_step(arch):
    cfg = reduced_config(get_arch(arch))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    state = api.init_decode_state(cfg, 2, 64)
    logits, new_state = api.decode(params, cfg, state,
                                   jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32))
    assert logits.shape == (2, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert jax.tree.structure(new_state) == jax.tree.structure(state)


CONSISTENCY_ARCHS = ["olmo-1b", "qwen2.5-3b", "llama3.2-3b", "yi-9b",
                     "rwkv6-1.6b", "zamba2-7b", "deepseek-v2-lite-16b",
                     "mixtral-8x22b", "qwen2-vl-7b"]


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_prefill_decode_consistency(arch):
    """Sequential decode from empty state == teacher-forced forward."""
    cfg = reduced_config(get_arch(arch))
    if cfg.inputs == "embeds":
        pytest.skip("embeds-input decode starts from token embeddings only")
    b, s = 2, 12
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    params = api.init_params(jax.random.PRNGKey(1), cfg)

    h, _ = transformer.forward(params, cfg, tokens=toks)
    ref_logits = np.asarray(transformer.logits_from_hidden(params, cfg, h))

    state = api.init_decode_state(cfg, b, 32)
    dec = jax.jit(lambda p, st, tok, pos: api.decode(p, cfg, st, tok, pos))
    got = []
    for t in range(s):
        logits, state = dec(params, state, toks[:, t:t + 1],
                            jnp.full((b,), t, jnp.int32))
        got.append(np.asarray(logits))
    got = np.stack(got, axis=1)  # [B, S, V]
    np.testing.assert_allclose(got, ref_logits, rtol=5e-2, atol=5e-3)


def test_sliding_window_ring_buffer():
    """With window w, decode must match a model that only sees the last w tokens."""
    cfg = reduced_config(get_arch("mixtral-8x22b"), attn_window=8)
    b, s = 1, 20
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    params = api.init_params(jax.random.PRNGKey(2), cfg)
    h, _ = transformer.forward(params, cfg, tokens=toks)
    ref_logits = np.asarray(transformer.logits_from_hidden(params, cfg, h))
    state = api.init_decode_state(cfg, b, s)  # ring buffer size = window = 8
    assert state["k"].shape[2] == 8
    dec = jax.jit(lambda p, st, tok, pos: api.decode(p, cfg, st, tok, pos))
    got = []
    for t in range(s):
        logits, state = dec(params, state, toks[:, t:t + 1],
                            jnp.full((b,), t, jnp.int32))
        got.append(np.asarray(logits))
    np.testing.assert_allclose(np.stack(got, 1), ref_logits, rtol=5e-2, atol=5e-3)


def test_whisper_decode_consistency():
    cfg = reduced_config(get_arch("whisper-small"))
    from repro.models import whisper
    b, s_enc, t_dec = 2, 16, 6
    rng = np.random.default_rng(5)
    frames = jnp.asarray(rng.standard_normal((b, s_enc, cfg.d_model)), cfg.cdtype)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, t_dec)), jnp.int32)
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    enc = whisper.encode(params, cfg, frames)
    h = whisper.decoder_forward(params, cfg, toks, enc)
    ref_logits = np.asarray(h @ params["embed"].T.astype(h.dtype))

    # build cross-KV per layer, then sequential decode
    state = whisper.init_decode_state(cfg, b, enc_len=s_enc)
    from repro.models.layers import layer_norm, linear
    ck, cv = [], []
    for li in range(cfg.n_layers):
        bp = jax.tree.map(lambda a: a[li], params["dec_blocks"])
        k = linear(bp["xattn"]["k"], enc).reshape(b, s_enc, cfg.n_kv_heads, cfg.hd)
        v = linear(bp["xattn"]["v"], enc).reshape(b, s_enc, cfg.n_kv_heads, cfg.hd)
        ck.append(k)
        cv.append(v)
    state["cross_k"] = jnp.stack(ck)
    state["cross_v"] = jnp.stack(cv)
    dec = jax.jit(lambda p, st, tok, pos: whisper.decode_step(p, cfg, st, tok, pos))
    got = []
    for t in range(t_dec):
        logits, state = dec(params, state, toks[:, t:t + 1],
                            jnp.full((b,), t, jnp.int32))
        got.append(np.asarray(logits))
    np.testing.assert_allclose(np.stack(got, 1), ref_logits, rtol=5e-2, atol=5e-3)


def test_unroll_matches_scan():
    cfg = reduced_config(get_arch("olmo-1b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(cfg, 2, 64)
    l1 = api.train_loss(params, cfg, batch, unroll=False)
    l2 = api.train_loss(params, cfg, batch, unroll=True)
    assert abs(float(l1) - float(l2)) < 1e-4


def test_causal_chunk_skip_matches_full():
    from dataclasses import replace
    cfg = reduced_config(get_arch("olmo-1b"), q_chunk=16)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(cfg, 2, 64)
    base = api.train_loss(params, cfg, batch, unroll=True)
    skip = api.train_loss(params, replace(cfg, causal_chunk_skip=True), batch, unroll=True)
    assert abs(float(base) - float(skip)) < 1e-4


def test_moe_manual_single_device_fallback():
    """Without a configured mesh, moe_ffn_manual must equal the global path."""
    from repro.models.moe import init_moe, moe_ffn, moe_ffn_manual
    rng = np.random.default_rng(0)
    p = init_moe(jax.random.PRNGKey(0), 32, 16, 4, 1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    y0, _ = moe_ffn(p, x, n_experts=4, top_k=2)
    y1, _ = moe_ffn_manual(p, x, n_experts=4, top_k=2, mesh=None)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-5, atol=1e-6)
