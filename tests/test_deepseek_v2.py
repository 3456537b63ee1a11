"""DeepSeek-V2's mechanisms at a tiny size on the CPU: multi-head latent
attention with ``kv_a_layernorm`` and YaRN rope, a leading dense layer, and
drop-free routed experts on the grouped Pallas launches (interpret mode),
served through ``ServingEngine``'s paged latent pool and compared with the
benchmark's plain float32 reference (``bench/references/deepseek_v2.py``)."""
import json
import math
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels.moe_group_matmul import moe_down, moe_gate_up
from repro.models import moe
from repro.models.attention import mla_rope
from repro.models.layers import rope_inv_freq
from repro.serving.engine import ServingEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _bench_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported as the harness imports it."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from harness.spec import load_module

    return load_module(BENCH.parent, kind, name)


# 1 dense + 2 MoE layers, 8 experts top-3 + 1 shared, small MLA dims, YaRN on
TINY = {"num_hidden_layers": 3, "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "num_experts_per_tok": 3, "n_shared_experts": 1, "vocab_size": 128,
        "serve_dtype": "float32"}


@pytest.fixture(scope="module")
def tiny():
    ref = _bench_module("references", "deepseek_v2")
    system = _bench_module("systems", "mla_moe")
    c = {**json.loads((BENCH / "configs" / "deepseek-v2-lite.json")
                      .read_text()), **TINY}
    spec = ref.spec_from_config(c)
    params = ref.make_params(spec, 1234, jnp.float32)
    return ref, spec, params, system.arch_config(c, "tiny-dsv2")


def _decode_rows(eng, prompt, n):
    """Greedy-decode ``n`` steps of a freshly submitted request through the
    engine's jitted decode over its paged pool: the logits rows of the
    positions len(prompt) - 1, ..., and the tokens they chose."""
    rid = eng.submit(prompt)
    slot = next(s for s, r in eng.slot_req.items() if r == rid)
    st, tok, pos = eng.state, prompt[-1], len(prompt)
    rows, toks = [], []
    for _ in range(n):
        logits, st = eng._decode(eng.params, st, eng._token_batch(slot, tok),
                                 eng._pos_batch(slot, pos - 1))
        row = np.asarray(logits[slot], np.float32)
        rows.append(row)
        tok, pos = int(row.argmax()), pos + 1
        toks.append(tok)
    eng.state = st
    eng.cancel(rid)
    return np.stack(rows), toks


def test_program_matches_reference(tiny):
    """Prefill, then cached paged decode, against the reference's full
    forward, for a cold prompt and for one that hits the prefix cache (the
    latent pool's tail-extend path).  Both sides compute in float32 and
    differ only in summation order (sorted expert rows against every expert
    densely, the per-step latent expansion): ~1e-6 of the logits' scale.
    The limit, 2e-4 of it, is far below what a dropped row, a swapped
    expert, a missing kv_norm, mscale or YaRN band moves (>= 1e-2)."""
    ref, spec, params, cfg = tiny
    assert cfg.n_dense_layers == 1 and cfg.moe.norm_topk is False
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64, kv_block=16)
    cold = [(7 * i + 3) % spec.vocab for i in range(21)]
    warm = cold[:16] + [(5 * i + 1) % spec.vocab for i in range(9)]
    for prompt, cached in ((cold, 0), (warm, 16)):
        rows, toks = _decode_rows(eng, prompt, 10)
        assert eng.results[max(eng.results)].stats["cached_tokens"] == cached
        full = np.asarray(ref.forward_logits(
            params, spec, jnp.asarray(prompt + toks[:-1], jnp.int32)))
        want = full[len(prompt) - 1:]
        scale = np.abs(want).max()
        assert np.abs(rows - want).max() <= 2e-4 * scale, (
            np.abs(rows - want).max(), scale)


def _expanded_mla_decode(p, x, cache, pos, *, n_heads, kv_lora, qk_nope,
                         qk_rope, v_dim, rope, executor=None, site=None):
    """The oracle: MLA decode as the published formula states it, the
    cached latent expanded through ``uk``/``uv`` to per-head keys and values
    over every position, then attention over them."""
    from repro.models import attention as at

    b = x.shape[0]
    c_new = at.rms_norm(at.linear(p["dkv"], x), p["kv_norm"])
    kr_new = at.linear(p["kr"], x)
    hit = jnp.arange(cache.kpos.shape[1])[None] == pos[:, None]
    kpos = jnp.where(hit, pos[:, None], cache.kpos)
    if isinstance(cache, at.PagedMLACache):
        new_cache = cache._replace(
            c_kv=at._paged_scatter(cache.c_kv, cache.tbl, cache.layer, pos,
                                   c_new[:, 0]),
            k_rope=at._paged_scatter(cache.k_rope, cache.tbl, cache.layer,
                                     pos, kr_new[:, 0]),
            kpos=kpos)
        c_kv = at.paged_view(new_cache.c_kv, cache.tbl, cache.layer)
        k_rope = at.paged_view(new_cache.k_rope, cache.tbl, cache.layer)
    else:
        onehot = hit.astype(cache.c_kv.dtype)[..., None]
        c_kv = cache.c_kv * (1 - onehot) + onehot * c_new
        k_rope = cache.k_rope * (1 - onehot) + onehot * kr_new
        new_cache = at.MLACache(c_kv=c_kv, k_rope=k_rope, kpos=kpos)
    q, k, v = at._mla_qkv(p, x, c_kv, k_rope, pos[:, None],
                          jnp.maximum(kpos, 0), n_heads, qk_nope, qk_rope,
                          v_dim, rope)
    valid = (kpos >= 0) & (kpos <= pos[:, None])
    mask = jnp.where(valid, 0.0, at._NEG)[:, None, None, None, :]
    out = at._sdpa(q.reshape(b, 1, n_heads, 1, qk_nope + qk_rope), k, v,
                   mask, rope.softmax_scale)
    return (at.linear(p["o"], out.reshape(b, 1, n_heads * v_dim)
                      .astype(x.dtype)), new_cache)


@pytest.mark.parametrize("kv_block", [None, 16])
def test_absorbed_decode_matches_expanded(tiny, kv_block, monkeypatch):
    """The engine's decode attends to the cached latent directly (``uk``
    folded into the query, ``uv`` applied after the probabilities weight
    the latent).  Over 8 steps of two partly filled slots beside an empty
    one (``kpos`` all -1, fed position -1), contiguous and paged, its
    logits match the expanded formula's <= 1e-4: both compute in float32
    and differ only in summation order."""
    from repro.models import transformer

    _, spec, params, cfg = tiny
    prompts = [[(7 * i + 3) % spec.vocab for i in range(21)],
               [(5 * i + 1) % spec.vocab for i in range(9)]]

    def logits():
        eng = ServingEngine(params, cfg, n_slots=3, max_len=64,
                            kv_block=kv_block)
        rids = [eng.submit(p) for p in prompts]
        slots = [next(s for s, r in eng.slot_req.items() if r == rid)
                 for rid in rids]
        st, rows = eng.state, []
        for step in range(8):
            tok = np.zeros((3, 1), np.int32)
            pos = np.full(3, -1, np.int32)
            for j, (s, p) in enumerate(zip(slots, prompts)):
                tok[s, 0] = (11 * step + 3 * j + 1) % spec.vocab
                pos[s] = len(p) + step
            out, st = eng._decode(eng.params, st, jnp.asarray(tok),
                                  jnp.asarray(pos))
            rows.append(np.asarray(out, np.float32))
        return np.stack(rows)

    absorbed = logits()
    monkeypatch.setattr(transformer, "mla_decode", _expanded_mla_decode)
    expanded = logits()
    assert np.isfinite(absorbed).all()
    assert np.abs(absorbed - expanded).max() <= 1e-4, (
        np.abs(absorbed - expanded).max(), np.abs(expanded).max())


def test_routing_is_drop_free(monkeypatch):
    """Every token of a batch of 64 picks the same 3 of 8 experts (a rigged
    router): capacity dispatch at factor 1.25 would keep 30 rows an expert
    and drop the rest.  Each token's output in the batch equals its output
    alone (same float32 arithmetic per row: the launch computes every row
    tile alike)."""
    p = moe.init_moe(jax.random.PRNGKey(0), 32, 16, 8, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32), jnp.float32)
    real = moe.route

    def rigged(router, xt, *, top_k, norm_topk):
        probs, _, _ = real(router, xt, top_k=top_k, norm_topk=norm_topk)
        sel = jnp.broadcast_to(jnp.arange(top_k), (xt.shape[0], top_k))
        return probs, jnp.take_along_axis(probs, sel, axis=1), sel

    monkeypatch.setattr(moe, "route", rigged)
    f = jax.jit(partial(moe.moe_ffn, n_experts=8, top_k=3, norm_topk=False))
    batch = np.asarray(f(p, x)[0])[0]
    alone = np.stack([np.asarray(f(p, x[:, t:t + 1])[0])[0, 0]
                      for t in range(64)])
    np.testing.assert_allclose(batch, alone, rtol=1e-6, atol=1e-6)
    # and every row reached its experts: the routed part is not zero
    shared = np.asarray(jax.vmap(lambda r: moe.swiglu(p["shared"], r))(x[0]))
    assert np.abs(batch - shared).min(axis=-1).max() > 0


@pytest.mark.parametrize("sizes", [
    [0, 5, 0, 200, 3, 0, 48, 0],   # empty groups; groups straddle row tiles
    [128, 0, 0, 0],                # one full tile, the rest empty
    [1, 1, 1, 1, 1, 1, 1, 1],      # every group inside one tile
    [130, 126, 0, 1],              # boundaries just past and before a tile
], ids=["empty-straddle", "one-group", "tiny-groups", "tile-edges"])
def test_grouped_launches_match_ragged_dot(sizes):
    """Both launches (interpret mode) against ``jax.lax.ragged_dot``, and
    their gradients against ragged_dot's (float32: summation order only)."""
    rng = np.random.default_rng(len(sizes))
    e, k, f, d = len(sizes), 256, 128, 256
    gs = jnp.asarray(sizes, jnp.int32)
    x = jnp.asarray(rng.normal(size=(sum(sizes), k)), jnp.float32)
    g, u = (jnp.asarray(rng.normal(size=(e, k, f)) / 16, jnp.float32)
            for _ in range(2))
    dw = jnp.asarray(rng.normal(size=(e, f, d)) / 16, jnp.float32)

    def ours(x, g):
        return moe_down(moe_gate_up(x, g, u, gs), dw, gs)

    def plain(x, g):
        with jax.default_matmul_precision("highest"):
            h = (jax.nn.silu(jax.lax.ragged_dot(x, g, gs))
                 * jax.lax.ragged_dot(x, u, gs))
            return jax.lax.ragged_dot(h, dw, gs)

    got, want = jax.jit(ours)(x, g), plain(x, g)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    gr = jax.jit(jax.grad(lambda *a: ours(*a).sum(), argnums=(0, 1)))(x, g)
    gw = jax.grad(lambda *a: plain(*a).sum(), argnums=(0, 1))(x, g)
    for a, b in zip(gr, gw):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, transcribed term by term."""
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def corr(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    lin = (np.arange(dim // 2) - low) / ((high + 0.001 if low == high else high)
                                         - low)
    inv_freq_mask = 1.0 - np.clip(lin, 0, 1)
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def test_yarn_and_mscale_match_published_formula():
    cfg = get_arch("deepseek-v2-lite-16b")
    y = cfg.yarn
    assert (y.factor, y.original_max_position, y.mscale) == (40.0, 4096, 0.707)
    got = rope_inv_freq(64, cfg.rope_theta, y)
    want = _published_yarn(64, 10000.0, 40.0, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # bands above beta_fast rotations keep theta^(-2i/d); the slowest are /40
    assert got[0] == 1.0 and np.isclose(got[-1], want[-1])
    assert np.isclose(got[-1] * 40, 10000.0 ** (-62 / 64))
    rope = mla_rope(cfg.mla.qk_nope, cfg.mla.qk_rope, cfg.rope_theta, y)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert np.isclose(rope.softmax_scale, 192 ** -0.5 * m * m)
    assert np.isclose(m * m, 1.5896, atol=1e-4)
    assert rope.cos_scale == 1.0  # mscale == mscale_all_dim


def test_published_config_layout():
    """deepseek-v2-lite-16b as its config.json states it."""
    cfg = get_arch("deepseek-v2-lite-16b")
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.d_ff) == (27, 1, 10944)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff_expert,
            cfg.moe.n_shared, cfg.moe.norm_topk) == (64, 6, 1408, 2, False)
    assert (cfg.mla.kv_lora, cfg.mla.qk_nope, cfg.mla.qk_rope,
            cfg.mla.v_dim) == (512, 128, 64, 128)
    from repro.models import api
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                     cfg))
    assert shapes["dense_blocks"]["ffn"]["gate"]["w"].shape == (1, 2048, 10944)
    assert shapes["blocks"]["ffn"]["gate"].shape == (26, 64, 2048, 1408)
    assert shapes["blocks"]["attn"]["kv_norm"].shape == (26, 512)
