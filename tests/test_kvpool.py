"""Paged KV-cache subsystem: block pool + prefix cache + continuous batching.

Covers the host-side allocator (refcounts, eviction, all-or-nothing admission),
paged-vs-contiguous decode parity (dense, MLA with a leading dense layer,
windowed, the compressed executor's unrolled loop), prefix sharing with
copy-on-write under divergence, block lifecycle under cancel / exhaustion, and
the scheduler's block-gated continuous admission."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs import get_arch, reduced_config
from repro.models import api
from repro.serving.engine import ServingEngine
from repro.serving.kvpool import KVPool
from repro.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def dense_model():
    cfg = reduced_config(get_arch("olmo-1b"))
    return cfg, api.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def mla_model():
    cfg = reduced_config(get_arch("deepseek-v2-lite-16b"))
    return cfg, api.init_params(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------- host pool


def test_pool_alloc_release_roundtrip():
    p = KVPool(n_slots=2, n_blocks=8, block_size=4, view_blocks=4,
               prefix_cache=False)
    plan = p.admit(0, list(range(10)))  # 3 prompt blocks + 1 decode reserve
    assert plan is not None and len(plan.new) == 4 and not plan.shared
    assert p.in_use_blocks == 4 and p.free_blocks == 4
    assert (plan.table != 0).sum() == 4
    p.release(0)
    assert p.free_blocks == 8 and p.in_use_blocks == 0


def test_pool_admission_all_or_nothing():
    p = KVPool(n_slots=2, n_blocks=4, block_size=4, view_blocks=8,
               prefix_cache=False)
    assert p.admit(0, list(range(12))) is not None  # 3 + reserve = all 4
    before = p.free_blocks
    assert p.admit(1, list(range(12))) is None  # nothing left
    assert p.free_blocks == before  # rollback returned everything


def test_pool_prefix_chain_and_lru_eviction():
    p = KVPool(n_slots=3, n_blocks=6, block_size=4, view_blocks=6)
    a = list(range(8))  # 2 full blocks
    p.admit(0, a)
    p.register_prefix(0, a)
    p.release(0)
    assert p.cached_blocks == 2 and p.free_blocks == 4
    plan = p.admit(1, a + [99, 98])  # full-chain hit + 1 fresh block
    assert plan.cached_tokens == 8 and len(plan.shared) == 2
    assert p.prefix_hit_blocks == 2
    p.release(1)
    # exhaust the pool: cached blocks are evicted LRU to serve new work
    plan = p.admit(2, [7] * 20)  # 5 blocks + reserve > 4 free
    assert plan is not None and p.evictions >= 2
    p.release(2)


def test_pool_cow_partial_tail_match():
    p = KVPool(n_slots=2, n_blocks=8, block_size=4, view_blocks=4)
    a = list(range(12))  # 3 full blocks, registered
    p.admit(0, a)
    p.register_prefix(0, a)
    # b shares 2 full blocks and the first 2 tokens of a's block 2
    plan = p.admit(1, a[:10])
    assert plan.cow is not None and plan.cow[0] == p._slot_blocks[0][2]
    assert plan.cached_tokens == 10  # whole prompt served from cache
    assert plan.cow[1] != plan.cow[0]  # private copy
    p.release(0)
    p.release(1)
    assert p.in_use_blocks == 0


# ----------------------------------------------------- paged decode parity


def _stepwise_logits(eng, prompt, n):
    """Greedy-decode ``n`` steps through the engine's raw jitted decode,
    returning the per-step logits row for the submitted request's slot."""
    rid = eng.submit(prompt)
    slot = next(s for s, r in eng.slot_req.items() if r == rid)
    st, tok, pos = eng.state, prompt[-1], len(prompt)
    rows = []
    for _ in range(n):
        logits, st = eng._decode(eng.params, st, eng._token_batch(slot, tok),
                                 eng._pos_batch(slot, pos - 1))
        row = np.asarray(logits[slot], np.float32)
        rows.append(row)
        tok, pos = int(row.argmax()), pos + 1
    eng.cancel(rid)
    return np.stack(rows)


def _executor_engines(**kw):
    """Contiguous and paged engines over a tiny LCC artifact, stepping
    through the executor's per-layer (unrolled) loop."""
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, core.CompressionConfig(
        algorithm="fp", weight_sharing=True, max_share_rel_err=0.06))
    return (cfg, ServingEngine(artifact=art, kv_block=None, **kw),
            ServingEngine(artifact=art, kv_block=16, **kw))


# "mla": one leading dense layer, then one MoE layer; "mla-deep": two MoE
# layers after it, so the MoE group's loop writes the pools at layers 1 and 2;
# "executor": the compressed engine's unrolled layer loop
@pytest.mark.parametrize("family", ["dense", "mla", "windowed", "mla-deep",
                                    "executor"])
def test_paged_matches_contiguous_logits(family, dense_model, mla_model):
    cfg, params = mla_model if family == "mla" else dense_model
    if family == "windowed":
        cfg = dataclasses.replace(cfg, attn_window=24)
    if family == "mla-deep":
        cfg = reduced_config(get_arch("deepseek-v2-lite-16b"), n_layers=3)
        assert cfg.n_dense_layers == 1
        params = api.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(n_slots=2, max_len=64)
    if family == "executor":
        cfg, ref, pag = _executor_engines(**kw)
        assert ref.executor is not None and pag.executor is not None
    else:
        ref = ServingEngine(params, cfg, kv_block=None, **kw)
        pag = ServingEngine(params, cfg, kv_block=16, **kw)
    prompt = [(7 * i + 3) % cfg.vocab for i in range(24)]
    n = 6  # stays inside the admitted blocks (no growth in the raw loop)
    l_ref = _stepwise_logits(ref, prompt, n)
    l_pag = _stepwise_logits(pag, prompt, n)
    assert np.abs(l_ref - l_pag).max() <= 1e-4
    # generate() crosses block boundaries (mid-decode growth) and, windowed,
    # wraps the ring: token streams must stay identical
    r_ref = ref.generate([prompt, prompt[:13]], max_new_tokens=30)
    r_pag = pag.generate([prompt, prompt[:13]], max_new_tokens=30)
    assert [r.tokens for r in r_ref] == [r.tokens for r in r_pag]
    assert pag.pool_stats()["in_use_blocks"] == 0
    if family == "executor":
        assert pag.executor.routed == pag.executor.sites


# ------------------------------------------------- prefix sharing on device


def test_prefix_hit_and_cow_divergence(dense_model):
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=128, kv_block=8)
    ref = ServingEngine(params, cfg, n_slots=2, max_len=128, kv_block=None)
    a = [(11 * i + 5) % cfg.vocab for i in range(24)]  # 3 full 8-blocks
    b = a[:20]  # shares 2 full blocks + half of a's block 2 -> COW
    ra0 = eng.generate([a], max_new_tokens=8)[0]
    s = eng.pool_stats()
    assert s["prefix_hit_blocks"] == 0 and s["in_use_blocks"] == 0
    rb = eng.generate([b], max_new_tokens=8)[0]
    s = eng.pool_stats()
    assert s["cow_copies"] == 1 and s["prefix_hit_tokens"] >= 20
    # COW correctness: the shared-prefix request decodes exactly like a cold
    # contiguous engine would
    assert rb.tokens == ref.generate([b], max_new_tokens=8)[0].tokens
    # divergence wrote only the private copy: a's cached blocks are intact
    ra1 = eng.generate([a], max_new_tokens=8)[0]
    assert ra1.tokens == ra0.tokens
    s = eng.pool_stats()
    assert s["in_use_blocks"] == 0  # zero leaks across all three requests


def test_prefix_partial_tail_pays_only_tail(dense_model):
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=128, kv_block=8)
    ref = ServingEngine(params, cfg, n_slots=2, max_len=128, kv_block=None)
    head = [(3 * i + 1) % cfg.vocab for i in range(16)]  # 2 full blocks
    p1 = head + [40, 41, 42]
    p2 = head + [50, 51, 52, 53, 54]  # same head, divergent tail
    eng.generate([p1], max_new_tokens=6)
    r2 = eng.generate([p2], max_new_tokens=6)[0]
    s = eng.pool_stats()
    assert s["prefix_hit_blocks"] == 2 and s["prefix_hit_tokens"] == 16
    # the tail-extend path is numerically the contiguous prefill
    assert r2.tokens == ref.generate([p2], max_new_tokens=6)[0].tokens
    assert s["in_use_blocks"] == 0


# --------------------------------------------------------- block lifecycle


def test_cancel_during_decode_returns_blocks(dense_model):
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64, kv_block=8,
                        prefix_cache=False)
    rid = eng.submit(list(range(20)))  # 3 prompt blocks + 1 reserve
    assert eng.pool_stats()["in_use_blocks"] == 4
    for _ in range(3):
        eng.step()
    assert eng.cancel(rid)
    s = eng.pool_stats()
    assert s["in_use_blocks"] == 0 and s["free_blocks"] == s["n_blocks"]
    assert eng.results[rid].finished


def test_pool_exhaustion_mid_decode_errors_gracefully(dense_model):
    cfg, params = dense_model
    # 7 usable blocks of 8: a 48-token prompt holds 6, grows into the 7th,
    # then the pool is dry -> errored finish, blocks returned
    eng = ServingEngine(params, cfg, n_slots=2, max_len=128, kv_block=8,
                        kv_blocks=7, prefix_cache=False)
    rid = eng.submit(list(range(2, 50)), max_new=40)
    while eng.active.any():
        eng.step()
    r = eng.results[rid]
    assert r.finished and r.error is not None and "exhausted" in r.error
    assert len(r.tokens) > r.prompt_len  # made progress before running dry
    assert eng.pool_stats()["in_use_blocks"] == 0


def test_oversized_prompt_rejected_via_scheduler(dense_model):
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=512, kv_block=8,
                        kv_blocks=7)  # pool capacity 56 < max_len
    sched = Scheduler(eng)
    bad = sched.enqueue(list(range(2, 90)))  # 88 tokens can never fit
    ok = sched.enqueue([5, 6, 7], max_new=4)
    sched.run()
    r_bad = sched.take_result(bad)
    assert r_bad.finished and r_bad.error is not None
    assert "pool" in r_bad.error
    r_ok = sched.take_result(ok)
    assert r_ok.error is None and len(r_ok.tokens) == 3 + 4


# ----------------------------------------------------- continuous batching


def test_continuous_admission_under_load(dense_model):
    cfg, params = dense_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64, kv_block=8)
    sched = Scheduler(eng)
    prompts = [[(i + 2) % cfg.vocab] * (6 + i) for i in range(6)]
    rids = [sched.enqueue(p, max_new=5 + (i % 3)) for i, p in enumerate(prompts)]
    sched.run()
    res = [sched.take_result(r) for r in rids]
    assert all(r.finished and r.error is None for r in res)
    assert all(len(r.tokens) - r.prompt_len == 5 + (i % 3)
               for i, r in enumerate(res))
    # 6 requests through 2 slots: later ones joined a live batch (no drain)
    assert sched.admitted_while_running >= 4
    assert eng.pool_stats()["in_use_blocks"] == 0


def test_admission_gated_on_blocks_not_just_slots(dense_model):
    cfg, params = dense_model
    # 2 slots but only 7 blocks: two 3-block prompts can't both be resident
    # (3 + 3 + their growth reserve > 7), so the second waits on blocks
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64, kv_block=8,
                        kv_blocks=7, prefix_cache=False)
    sched = Scheduler(eng)
    rids = [sched.enqueue(list(range(2, 24)), max_new=4) for _ in range(2)]
    sched.run()
    res = [sched.take_result(r) for r in rids]
    assert all(r.error is None and len(r.tokens) == 22 + 4 for r in res)
    assert sched.mem_stalls > 0  # the gate actually engaged
    assert eng.pool_stats()["in_use_blocks"] == 0
