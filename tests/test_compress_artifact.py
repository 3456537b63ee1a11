"""Unified compression API: family adapters, the serializable CompressedModel
artifact, and engine-integrated LCC decode (fused kernel inside the jitted
decode step)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs import get_arch
from repro.configs.base import MoESpec, reduced_config
from repro.core.artifact import CompressedModel
from repro.models import api
from repro.serving.engine import ServingEngine


def _tiny_cfg():
    return reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                          n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                          n_layers=2)


def _fp_compression():
    return core.CompressionConfig(algorithm="fp", weight_sharing=True,
                                  max_share_rel_err=0.06)


@pytest.fixture(scope="module")
def dense_artifact():
    cfg = _tiny_cfg()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return api.compress_model(params, cfg, _fp_compression())


# ---------------------------------------------------------------- adapters


def test_adapter_covers_three_families(dense_artifact):
    """compress_model works for dense, MoE and ResNet via the registry —
    no ValueError carve-outs for supported families."""
    # dense transformer: FFN + attention projections
    names = set(dense_artifact.records)
    assert {"ffn.gate.l0", "ffn.up.l1", "ffn.down.l0", "attn.q.l0",
            "attn.o.l1"} <= names
    assert dense_artifact.report.total_baseline() > 0

    # MoE: per-expert dense matrices + attention
    cfg_m = reduced_config(
        get_arch("mixtral-8x22b"), d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, vocab=64, n_layers=1,
        moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16))
    pm = api.init_params(jax.random.PRNGKey(1), cfg_m)
    art_m = api.compress_model(pm, cfg_m, _fp_compression())
    assert {"moe.gate.l0.e0", "moe.gate.l0.e1", "moe.down.l0.e0",
            "attn.q.l0"} <= set(art_m.records)
    # dense-effective params still decode
    st = api.init_decode_state(cfg_m, 1, 8)
    logits, _ = api.decode(art_m.params, cfg_m, st,
                           jnp.asarray([[3]], jnp.int32),
                           jnp.asarray([0], jnp.int32))
    assert logits.shape == (1, cfg_m.vocab)

    # ResNet: conv kernels via the CMVM reshape + the linear head
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_forward

    rcfg = ResNetConfig(stages=(1,), widths=(8,), classes=4, in_ch=3)
    rp = init_resnet(jax.random.PRNGKey(2), rcfg)
    art_r = api.compress_model(rp, rcfg, _fp_compression())
    assert {"stem", "block0.conv1", "block0.conv2", "head"} <= set(art_r.records)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 8, 8)),
                    jnp.float32)
    assert resnet_forward(art_r.params, x).shape == (2, 4)


def test_unit_enumeration_no_family_carveouts():
    """Every assigned family enumerates compressible units (the PR-1 surface
    raised ValueError for anything but the dense-transformer FFN)."""
    for arch in ("olmo-1b", "qwen2-vl-7b", "mixtral-8x22b",
                 "deepseek-v2-lite-16b", "rwkv6-1.6b", "zamba2-7b",
                 "whisper-small"):
        cfg = reduced_config(get_arch(arch))
        params = api.init_params(jax.random.PRNGKey(3), cfg)
        units = api.compressible_units(params, cfg)
        assert units, f"{arch}: no compressible units"


def test_rebind_writes_effective_weight(dense_artifact):
    cfg = _tiny_cfg()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    w_new = np.full((cfg.d_ff, cfg.d_model), 0.25)
    p2 = api.rebind(params, cfg, "ffn.gate.l1", w_new)
    # target layer updated, original untouched, sibling layer untouched
    assert np.allclose(np.asarray(p2["blocks"]["ffn"]["gate"]["w"][1]), 0.25)
    assert not np.allclose(np.asarray(params["blocks"]["ffn"]["gate"]["w"][1]), 0.25)
    np.testing.assert_array_equal(np.asarray(p2["blocks"]["ffn"]["gate"]["w"][0]),
                                  np.asarray(params["blocks"]["ffn"]["gate"]["w"][0]))
    with pytest.raises(KeyError, match="no compressible unit"):
        api.rebind(params, cfg, "nope.l0", w_new)


# ---------------------------------------------------------------- artifact


def test_artifact_roundtrip_bitwise(dense_artifact, tmp_path):
    """Save/load through the Checkpointer: decode logits bitwise-identical."""
    cfg = dense_artifact.config
    d = str(tmp_path / "artifact")
    dense_artifact.save(d)
    art2 = CompressedModel.load(d)

    assert set(art2.records) == set(dense_artifact.records)
    assert set(art2.packed) == set(dense_artifact.packed)
    r1 = dense_artifact.records["ffn.gate.l0"]
    r2 = art2.records["ffn.gate.l0"]
    np.testing.assert_array_equal(r1.effective, r2.effective)
    np.testing.assert_array_equal(r1.decomposition.to_dense(),
                                  r2.decomposition.to_dense())

    state = api.init_decode_state(cfg, 1, 16)
    tok = jnp.asarray([[3]], jnp.int32)
    pos = jnp.asarray([0], jnp.int32)
    run = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos)[0])
    np.testing.assert_array_equal(np.asarray(run(dense_artifact.params)),
                                  np.asarray(run(art2.params)))


def test_artifact_corrupted_shard_skipped(dense_artifact, tmp_path):
    """A corrupted newest step falls back to the previous intact one."""
    d = str(tmp_path / "artifact")
    dense_artifact.save(d, step=0)
    dense_artifact.save(d, step=1)
    shard = os.path.join(d, "step_0000000001", "shard_0.msgpack")
    with open(shard, "r+b") as f:
        f.seek(200)
        f.write(b"\xff" * 16)
    art = CompressedModel.load(d)  # must not raise
    assert set(art.records) == set(dense_artifact.records)

    # nothing intact at all -> clean failure, not a crash elsewhere
    with open(os.path.join(d, "step_0000000000", "shard_0.msgpack"), "r+b") as f:
        f.seek(200)
        f.write(b"\xff" * 16)
    with pytest.raises(FileNotFoundError, match="no intact"):
        CompressedModel.load(d)


# ------------------------------------------------------- engine integration


def test_engine_decode_runs_fused_kernel(dense_artifact, monkeypatch):
    """ServingEngine(artifact=...) routes every compressed site (FFN *and*
    attention) through fused kernel launches inside the jitted decode step,
    and its logits match the dense-effective forward to <= 1e-4."""
    from repro.kernels import ops

    calls = {"chain": 0, "group": 0}
    real_chain, real_group = ops.lcc_chain_matmul, ops.lcc_group_matmul

    def counting_chain(*a, **k):
        calls["chain"] += 1
        return real_chain(*a, **k)

    def counting_group(*a, **k):
        calls["group"] += 1
        return real_group(*a, **k)

    monkeypatch.setattr(ops, "lcc_chain_matmul", counting_chain)
    monkeypatch.setattr(ops, "lcc_group_matmul", counting_group)

    cfg = dense_artifact.config
    eng = ServingEngine(artifact=dense_artifact, n_slots=2, max_len=32)
    assert eng.executor is not None
    assert eng.executor.sites == set(dense_artifact.records)
    res = eng.generate([[3, 1, 4], [1, 5]], max_new_tokens=4)
    assert all(r.finished for r in res)
    assert calls["chain"] > 0, "no single-site chain launch (o, down) traced"
    assert calls["group"] > 0, \
        "no fused-region (q/k/v, gate/up) grouped launch was traced"
    # every compressed site dispatched through a fused kernel — nothing fell
    # back to the dense-effective matmul on the hot path
    assert eng.executor.routed == eng.executor.sites

    # same artifact served through the stock XLA dense-effective path
    eng_dense = ServingEngine(artifact=dense_artifact, n_slots=2, max_len=32,
                              use_kernel=False)
    assert eng_dense.executor is None
    res_d = eng_dense.generate([[3, 1, 4], [1, 5]], max_new_tokens=4)
    assert [r.tokens for r in res] == [r.tokens for r in res_d]

    state = api.init_decode_state(cfg, 1, 16)
    tok = jnp.asarray([[3]], jnp.int32)
    pos = jnp.asarray([0], jnp.int32)
    l_kernel, _ = api.decode(dense_artifact.params, cfg, state, tok, pos,
                             executor=eng.executor)
    l_dense, _ = api.decode(dense_artifact.params, cfg, state, tok, pos)
    assert float(jnp.abs(l_kernel - l_dense).max()) <= 1e-4


# ---------------------------------------------------------------- prefill


def test_bulk_prefill_matches_tokenwise():
    """One api.prefill forward writes the same KV the per-token decode loop
    produced (same greedy continuations), including slot reuse across
    requests of different lengths."""
    cfg = reduced_config(get_arch("olmo-1b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    bulk = ServingEngine(params, cfg, n_slots=2, max_len=64)
    loop = ServingEngine(params, cfg, n_slots=2, max_len=64, bulk_prefill=False)
    prompts = [[5, 9, 2, 7, 11, 1, 3], [7, 1], [4, 4, 4, 8], [30]]
    r_bulk = bulk.generate(prompts, max_new_tokens=5)
    r_loop = loop.generate(prompts, max_new_tokens=5)
    assert [r.tokens for r in r_bulk] == [r.tokens for r in r_loop]


def test_bulk_prefill_mla():
    cfg = reduced_config(get_arch("deepseek-v2-lite-16b"))
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    bulk = ServingEngine(params, cfg, n_slots=1, max_len=32)
    loop = ServingEngine(params, cfg, n_slots=1, max_len=32, bulk_prefill=False)
    a = bulk.generate([[3, 1, 4, 1, 5]], max_new_tokens=4)[0]
    b = loop.generate([[3, 1, 4, 1, 5]], max_new_tokens=4)[0]
    assert a.tokens == b.tokens


# ---------------------------------------------------------------- accounting


def test_shared_labels_dtype_and_bytes():
    """Weight-sharing labels are stored at their deployment width and the
    byte accounting reads the stored dtype (not an int64 assumption)."""
    rng = np.random.default_rng(0)
    cents = rng.standard_normal((24, 4))
    labels = rng.integers(0, 4, 32)
    w = cents[:, labels] + 1e-4 * rng.standard_normal((24, 32))
    report = core.ModelCostReport()
    cd = core.compress_dense_matrix(
        "shared_unit", w,
        core.CompressionConfig(algorithm="fp", weight_sharing=True,
                               max_share_rel_err=0.06), report)
    assert cd.shared is not None, "clustered matrix must trigger sharing"
    assert cd.shared.labels.dtype == np.uint16
    lc = report.layers[0]
    assert lc.stage_bytes["lcc"] == (cd.decomposition.storage_bytes()
                                     + cd.shared.labels.nbytes)
    # reference evaluation still works with the narrow label dtype
    x = rng.standard_normal((32, 3))
    np.testing.assert_allclose(cd.apply(x), cd.effective @ x[cd.kept_columns],
                               atol=1e-9)


def test_compress_ffn_for_serving_legacy_wrapper(dense_artifact):
    """The PR-1 entry point still returns (params_c, matvecs, report) and now
    delegates to the adapter registry."""
    cfg = _tiny_cfg()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    from repro.serving.engine import compress_ffn_for_serving

    params_c, matvecs, report = compress_ffn_for_serving(
        params, cfg, _fp_compression())
    assert set(matvecs) == {"gate", "up", "down"}
    assert all(len(v) == cfg.n_layers for v in matvecs.values())
    assert report.total_baseline() > 0
    # dense-effective FFN weights replaced, embeddings untouched
    assert not np.array_equal(np.asarray(params_c["blocks"]["ffn"]["gate"]["w"]),
                              np.asarray(params["blocks"]["ffn"]["gate"]["w"]))
    np.testing.assert_array_equal(np.asarray(params_c["embed"]),
                                  np.asarray(params["embed"]))
