"""Full-model compressed execution: the site-keyed CompressedExecutor routes
attention, MoE experts, recurrent mixes, whisper-decoder and conv sites
through fused kernel launches inside the jitted decode step, with
compressed-vs-dense logits parity <= 1e-4 and no dense-effective matmul on
the hot path for covered sites."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs import get_arch
from repro.configs.base import MoESpec, SSMSpec, reduced_config
from repro.core.artifact import CompressedModel
from repro.models import api
from repro.serving.engine import ServingEngine
from repro.serving.executor import CompressedExecutor, GroupedLCCMatvec, LCCMatvec


def _fp():
    return core.CompressionConfig(algorithm="fp", weight_sharing=True,
                                  max_share_rel_err=0.06)


def _decode_parity(art, *, batch: int = 1, smax: int = 8):
    """Build an executor over ``art`` and compare one jitted decode step on
    the kernel path vs the dense-effective path.  Returns (executor, err)."""
    cfg = art.config
    ex = CompressedExecutor(art, interpret=None)
    state = api.init_decode_state(cfg, batch, smax)
    tok = jnp.asarray([[3]] * batch, jnp.int32)
    pos = jnp.asarray([0] * batch, jnp.int32)
    l_k, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                          executor=ex))(art.params)
    l_d, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos))(art.params)
    return ex, float(jnp.abs(l_k - l_d).max())


# ------------------------------------------------------------ family parity


def test_moe_executor_parity_and_grouped_dispatch(monkeypatch):
    """All experts of an MoE layer apply their chains through the grouped
    (one-dispatch) launch; compressed logits match dense-effective <= 1e-4.
    Plans are disabled here so the per-region grouped route stays covered
    (with plans on, the whole-step MoE plan absorbs the expert dispatches)."""
    from repro.kernels import ops

    calls = {"group": 0}
    real = ops.lcc_group_matmul

    def counting(*a, **k):
        calls["group"] += 1
        return real(*a, **k)

    monkeypatch.setattr(ops, "lcc_group_matmul", counting)

    cfg = reduced_config(
        get_arch("mixtral-8x22b"), d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, vocab=64, n_layers=1,
        moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16))
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    art = api.compress_model(params, cfg, _fp())
    cfg = art.config
    ex = CompressedExecutor(art, interpret=None, use_plans=False)
    state = api.init_decode_state(cfg, 2, 8)
    tok = jnp.asarray([[3]] * 2, jnp.int32)
    pos = jnp.asarray([0] * 2, jnp.int32)
    l_k, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                          executor=ex))(art.params)
    l_d, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos))(art.params)
    assert float(jnp.abs(l_k - l_d).max()) <= 1e-4
    assert ex.routed == ex.sites, ex.sites - ex.routed
    assert calls["group"] > 0, "MoE experts never hit the grouped launch"
    assert ex.plan_fallbacks.get("step") == "plans_disabled"


def test_rwkv6_executor_parity():
    """Recurrent family: time-mix r/k/v/g + channel-mix sites run fused."""
    cfg = reduced_config(get_arch("rwkv6-1.6b"), d_model=64, head_dim=16,
                         d_ff=96, vocab=64)
    params = api.init_params(jax.random.PRNGKey(2), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex, err = _decode_parity(art)
    assert err <= 1e-4, err
    assert ex.routed == ex.sites, ex.sites - ex.routed


def test_hybrid_executor_parity():
    """zamba2: mamba in/out projections + the weight-shared attention block."""
    cfg = reduced_config(get_arch("zamba2-7b"), d_model=64, n_heads=4,
                         n_kv_heads=4, head_dim=16, d_ff=96, vocab=64,
                         ssm=SSMSpec(d_inner=64, d_state=16, head_dim=16,
                                     d_conv=4))
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex, err = _decode_parity(art)
    assert err <= 1e-4, err
    assert ex.routed == ex.sites, ex.sites - ex.routed


def test_mla_executor_parity():
    """MLA projections (q/dkv/kr/uk/uv/o) route through fused chains —
    together with the MoE expert + shared-expert sites of the same layer."""
    cfg = reduced_config(get_arch("deepseek-v2-lite-16b"), d_model=32,
                         n_heads=2, n_kv_heads=2, vocab=64, n_layers=2,
                         moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16,
                                     n_shared=1))
    params = api.init_params(jax.random.PRNGKey(4), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex, err = _decode_parity(art)
    assert err <= 1e-4, err
    assert ex.routed == ex.sites, ex.sites - ex.routed


def test_whisper_executor_parity():
    """Whisper decoder self/cross-attention + MLP sites run fused; encoder
    and cross-KV sites only execute at prefill, so the decode step routes
    exactly the dec.* sites (cross k/v excluded — their KV is static)."""
    cfg = reduced_config(get_arch("whisper-small"), d_model=64, n_heads=4,
                         n_kv_heads=4, head_dim=16, d_ff=96, vocab=64,
                         n_layers=1, enc_layers=1)
    params = api.init_params(jax.random.PRNGKey(5), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex, err = _decode_parity(art)
    assert err <= 1e-4, err
    expected = {n for n in ex.sites
                if n.startswith("dec.") and not (
                    n.startswith("dec.xattn.k") or n.startswith("dec.xattn.v"))}
    assert ex.routed == expected, ex.routed ^ expected


# ---------------------------------------------------------------- conv path


@pytest.mark.parametrize("method", ["pk", "fk"])
def test_conv_executor_parity(method):
    """Compressed ResNet channels execute through the conv-as-matmul grouped
    launch (FK and PK reshapes), matching the dense-effective conv <= 1e-4 —
    including the stride-2 stage transition and the 1x1 projection."""
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_forward

    comp = core.CompressionConfig(algorithm="fp", weight_sharing=True,
                                  max_share_rel_err=0.06, conv_method=method)
    rcfg = ResNetConfig(stages=(1, 1), widths=(8, 12), classes=4, in_ch=3)
    rp = init_resnet(jax.random.PRNGKey(2), rcfg)
    art = api.compress_model(rp, rcfg, comp)
    ex = CompressedExecutor(art, interpret=None)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 10, 10)),
                    jnp.float32)
    y_k = resnet_forward(art.params, x, executor=ex)
    y_d = resnet_forward(art.params, x)
    assert float(jnp.abs(y_k - y_d).max()) <= 1e-4
    assert ex.routed == ex.sites  # every conv + the head dispatched fused


# ---------------------------------------------------- engine / serving level


def test_engine_serves_moe_artifact():
    """ServingEngine(artifact=...) is family-agnostic: an MoE artifact decodes
    on the kernel path and produces the same tokens as the dense-effective
    engine."""
    cfg = reduced_config(
        get_arch("mixtral-8x22b"), d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, vocab=64, n_layers=1,
        moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16))
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    art = api.compress_model(params, cfg, _fp())
    eng = ServingEngine(artifact=art, n_slots=2, max_len=32)
    assert eng.executor is not None and eng.executor.sites == set(art.records)
    res = eng.generate([[3, 1, 4], [1, 5]], max_new_tokens=4)
    eng_d = ServingEngine(artifact=art, n_slots=2, max_len=32, use_kernel=False)
    res_d = eng_d.generate([[3, 1, 4], [1, 5]], max_new_tokens=4)
    assert [r.tokens for r in res] == [r.tokens for r in res_d]
    assert eng.executor.routed == eng.executor.sites


# ------------------------------------------------------- grouped matvec unit


def test_grouped_matvec_matches_per_site():
    """GroupedLCCMatvec (one launch) == per-site LCCMatvec outputs."""
    rng = np.random.default_rng(0)
    report = core.ModelCostReport()
    recs = [core.compress_dense_matrix(f"u{i}", rng.standard_normal((16 + 8 * i, 24)),
                                       _fp(), report)
            for i in range(3)]
    grouped = GroupedLCCMatvec(recs, interpret=None)
    singles = [LCCMatvec(r, interpret=None) for r in recs]
    xs = [jnp.asarray(rng.standard_normal((24, 5))) for _ in recs]
    ys = grouped(xs)
    for y, mv, x in zip(ys, singles, xs):
        np.testing.assert_allclose(np.asarray(y), np.asarray(mv(x)),
                                   rtol=0, atol=1e-5)


# ------------------------------------------------------------- api / artifact


def test_compress_model_include_callable():
    """include= accepts a callable site filter, not just a prefix string."""
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp(),
                             include=lambda n: n.startswith("attn.q")
                             or n == "ffn.down.l1")
    assert set(art.records) == {"attn.q.l0", "attn.q.l1", "ffn.down.l1"}
    # unfiltered sites keep their original weights in the effective params
    np.testing.assert_array_equal(
        np.asarray(art.params["blocks"]["ffn"]["gate"]["w"]),
        np.asarray(params["blocks"]["ffn"]["gate"]["w"]))


def test_artifact_roundtrip_non_ffn_records(tmp_path):
    """Attention and conv records survive save/load bitwise and the loaded
    artifact still routes through the executor."""
    # attention record round-trip (dense transformer, attention sites only)
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp(), include="attn.")
    d = str(tmp_path / "attn_art")
    art.save(d)
    art2 = CompressedModel.load(d)
    assert set(art2.records) == set(art.records)
    r1, r2 = art.records["attn.q.l0"], art2.records["attn.q.l0"]
    np.testing.assert_array_equal(r1.effective, r2.effective)
    np.testing.assert_array_equal(r1.kept_columns, r2.kept_columns)
    np.testing.assert_array_equal(r1.decomposition.to_dense(),
                                  r2.decomposition.to_dense())
    ex, err = _decode_parity(art2)
    assert err <= 1e-4
    assert {n for n in ex.routed if n.startswith("attn.")} == set(art.records)

    # conv record round-trip (ResNet) + compressed-domain forward after load
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_forward

    rcfg = ResNetConfig(stages=(1,), widths=(8,), classes=4, in_ch=3)
    rp = init_resnet(jax.random.PRNGKey(2), rcfg)
    art_r = api.compress_model(rp, rcfg, _fp())
    dr = str(tmp_path / "conv_art")
    art_r.save(dr)
    art_r2 = CompressedModel.load(dr)
    rec1 = art_r.records["block0.conv1"]
    rec2 = art_r2.records["block0.conv1"]
    assert rec1["channels_nonzero"] == rec2["channels_nonzero"]
    assert set(rec1["decompositions"]) == set(rec2["decompositions"])
    for ch in rec1["decompositions"]:
        np.testing.assert_array_equal(rec1["decompositions"][ch].to_dense(),
                                      rec2["decompositions"][ch].to_dense())
    ex_r = CompressedExecutor(art_r2)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 3, 8, 8)),
                    jnp.float32)
    y_k = resnet_forward(art_r2.params, x, executor=ex_r)
    y_d = resnet_forward(art_r2.params, x)
    assert float(jnp.abs(y_k - y_d).max()) <= 1e-4


# ------------------------------------------------------------------ metrics


def test_compressed_adds_metric():
    """flops.compressed_adds reports the Table-1 additions alongside dense
    MACs, with MoE expert stacks scaled to the per-token active count."""
    from repro.models import flops

    cfg = reduced_config(
        get_arch("mixtral-8x22b"), d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, vocab=64, n_layers=1,
        moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16))
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    art = api.compress_model(params, cfg, _fp())
    m = flops.compressed_adds(cfg, art)
    assert m["baseline_adds"] == art.report.total_baseline()
    assert m["compressed_adds"] == art.report.total_stage("lcc")
    assert m["ratio"] > 1.0  # compression must reduce additions
    # top_k=1 of 2 experts: the active view charges half of each expert stack
    assert m["active_baseline_adds"] < m["baseline_adds"]
    assert m["active_ratio"] > 1.0


# --------------------------------------------------------------- layer plans


def test_step_plan_decode_parity_all_sites():
    """Whole-step layer plan == per-region kernels == dense-effective decode
    (<= 1e-4), with every site routed and exactly one plan built."""
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp())
    batch, smax = 2, 8
    state = api.init_decode_state(cfg, batch, smax)
    tok = jnp.asarray([[3]] * batch, jnp.int32)
    pos = jnp.asarray([0] * batch, jnp.int32)

    ex_plan = CompressedExecutor(art, interpret=None)
    ex_reg = CompressedExecutor(art, interpret=None, use_plans=False)
    run = lambda ex: jax.jit(
        lambda p: api.decode(p, cfg, state, tok, pos, executor=ex))(art.params)
    l_plan, s_plan = run(ex_plan)
    l_reg, _ = run(ex_reg)
    l_d, s_d = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos))(art.params)

    assert float(jnp.abs(l_plan - l_d).max()) <= 1e-4
    assert float(jnp.abs(l_plan - l_reg).max()) <= 1e-4
    assert ex_plan.n_layer_plans == 1
    assert ex_plan.routed == ex_plan.sites
    for leaf in ("k", "v", "kpos"):  # KV write-back outside the kernel
        d = jnp.abs(s_plan[leaf].astype(jnp.float32)
                    - s_d[leaf].astype(jnp.float32))
        assert float(d.max()) <= 1e-4, leaf
    # a second step from the plan-updated state keeps tracking dense
    tok2 = jnp.asarray([[5]] * batch, jnp.int32)
    pos2 = jnp.asarray([1] * batch, jnp.int32)
    l2p, _ = jax.jit(lambda p: api.decode(p, cfg, s_plan, tok2, pos2,
                                          executor=ex_plan))(art.params)
    l2d, _ = jax.jit(lambda p: api.decode(p, cfg, s_d, tok2, pos2))(art.params)
    assert float(jnp.abs(l2p - l2d).max()) <= 1e-4


def test_step_plan_bakes_uncovered_sites_dense():
    """An FFN-only artifact still gets a whole-step plan: attention q/k/v/o
    ride along as baked dense blocks, and the plan builds lazily inside the
    jitted trace without touching traced params."""
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp(),
                             include=lambda n: n.startswith("ffn."))
    assert all(n.startswith("ffn.") for n in art.records)
    ex = CompressedExecutor(art, interpret=None)
    state = api.init_decode_state(cfg, 2, 8)
    tok = jnp.asarray([[3]] * 2, jnp.int32)
    pos = jnp.asarray([0] * 2, jnp.int32)
    l_k, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                          executor=ex))(art.params)
    l_d, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos))(art.params)
    assert ex.n_layer_plans == 1  # the lazy in-trace build must not fall back
    assert float(jnp.abs(l_k - l_d).max()) <= 1e-4
    assert ex.routed == ex.sites == set(art.records)


def test_moe_plan_executor_parity():
    """Whole-step MoE plan (attention + router top-k + both expert
    super-stages in ONE launch) == per-region grouped kernels ==
    dense-effective decode, including a second decode step."""
    from repro.kernels import dispatch

    cfg = reduced_config(
        get_arch("mixtral-8x22b"), d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, vocab=64, n_layers=2,
        moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp())
    cfg = art.config
    state = api.init_decode_state(cfg, 2, 8)
    tok = jnp.asarray([[3]] * 2, jnp.int32)
    pos = jnp.asarray([0] * 2, jnp.int32)
    ex_plan = CompressedExecutor(art, interpret=None)
    ex_reg = CompressedExecutor(art, interpret=None, use_plans=False)
    run = lambda ex: jax.jit(
        lambda p: api.decode(p, cfg, state, tok, pos, executor=ex))(art.params)
    dispatch.reset_launch_count()
    t0 = dispatch.launch_count()
    l_plan, s_plan = run(ex_plan)
    n_launch = dispatch.launch_count() - t0
    l_reg, _ = run(ex_reg)
    l_d, s_d = jax.jit(lambda p: api.decode(p, cfg, state, tok,
                                            pos))(art.params)
    assert float(jnp.abs(l_plan - l_d).max()) <= 1e-4
    assert float(jnp.abs(l_plan - l_reg).max()) <= 1e-4
    # the routed block folds into the step plan: launches == plans == 1
    assert ex_plan.n_layer_plans == 1
    assert n_launch == 1, n_launch
    assert ex_plan.plan_fallbacks == {}
    assert ex_plan.routed == ex_plan.sites
    # second decode step from the plan-updated state keeps tracking dense
    tok2 = jnp.asarray([[5]] * 2, jnp.int32)
    pos2 = jnp.asarray([1] * 2, jnp.int32)
    l2p, _ = jax.jit(lambda p: api.decode(p, cfg, s_plan, tok2, pos2,
                                          executor=ex_plan))(art.params)
    l2d, _ = jax.jit(lambda p: api.decode(p, cfg, s_d, tok2, pos2))(art.params)
    assert float(jnp.abs(l2p - l2d).max()) <= 1e-4


def test_engine_step_plan_single_launch():
    """Engine-level (paged KV): plan tokens == dense tokens AND the measured
    Pallas launches per fused decode step equals the number of layer plans."""
    from repro.serving.engine import ServingEngine

    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    art = api.compress_model(params, cfg, _fp())
    eng_k = ServingEngine(artifact=art, n_slots=2, max_len=32)
    eng_d = ServingEngine(artifact=art, n_slots=2, max_len=32,
                          use_kernel=False)
    prompt = [5, 9, 2, 7]
    out_k = eng_k.generate([prompt], max_new_tokens=8, temperature=0.0)
    out_d = eng_d.generate([prompt], max_new_tokens=8, temperature=0.0)
    assert [r.tokens for r in out_k] == [r.tokens for r in out_d]
    assert eng_k.n_layer_plans == 1
    assert eng_k.pallas_launches_per_step == eng_k.n_layer_plans == 1


def test_pack_group_padding_waste_reported():
    """pack_group reports the zero-row / zero-slice padding fractions of the
    stacked [G, E, P, N, S] slab, and the executor mirrors them into the
    artifact's pipeline_stats."""
    from repro.core.lcc import lcc_decompose
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    decs = [lcc_decompose(rng.standard_normal(shape), algorithm="fp",
                          target_snr_db=35.0)
            for shape in [(48, 16), (8, 16), (12, 12)]]
    pg = ops.pack_group([ops.pack_decomposition(d) for d in decs])
    w = pg.waste
    assert w is not None
    assert len(w["row_waste"]) == len(decs)
    assert all(0.0 <= f <= 1.0 for f in w["row_waste"])
    # the (8, 16) member pads against the 48-row member: real waste shows up
    assert max(w["row_waste"]) > 0.0
    assert 0.0 <= w["mean_row_waste"] <= 1.0

    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex = CompressedExecutor(art, interpret=None, use_plans=False)
    state = api.init_decode_state(cfg, 1, 8)
    tok = jnp.asarray([[3]], jnp.int32)
    pos = jnp.asarray([0], jnp.int32)
    jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                 executor=ex))(art.params)
    pw = art.pipeline_stats.get("padding_waste", {})
    assert pw, "grouped regions must record their padding waste"
    assert all(0.0 <= v["mean_row_waste"] <= 1.0 for v in pw.values())


def test_artifact_plans_roundtrip(tmp_path):
    """Packed layer-plan stages persist through save/load, and a fresh
    executor on the loaded artifact reuses them (same decode numerics)."""
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex = CompressedExecutor(art, interpret=None)
    assert ex.step_plan(cfg) is not None  # builds + stores into art.plans
    assert "step" in art.plans

    d = str(tmp_path / "plan_art")
    art.save(d)
    art2 = CompressedModel.load(d)
    assert "step" in art2.plans
    for name, ps in art.plans["step"].items():
        ps2 = art2.plans["step"][name]
        assert ps2.k_alloc == ps.k_alloc and ps2.out_dim == ps.out_dim
        for f in ("prep_src", "prep_tgt", "gidx", "gexp", "gsgn", "outg",
                  "fs_mat", "dw_mat", "bias", "segs"):
            a, b = getattr(ps, f), getattr(ps2, f)
            assert (a is None) == (b is None), (name, f)
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ex2 = CompressedExecutor(art2, interpret=None)
    state = api.init_decode_state(cfg, 1, 8)
    tok = jnp.asarray([[3]], jnp.int32)
    pos = jnp.asarray([0], jnp.int32)
    l1, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                         executor=ex))(art.params)
    l2, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                         executor=ex2))(art2.params)
    assert ex2.n_layer_plans == 1
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-6, atol=1e-6)


def test_pr8_plan_artifacts_without_segs_load_and_decode_bitwise(tmp_path):
    """PR 8-era saved plans carry no segment-packed layout: stripping ``segs``
    before save must load back with ``segs is None`` and decode through the
    original full-gather operand path bitwise-identically to the in-memory
    stripped plan."""
    import dataclasses

    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex = CompressedExecutor(art, interpret=None)
    assert ex.step_plan(cfg) is not None
    # simulate a PR 8 artifact: drop the segment descriptors before saving
    art.plans["step"] = {n: dataclasses.replace(ps, segs=None, seg_stats=None,
                                                waste=None)
                         for n, ps in art.plans["step"].items()}
    d = str(tmp_path / "pr8_art")
    art.save(d)
    art2 = CompressedModel.load(d)
    assert all(ps.segs is None for ps in art2.plans["step"].values())

    state = api.init_decode_state(cfg, 2, 8)
    tok = jnp.asarray([[3]] * 2, jnp.int32)
    pos = jnp.asarray([0] * 2, jnp.int32)
    ex_mem = CompressedExecutor(art, interpret=None)  # reuses stripped stages
    ex_load = CompressedExecutor(art2, interpret=None)
    l_mem, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                            executor=ex_mem))(art.params)
    l_load, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                             executor=ex_load))(art2.params)
    assert ex_load.n_layer_plans == 1  # legacy layout still plans
    np.testing.assert_array_equal(np.asarray(l_mem), np.asarray(l_load))
    # and the operand path still tracks dense within tolerance
    l_d, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos))(art.params)
    assert float(jnp.abs(l_load - l_d).max()) <= 1e-4


def test_plan_fallback_reasons_and_segment_stats():
    """Ineligible families record a reason string in ``plan_fallbacks``;
    eligible plans record per-stage padding waste and segment-layout
    run-length stats into the artifact's pipeline_stats."""
    # hybrid family: step plan must fall back with a reason, not silently
    cfg_hyb = reduced_config(get_arch("zamba2-7b"), d_model=64, n_heads=4,
                             n_kv_heads=4, head_dim=16, d_ff=96, vocab=64,
                             ssm=SSMSpec(d_inner=64, d_state=16, head_dim=16,
                                         d_conv=4))
    params = api.init_params(jax.random.PRNGKey(0), cfg_hyb)
    art = api.compress_model(params, cfg_hyb, _fp())
    ex = CompressedExecutor(art, interpret=None)
    assert ex.step_plan(cfg_hyb) is None
    assert ex.plan_fallbacks.get("step") == "family:hybrid"

    # eligible dense family: stages carry segs + stats, recorded in the art
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex = CompressedExecutor(art, interpret=None)
    plan = ex.step_plan(cfg)
    assert plan is not None and ex.plan_fallbacks == {}
    segged = [ps for ps in plan.stages.values() if ps.segs is not None]
    assert segged, "new plans must carry segment descriptors"
    seg = art.pipeline_stats.get("segment_layout", {})
    pw = art.pipeline_stats.get("padding_waste", {})
    assert any(k.startswith("plan.") for k in seg), seg
    assert any(k.startswith("plan.") for k in pw), pw
    for st in seg.values():
        assert st["p50_run_after"] >= st["p50_run_before"] or \
            st["n_runs_after"] <= st["n_runs_before"]
        assert 0.0 <= st["gather_frac"] <= 1.0
    for wv in (v for k, v in pw.items() if k.startswith("plan.")):
        assert 0.0 <= wv["row_waste"] <= 1.0
        assert 0.0 <= wv["slice_waste"] <= 1.0


def test_engine_plan_stats_and_fallback_metric():
    """Engine telemetry: ``plan_stats()`` reports plans/launches/fallback
    reasons and ``serving_plan_fallbacks_total{reason}`` counts each plan
    key once."""
    from repro.serving.engine import ServingEngine

    cfg = reduced_config(get_arch("zamba2-7b"), d_model=64, n_heads=4,
                         n_kv_heads=4, head_dim=16, d_ff=96, vocab=64,
                         ssm=SSMSpec(d_inner=64, d_state=16, head_dim=16,
                                     d_conv=4))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp())
    eng = ServingEngine(artifact=art, n_slots=2, max_len=16)
    eng.generate([[5, 9]], max_new_tokens=4, temperature=0.0)
    st = eng.plan_stats()
    assert st["n_layer_plans"] == 0
    assert st["fallbacks"].get("step") == "family:hybrid"
    assert "pallas_launches_per_step" in st
    metric = eng.metrics.to_prometheus()
    assert 'serving_plan_fallbacks_total{reason="family:hybrid"} 1' in metric
