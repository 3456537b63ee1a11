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


def _two_step_parity(art, *, batch: int = 2, smax: int = 8):
    """Two jitted decode steps on the kernel path vs the dense-effective
    path: logits and the updated ``k``/``v``/``kpos`` leaves (when the state
    has them) within 1e-4 at each step, the second step run from each path's
    own first-step state.  Returns the executor."""
    cfg = art.config
    ex = CompressedExecutor(art, interpret=None)
    s_k = s_d = api.init_decode_state(cfg, batch, smax)
    for step, t in enumerate((3, 5)):
        tok = jnp.asarray([[t]] * batch, jnp.int32)
        pos = jnp.asarray([step] * batch, jnp.int32)
        l_k, s_k = jax.jit(lambda p, st: api.decode(
            p, cfg, st, tok, pos, executor=ex))(art.params, s_k)
        l_d, s_d = jax.jit(lambda p, st: api.decode(
            p, cfg, st, tok, pos))(art.params, s_d)
        assert float(jnp.abs(l_k - l_d).max()) <= 1e-4, step
        for leaf in ("k", "v", "kpos"):
            if leaf in s_d:
                d = jnp.abs(s_k[leaf].astype(jnp.float32)
                            - s_d[leaf].astype(jnp.float32))
                assert float(d.max()) <= 1e-4, (step, leaf)
    return ex


# ------------------------------------------------------------ family parity


@pytest.mark.parametrize("n_layers", [1, 2])
def test_moe_executor_parity_and_grouped_dispatch(n_layers, monkeypatch):
    """All experts of an MoE layer apply their chains through the grouped
    (one-dispatch) launch; compressed logits and KV leaves match
    dense-effective <= 1e-4 over two decode steps."""
    from repro.kernels import ops

    calls = {"group": 0}
    real = ops.lcc_group_matmul

    def counting(*a, **k):
        calls["group"] += 1
        return real(*a, **k)

    monkeypatch.setattr(ops, "lcc_group_matmul", counting)

    cfg = reduced_config(
        get_arch("mixtral-8x22b"), d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, vocab=64, n_layers=n_layers,
        moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16))
    params = api.init_params(jax.random.PRNGKey(n_layers - 1), cfg)
    art = api.compress_model(params, cfg, _fp())
    ex = _two_step_parity(art)
    assert ex.routed == ex.sites, ex.sites - ex.routed
    assert calls["group"] > 0, "MoE experts never hit the grouped launch"


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


def _mla_routes(fn):
    """The ``mla_decode_routes_total`` counts that tracing ``fn`` adds."""
    from repro.obs import get_global

    def counts():
        m = get_global().get("mla_decode_routes_total")
        return {} if m is None else {r["labels"]["route"]: r["value"]
                                     for r in m.values()}

    before = counts()
    fn()
    return {k: v - before.get(k, 0.0) for k, v in counts().items()
            if v != before.get(k, 0.0)}


# "dense": no executor; "no_uk_uv": an executor over every site but the
# latent's up-projections; "all": an executor that binds uk/uv as chains
@pytest.mark.parametrize("bound,route", [("dense", "absorbed"),
                                         ("no_uk_uv", "absorbed"),
                                         ("all", "expanded")])
def test_mla_decode_route_follows_executor(bound, route):
    """MLA decode attends to the cached latent directly unless the executor
    binds ``uk`` or ``uv``: a chain has no transposed form to fold into the
    query, so the latent is then expanded through it.  The route is counted
    at trace time; either way the step matches dense decode <= 1e-4."""
    cfg = reduced_config(get_arch("deepseek-v2-lite-16b"), d_model=32,
                         n_heads=2, n_kv_heads=2, vocab=64, n_layers=2,
                         moe=MoESpec(n_experts=2, top_k=1, d_ff_expert=16,
                                     n_shared=1))
    params = api.init_params(jax.random.PRNGKey(4), cfg)
    state = api.init_decode_state(cfg, 1, 8)
    tok, pos = jnp.asarray([[3]], jnp.int32), jnp.asarray([0], jnp.int32)
    ex = None
    if bound != "dense":
        keep = (None if bound == "all" else
                lambda n: not n.startswith(("attn.uk.", "attn.uv.")))
        art = api.compress_model(params, cfg, _fp(), include=keep)
        params, ex = art.params, CompressedExecutor(art, interpret=None)
        assert any(n.startswith("attn.q.") for n in ex.sites)
        assert (bound == "all") == any(n.startswith("attn.uk.")
                                       for n in ex.sites)
    step = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos, executor=ex))
    routes = _mla_routes(lambda: step.trace(params))
    assert set(routes) == {route} and routes[route] > 0, routes
    if ex is not None:
        l_d, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos))(params)
        err = float(jnp.abs(step(params)[0] - l_d).max())
        assert err <= 1e-4, err
        assert any(n.startswith("attn.q.") for n in ex.routed), ex.routed


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


# ------------------------------------------------- per-region decode route


def _with_random_biases(params, seed: int = 7):
    """Give every q/k/v bias random values (they initialise to zero), so a
    site whose bias the kernel route drops cannot pass parity."""
    rng = np.random.default_rng(seed)
    attn = dict(params["blocks"]["attn"])
    for proj in ("q", "k", "v"):
        if "b" in attn[proj]:
            b = attn[proj]["b"]
            attn[proj] = {**attn[proj], "b": jnp.asarray(
                0.1 * rng.standard_normal(b.shape), b.dtype)}
    return {**params, "blocks": {**params["blocks"], "attn": attn}}


# (arch, sites): every site of olmo-1b; its FFN sites only (attention stays
# dense beside the LCC launches); llama3.2-3b's grouped KV heads; qwen2.5-3b's
# q/k/v biases
@pytest.mark.parametrize("arch,include", [
    ("olmo-1b", None), ("olmo-1b", "ffn."), ("llama3.2-3b", None),
    ("qwen2.5-3b", None)], ids=["olmo-1b", "olmo-1b-ffn", "llama3.2-3b",
                                "qwen2.5-3b"])
def test_decode_parity_two_steps(arch, include):
    """The per-region LCC route (one launch per q/k/v, o, gate/up and down
    region) tracks dense-effective decode <= 1e-4 over two steps, KV leaves
    included, and routes every compressed site."""
    base = get_arch(arch)
    n_kv = 2 if base.n_kv_heads < base.n_heads else 4
    cfg = reduced_config(base, d_model=32, n_heads=4, n_kv_heads=n_kv,
                         head_dim=8, d_ff=48, vocab=64, n_layers=2)
    assert (cfg.n_kv_heads < cfg.n_heads) == (arch != "olmo-1b")
    params = _with_random_biases(api.init_params(jax.random.PRNGKey(0), cfg))
    assert ("b" in params["blocks"]["attn"]["q"]) == (arch == "qwen2.5-3b")
    art = api.compress_model(params, cfg, _fp(), include=include)
    if include is not None:
        assert art.records and all(n.startswith(include) for n in art.records)
    ex = _two_step_parity(art)
    assert ex.routed == ex.sites == set(art.records)


@pytest.mark.parametrize("n_layers", [1, 2])
@pytest.mark.parametrize("kv_block", [16, None], ids=["paged", "contiguous"])
def test_engine_kernel_tokens_and_launches(n_layers, kv_block):
    """Engine level: kernel tokens == dense-effective tokens, and an
    all-sites dense artifact's fused decode step emits four LCC launches a
    layer (q/k/v, o, gate/up, down), as the chip's step does."""
    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=n_layers)
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    comp = core.CompressionConfig(algorithm="fp", weight_sharing=False)
    art = api.compress_model(params, cfg, comp)
    eng_k = ServingEngine(artifact=art, n_slots=2, max_len=32,
                          kv_block=kv_block)
    eng_d = ServingEngine(artifact=art, n_slots=2, max_len=32,
                          kv_block=kv_block, use_kernel=False)
    prompts = [[5, 9, 2, 7], [3, 1]]
    out_k = eng_k.generate(prompts, max_new_tokens=6, temperature=0.0)
    out_d = eng_d.generate(prompts, max_new_tokens=6, temperature=0.0)
    assert [r.tokens for r in out_k] == [r.tokens for r in out_d]
    assert eng_k.executor.routed == eng_k.executor.sites
    assert eng_k.pallas_launches_per_step == 4 * n_layers


def test_artifact_with_legacy_plans_section_loads_and_decodes(tmp_path):
    """Artifacts written by older builds carry a ``"plans"`` manifest section
    and ``plans/...`` arrays; loading skips both, and the artifact decodes
    bitwise like the same artifact saved without them."""
    import json

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.core.artifact import _unflatten

    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    art = api.compress_model(params, cfg, _fp())
    plain = str(tmp_path / "plain")
    art.save(plain)

    flat = Checkpointer(plain).restore_flat(0)
    man = json.loads(np.asarray(flat["manifest"], np.uint8).tobytes())
    assert "plans" not in man
    man["plans"] = {"step": {"qkv": {
        "k_alloc": 33, "d_src": 32, "out_dim": 64, "n_layers": 2,
        "site_names": ["attn.q.l0", "attn.k.l0", "attn.v.l0"],
        "present": ["gidx", "gsgn"]}}}
    flat["manifest"] = np.frombuffer(json.dumps(man).encode(), np.uint8).copy()
    flat["plans/step/qkv/gidx"] = np.zeros((2, 1, 8, 2), np.int32)
    flat["plans/step/qkv/gsgn"] = np.ones((2, 1, 8, 2), np.int8)
    legacy = str(tmp_path / "legacy")
    Checkpointer(legacy).save(0, _unflatten(flat), blocking=True)
    assert "plans/step/qkv/gidx" in Checkpointer(legacy).restore_flat(0)

    art_p, art_l = CompressedModel.load(plain), CompressedModel.load(legacy)
    assert set(art_l.records) == set(art_p.records)
    assert set(art_l.packed) == set(art_p.packed)
    state = api.init_decode_state(cfg, 2, 8)
    tok = jnp.asarray([[3]] * 2, jnp.int32)
    pos = jnp.asarray([0] * 2, jnp.int32)

    def run(a):
        ex = CompressedExecutor(a, interpret=None)
        logits, _ = jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                                 executor=ex))(a.params)
        return ex, np.asarray(logits)

    ex_l, l_l = run(art_l)
    _, l_p = run(art_p)
    np.testing.assert_array_equal(l_l, l_p)
    assert ex_l.routed == ex_l.sites == set(art.records)


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
    ex = CompressedExecutor(art, interpret=None)
    state = api.init_decode_state(cfg, 1, 8)
    tok = jnp.asarray([[3]], jnp.int32)
    pos = jnp.asarray([0], jnp.int32)
    jax.jit(lambda p: api.decode(p, cfg, state, tok, pos,
                                 executor=ex))(art.params)
    pw = art.pipeline_stats.get("padding_waste", {})
    assert pw, "grouped regions must record their padding waste"
    assert all(0.0 <= v["mean_row_waste"] <= 1.0 for v in pw.values())
