"""Compile the chip path's Pallas kernels and decode step for a described
TPU v5e.

Nothing runs: each test lowers a kernel at a published olmo-1b site shape
through the TPU compiler against a v5e topology that is described, not
attached, so a kernel that Mosaic refuses (tiling, scoped VMEM) or that takes
minutes to compile fails here instead of on the chip.  The serving engine's
fused decode step is compiled the same way and its optimised HLO read for
ops that would move the whole KV pool.  The topology is
described inside a module fixture only — never at import — so every test
worker collects the same tests and only the one that runs this file loads the
TPU library.
"""
import math
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch, reduced_config
from repro.configs.base import MLASpec
from repro.core.lcc import plan_col_slices
from repro.kernels.group_prox import group_prox
from repro.kernels.lcc_chain_matmul import lcc_chain_matmul, term_rows
from repro.kernels.lcc_group_matmul import lcc_group_matmul
from repro.kernels.lcc_matmul import lcc_factor_matmul
from repro.kernels.shared_matmul import cluster_segment_sum
from repro.models import api
from repro.serving.engine import ServingEngine

# olmo-1b sites (out N, in K): attn.q 2048->2048, ffn.up 2048->8192,
# ffn.down 8192->2048; slices as the compressor cuts them, 4 factors, S = 2
OLMO_SITES = {"attn.q": (2048, 2048), "ffn.up": (8192, 2048),
              "ffn.down": (2048, 8192)}
P_FACTORS, S_TERMS, BATCH = 4, 2, 8
COMPILE_BUDGET_S = 30.0  # the unbounded kernel took > 110 s at N_pad 8192
# olmo1b-lcc-decode's own launches (bench/configs/olmo-1b-lcc.json): slices
# of width round(log2 N) as the compressor cuts them, 6 factors (5 for
# ffn.down), 2 terms a row, 32 decode slots
CELL = {"attn.q": 6, "ffn.up": 6, "ffn.down": 5}
CELL_BATCH = 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler / library lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*shapes).compile()
    dt = time.perf_counter() - t0
    assert dt < COMPILE_BUDGET_S, f"compile took {dt:.1f}s"
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel, not XLA
    return compiled


def _chain_shapes(sharding, site, groups=None, cell=False, slice_width=None):
    n, k = OLMO_SITES[site]
    slices = plan_col_slices(n, k, round(math.log2(n)) if cell
                             else slice_width)
    width = max(c1 - c0 for c0, c1 in slices)
    lead = (len(slices),) if groups is None else (groups, len(slices))
    term = lead + (CELL[site] if cell else P_FACTORS, n, S_TERMS)
    batch = CELL_BATCH if cell else BATCH

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    words = term[:-2] + (1, S_TERMS * term_rows(n))  # pack_terms' layout
    # ops passes the slice inputs as first_width rows, sublane-rounded
    return width, batch, (sds(term, jnp.int32), sds(term, jnp.int8),
                          sds(term, jnp.int8),
                          sds(lead + (-(-width // 8) * 8, batch), jnp.float32),
                          sds(words, jnp.int32))


# "wide": slices of 1024 columns, past DENSE_FIRST_MAX, so the first factor
# is gathered by row as well
@pytest.mark.parametrize("site,cell,slice_width", [
    *(pytest.param(s, False, None, id=s) for s in sorted(OLMO_SITES)),
    *(pytest.param(s, True, None, id=f"cell-{s}")
      for s in ("attn.q", "ffn.down")),
    pytest.param("ffn.up", False, 1024, id="wide-ffn.up")])
def test_lcc_chain_matmul_compiles(one_chip, site, cell, slice_width):
    width, batch, shapes = _chain_shapes(one_chip, site, cell=cell,
                                         slice_width=slice_width)
    _compile(lambda i, e, s, x, t: lcc_chain_matmul(
        i, e, s, x, block_b=batch, first_width=width, interpret=False,
        terms=t), *shapes)


@pytest.mark.parametrize("site,groups,cell", [
    *(pytest.param(s, g, False, id=f"{s}-{g}")
      for s, g in [("attn.q", 3), ("ffn.up", 2), ("ffn.down", 1)]),
    *(pytest.param(s, g, True, id=f"cell-{s}-{g}")
      for s, g in [("attn.q", 3), ("ffn.up", 2)])])
def test_lcc_group_matmul_compiles(one_chip, site, groups, cell):
    width, batch, shapes = _chain_shapes(one_chip, site, groups, cell=cell)
    _compile(lambda i, e, s, x, t: lcc_group_matmul(
        i, e, s, x, block_b=batch, first_width=width, interpret=False,
        terms=t), *shapes)


@pytest.mark.parametrize("shape,dtype", [((2048, 8192), jnp.float32),
                                         ((8192, 2048), jnp.bfloat16)])
def test_group_prox_compiles(one_chip, shape, dtype):
    _compile(lambda a: group_prox(a, 0.1, interpret=False),
             jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip))


def test_cluster_segment_sum_compiles(one_chip):
    k, c = 8192, 2048  # ffn.down inputs shared into 2048 clusters
    _compile(lambda lab, x: cluster_segment_sum(
        lab, x, num_clusters=c, block_b=BATCH, interpret=False),
        jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, BATCH), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("site", sorted(OLMO_SITES))
def test_lcc_factor_matmul_compiles(one_chip, site):
    n, k = OLMO_SITES[site]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(lambda i, e, s, x: lcc_factor_matmul(
        i, e, s, x, block_b=BATCH, interpret=False),
        sds((n, S_TERMS), jnp.int32), sds((n, S_TERMS), jnp.int8),
        sds((n, S_TERMS), jnp.int8), sds((k, BATCH), jnp.float32))


# ops that would copy, slice or restack a KV pool rather than write its rows
POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "concatenate",
              "pad", "slice")
_HLO_OP = re.compile(r"(%[\w.-]+) = \w+\[([\d,]*)\](?:\{([^}]*)\})? ([\w-]+)\((.*)")


def _hlo_ops(text):
    """(name, dims, minor-to-major, opcode, operand names, fused) of every
    array op; ``fused`` marks an op inside a fusion's computation."""
    fusions = set(re.findall(r" fusion\(.*?calls=(%[\w.-]+)", text))
    ops, fused = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) .*\{$", line)
        if head:
            fused = head.group(1) in fusions
        m = _HLO_OP.search(line)
        if m:
            name, dims, layout, opcode, rest = m.groups()
            ops.append((name, tuple(int(d) for d in dims.split(",") if d),
                        (layout or "").split(":")[0], opcode,
                        re.findall(r"%[\w.-]+", rest.split("),")[0]), fused))
    return ops


def _decode_cfg(family):
    """Tiny serving configs at the published pool widths (olmo-1b's 128-wide
    heads; DeepSeek-V2-Lite's 512-wide latent and 64-wide rope key), so the
    compiler lays the pools out as it does at full size."""
    bf16 = dict(param_dtype="bfloat16", compute_dtype="bfloat16")
    if family == "dense":
        return reduced_config(get_arch("olmo-1b"), head_dim=128, **bf16)
    mla = MLASpec(kv_lora=512, qk_nope=32, qk_rope=64, v_dim=32)
    cfg = reduced_config(get_arch("deepseek-v2-lite-16b"), mla=mla, **bf16)
    assert cfg.n_dense_layers == 1 and cfg.n_layers == 2
    return cfg


@pytest.mark.parametrize("family", ["dense", "mla"])
def test_decode_step_writes_pool_in_place(one_chip, family):
    """The engine's fused, donated decode step (dense GQA; MLA with a leading
    dense layer before the MoE stack) at olmo1b-decode's pool geometry: 32
    slots, 1152 positions, 2312 blocks of 16.  No op copies, slices, pads or
    restacks a whole pool leaf ``[L, Nb, ...]`` or one layer's pool
    ``[Nb, ...]`` — each layer writes its rows with a scatter into the carried
    pools — and every pool output aliases its donated input.  The one
    exception is a pool whose minor dimension is narrower than a 128-lane row
    (MLA's 64-wide rope key): the chip keeps it in a layout of its own, and
    the layer loop's scatter and gather take it row-major, so it may be
    relaid once into the loop and once out.  Compiled here for a described
    v5e (no chip); the step's MoE launches lower in interpret mode.  The
    gathered view must also reach attention without an f32 copy of it, and
    MLA's heads attend to the latent itself: no op, fused or not, expands
    the view to per-head keys or values or copies the rotary key to every
    head."""
    cfg = _decode_cfg(family)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, n_slots=2, max_len=32, kv_block=16,
                        metrics=False)
    n = 32
    state = jax.eval_shape(
        lambda: api.init_decode_state(cfg, n, 1152, kv_block=16))

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    vec = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    args = (jax.tree.map(sds, eng.params), jax.tree.map(sds, state), vec, vec,
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip), vec, vec,
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    t0 = time.perf_counter()
    text = eng._step_fn.lower(*args).compile().as_text()
    assert time.perf_counter() - t0 < COMPILE_BUDGET_S

    pools = {k: v.shape for k, v in state.items()
             if k not in ("kpos", "block_tbl")}
    assert pools and all(s[1] == 2312 for s in pools.values())
    ops = _hlo_ops(text)
    shape_of = {name: (dims, layout) for name, dims, layout, *_ in ops}
    for leaf, shape in pools.items():
        relaid = 0
        for name, dims, layout, opcode, operands, _ in ops:
            if opcode not in POOL_MOVES:
                continue
            touched = [dims] + [shape_of[o][0] for o in operands
                                if o in shape_of]
            if not any(t in (shape, shape[1:]) for t in touched):
                continue
            src = shape_of.get(operands[0] if operands else "", ((), ""))
            if (opcode == "copy" and dims == shape and src[0] == shape
                    and src[1] != layout and shape[-1] < 128):
                relaid += 1
                continue
            raise AssertionError(f"{leaf}: {opcode} {name} moves the pool")
        assert relaid <= 2, f"{leaf}: relaid {relaid} times"
        # the gathered view reaches attention in the pool's dtype: no layer
        # writes an f32 copy of it
        mb = state["block_tbl"].shape[1]
        views = {(n * mb, shape[2]) + shape[3:], (n, mb * shape[2]) + shape[3:]}
        assert not [name for name, dims, _, opcode, _, fused in ops
                    if opcode == "convert" and dims in views and not fused], leaf
        assert any(opcode == "scatter" and dims == shape
                   for _, dims, _, opcode, *_ in ops), f"{leaf}: no row write"
    if family == "mla":
        h, t = cfg.n_heads, state["block_tbl"].shape[1] * 16
        widths = {cfg.mla.qk_nope, cfg.mla.v_dim, cfg.mla.qk_rope,
                  cfg.mla.qk_nope + cfg.mla.qk_rope}
        per_head = ({(n, t, h, d) for d in widths}
                    | {(n, t, h * d) for d in widths}
                    | {(n * t, h * d) for d in widths})
        assert not [(name, dims) for name, dims, *_ in ops
                    if dims in per_head]

    # output j (the new state's leaves lead, in key order) aliases the
    # entry parameter that holds the donated state's leaf
    head = text[text.index("input_output_alias="):]
    alias = dict(re.findall(r"\{(\d+)\}: \((\d+),",
                            head[:head.index("entry_computation_layout")]))
    for j, key in enumerate(sorted(state)):
        if key in pools:
            param = re.search(rf"%state__{key}__\S* = .* parameter\((\d+)\)",
                              text)
            assert param and alias.get(str(j)) == param.group(1), (key, alias)
