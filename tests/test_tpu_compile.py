"""Compile the chip path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at a published olmo-1b site shape
through the TPU compiler against a v5e topology that is described, not
attached, so a kernel that Mosaic refuses (tiling, scoped VMEM) or that takes
minutes to compile fails here instead of on the chip.  The topology is
described inside a module fixture only — never at import — so every test
worker collects the same tests and only the one that runs this file loads the
TPU library.
"""
import math
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lcc import plan_col_slices
from repro.kernels.group_prox import group_prox
from repro.kernels.lcc_chain_matmul import lcc_chain_matmul, term_rows
from repro.kernels.lcc_group_matmul import lcc_group_matmul
from repro.kernels.lcc_matmul import lcc_factor_matmul
from repro.kernels.shared_matmul import cluster_segment_sum

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
