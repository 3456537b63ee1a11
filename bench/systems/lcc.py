"""A dense decoder whose every projection is served as an LCC artifact on the
program's compiled kernels (``ServingEngine(artifact=..., use_kernel=True)``).

Compressing published widths takes far longer than a run (about 55 minutes
for one FFN site on the host), so the artifact is built from the seed with
the chain structure the repository's FP compressor produces at these shapes
(``lcc`` in the configuration file, measured by
``bench/tools/chain_sample.py``): every column kept, no weight sharing,
column slices of width round(log2 N) clipped to [2, 16], and per slice a
chain of ``factors`` sparse factors whose rows each sum ``terms_per_row``
signed powers of two.  The first factor reads the slice's columns, each
later one the previous factor's N rows.  Later factors' exponents are 0 or
-1 (a factor keeps the scale of what it reads, on average 1.25x in
variance); the first factor's are the two adjacent powers whose mix gives
the layer's output the variance of the dense initialiser's (0.774 times the
input's).

The dense-effective weights — what the chains compute, used by the
program's prefill (rounded to the serving dtype) and by the reference (in
float32) — come from applying the chains to the identity by plain code here.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import program
from harness.spec import load_module
from harness.traffic import rng_for

__all__ = ["build", "slice_grid", "synth_site", "effective_weight",
           "site_dims", "SITES"]

SITES = (("attn", "q"), ("attn", "k"), ("attn", "v"), ("attn", "o"),
         ("ffn", "gate"), ("ffn", "up"), ("ffn", "down"))
# variance of truncated_normal(-2, 2): the dense initialiser's weights times
# fan_in, and so the output variance a site must give for unit inputs
INIT_VAR = 0.7737


def site_dims(spec, group: str, proj: str) -> tuple[int, int]:
    """(N out, K in) of a site, as y = W x."""
    qd, kvd = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    return {("attn", "q"): (qd, spec.d_model),
            ("attn", "k"): (kvd, spec.d_model),
            ("attn", "v"): (kvd, spec.d_model),
            ("attn", "o"): (spec.d_model, qd),
            ("ffn", "gate"): (spec.d_ff, spec.d_model),
            ("ffn", "up"): (spec.d_ff, spec.d_model),
            ("ffn", "down"): (spec.d_model, spec.d_ff)}[(group, proj)]


def slice_grid(n: int, k: int) -> list[tuple[int, int]]:
    """The compressor's column slices: width round(log2 N) in [2, 16]."""
    w = int(np.clip(round(math.log2(max(n, 2))), 2, 16))
    w = max(1, min(w, k))
    return [(c0, min(c0 + w, k)) for c0 in range(0, k, w)]


def synth_site(rng: np.random.Generator, n: int, k: int, factors: int,
               terms: int) -> dict:
    """Seeded chains of one site: ``idx`` [E, P, N, S] int32, ``exp`` and
    ``sign`` int8, and the column ``slices``."""
    slices = slice_grid(n, k)
    e = len(slices)
    widths = np.array([c1 - c0 for c0, c1 in slices])
    idx = rng.integers(0, n, size=(e, factors, n, terms), dtype=np.int64)
    idx[:, 0] = (rng.random((e, n, terms))
                 * widths[:, None, None]).astype(np.int64)
    sign = rng.choice(np.array([-1, 1], np.int8), size=idx.shape)
    exp = rng.integers(-1, 1, size=idx.shape).astype(np.int8)
    # first factor: E[c^2] = m so that E slices of (terms * m * 1.25^(P-1))
    # give the initialiser's output variance
    m = INIT_VAR / (e * terms * 1.25 ** (factors - 1))
    a = math.ceil(math.log(m, 4))
    q = (m - 4.0 ** (a - 1)) / (4.0 ** a - 4.0 ** (a - 1))
    exp[:, 0] = np.where(rng.random((e, n, terms)) < q, a, a - 1)
    return {"idx": idx.astype(np.int32), "exp": exp, "sign": sign,
            "slices": slices}


def effective_weight(site: dict, k: int) -> jnp.ndarray:
    """W [N, K] in float32 that the chains of ``site`` compute."""
    w = max(c1 - c0 for c0, c1 in site["slices"])
    cur = _chains_dense(site["idx"], site["exp"], site["sign"], w)
    e, n, _ = cur.shape
    return jnp.transpose(cur, (1, 0, 2)).reshape(n, e * w)[:, :k]


@partial(jax.jit, static_argnames=("width",))
def _chains_dense(idx, exp, sign, width: int):
    """[E, N, W]: every slice's chain applied to the identity of its
    width (plain gathers and sums, float32)."""
    coef = sign.astype(jnp.float32) * jnp.exp2(exp.astype(jnp.float32))

    def one(ix, cf):
        cur = jnp.einsum("ns,nsw->nw", cf[0],
                         jax.nn.one_hot(ix[0], width, dtype=jnp.float32))
        for p in range(1, ix.shape[0]):
            cur = jnp.einsum("ns,nsw->nw", cf[p], cur[ix[p]])
        return cur

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one)(idx, coef)


class System:
    def __init__(self, cell, seed: int, devices):
        dense = load_module(cell.root, "systems", "dense")
        self._dense = dense.System(cell, seed, devices)
        for k in ("ref", "spec", "cfg", "split", "cell", "seed"):
            setattr(self, k, getattr(self._dense, k))
        self.lcc = cell.config["lcc"]
        self.engine = None
        self.lcc_sites = None

    def _sites(self):
        """Yield (layer, group, proj, N, K, chains) for every site."""
        for li in range(self.spec.layers):
            for si, (group, proj) in enumerate(SITES):
                n, k = site_dims(self.spec, group, proj)
                key = "attn" if group == "attn" else f"ffn.{proj}"
                chains = synth_site(rng_for(self.seed, "artifact", li, si),
                                    n, k, int(self.lcc["factors"][key]),
                                    int(self.lcc["terms_per_row"]))
                yield li, group, proj, n, k, chains

    def _params(self, dtype, keep=None):
        """The seeded weights with every site replaced by its chains'
        dense equivalent, in ``dtype``; ``keep(li, group, proj, n, k,
        chains)`` sees each site's chains."""
        p = self._dense.weights(dtype)
        blocks = p["blocks"]
        for li, group, proj, n, k, chains in self._sites():
            w = effective_weight(chains, k).T.astype(dtype)  # [K, N]
            leaf = blocks[group][proj]["w"]
            blocks[group][proj]["w"] = leaf.at[li].set(w)
            if keep is not None:
                keep(li, group, proj, n, k, chains)
        return p

    def build(self):
        program.import_program()
        from repro.core.artifact import CompressedModel
        from repro.core.compress import CompressedDense
        from repro.core.lcc import LCCChain, LCCDecomposition, LCCFactor

        records, sites = {}, []

        def keep(li, group, proj, n, k, ch):
            chains = []
            for e, (c0, c1) in enumerate(ch["slices"]):
                fs = [LCCFactor(idx=ch["idx"][e, p], exp=ch["exp"][e, p],
                                sign=ch["sign"][e, p],
                                in_dim=(c1 - c0) if p == 0 else n)
                      for p in range(ch["idx"].shape[1])]
                chains.append(LCCChain(factors=fs, in_dim=c1 - c0))
            name = f"{group}.{proj}.l{li}"
            records[name] = CompressedDense(
                name=name, kept_columns=np.arange(k), shared=None,
                decomposition=LCCDecomposition(
                    shape=(n, k), col_slices=list(ch["slices"]),
                    slices=chains, algorithm="fp", target_snr_db=0.0),
                effective=None)
            sites.append((name, int(np.count_nonzero(ch["sign"])), k, n))

        t0 = time.perf_counter()
        params = self._params(jnp.dtype(self.cell.config["serve_dtype"]),
                              keep)
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        art = CompressedModel(config=self.cfg, params=params,
                              records=records)
        self.engine = program.build_engine(
            artifact=art, deployment=self.cell.deployment, seed=self.seed)
        self.lcc_sites = sites
        self.split.update(artifact_s=t1 - t0,
                          engine_s=time.perf_counter() - t1)
        return self

    def reference_params(self):
        """Float32 weights with every site the exact dense equivalent of
        its chains, made again from the seed."""
        return self._params(jnp.float32)


def build(cell, seed: int, devices) -> System:
    return System(cell, seed, devices).build()
