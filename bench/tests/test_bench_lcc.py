"""The synthetic LCC artifact: its dense-effective weights equal the plain
application of its chains, its scale follows the dense initialiser's, and
the engine's compressed serving of it agrees with the benchmark's
reference."""
import numpy as np
import pytest

import bench_testlib
from harness.spec import load_module
from harness.traffic import rng_for

lcc = load_module(bench_testlib.ROOT, "systems", "lcc")


def _apply_chains(site, x):
    """y = sum over slices of F_P ... F_1 x_slice, by loops over terms."""
    n = site["idx"].shape[2]
    y = np.zeros(n)
    for e, (c0, c1) in enumerate(site["slices"]):
        cur = x[c0:c1]
        for p in range(site["idx"].shape[1]):
            nxt = np.zeros(n)
            for r in range(n):
                for s in range(site["idx"].shape[3]):
                    nxt[r] += (site["sign"][e, p, r, s]
                               * 2.0 ** site["exp"][e, p, r, s]
                               * cur[site["idx"][e, p, r, s]])
            cur = nxt
        y += cur
    return y


def test_effective_weight_is_the_chains_applied():
    n, k = 48, 40
    site = lcc.synth_site(rng_for(7, "artifact", 0, 0), n, k, 3, 2)
    assert site["slices"] == lcc.slice_grid(n, k)
    w = np.asarray(lcc.effective_weight(site, k), np.float64)
    x = np.random.default_rng(1).standard_normal(k)
    np.testing.assert_allclose(w @ x, _apply_chains(site, x), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("n,k,factors", [(2048, 2048, 6), (2048, 8192, 5)])
def test_scale_follows_the_dense_initialiser(n, k, factors):
    site = lcc.synth_site(rng_for(11, "artifact", 0, 1), n, k, factors, 2)
    w = np.asarray(lcc.effective_weight(site, k), np.float64)
    # output variance for unit inputs, against truncnormal(-2, 2)'s 0.774
    assert 0.5 < (w ** 2).sum(axis=1).mean() / lcc.INIT_VAR < 2.0


def test_compressed_serving_agrees_with_the_reference(tmp_path, monkeypatch):
    root = bench_testlib.tiny_root(tmp_path)
    r = bench_testlib.run_tiny(monkeypatch, root, "tiny-lcc")
    assert r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] <= bench_testlib.TINY_LIMIT
    assert r["checks"]["tokens_checked"]["value"] >= 40
    assert r["failed"] == 0 and r["attempted"] >= 4
