"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lcc import lcc_decompose
from repro.kernels import ops, ref
from repro.kernels.lcc_chain_matmul import pack_terms
from repro.kernels.group_prox import group_prox
from repro.kernels.lcc_matmul import lcc_factor_matmul
from repro.kernels.shared_matmul import cluster_segment_sum


@pytest.mark.parametrize("n,k,b,s", [(128, 128, 128, 2), (256, 128, 64, 3),
                                     (128, 256, 32, 4), (384, 128, 128, 2)])
def test_lcc_factor_matmul_shapes(n, k, b, s):
    rng = np.random.default_rng(n + k + b)
    idx = jnp.asarray(rng.integers(0, k, (n, s)), jnp.int32)
    exp = jnp.asarray(rng.integers(-8, 8, (n, s)), jnp.int8)
    sign = jnp.asarray(rng.choice([-1, 0, 1], (n, s)), jnp.int8)
    x = jnp.asarray(rng.standard_normal((k, b)), jnp.float32)
    got = lcc_factor_matmul(idx, exp, sign, x, block_n=128, block_k=128, block_b=min(b, 128))
    want = ref.lcc_factor_matmul_ref(idx, exp, sign, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lcc_factor_matmul_dtypes(dtype):
    rng = np.random.default_rng(7)
    n, k, b, s = 128, 128, 128, 2
    idx = jnp.asarray(rng.integers(0, k, (n, s)), jnp.int32)
    exp = jnp.asarray(rng.integers(-6, 6, (n, s)), jnp.int8)
    sign = jnp.asarray(rng.choice([-1, 1], (n, s)), jnp.int8)
    x = jnp.asarray(rng.standard_normal((k, b)), dtype)
    got = lcc_factor_matmul(idx, exp, sign, x)
    want = ref.lcc_factor_matmul_ref(idx, exp, sign, x.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_chain_apply_matches_decomposition():
    rng = np.random.default_rng(8)
    w = rng.standard_normal((96, 24))
    dec = lcc_decompose(w, algorithm="fp", target_snr_db=40.0)
    packed = ops.pack_decomposition(dec)
    x = jnp.asarray(rng.standard_normal((24, 7)), jnp.float32)
    got = np.asarray(ops.apply_packed_decomposition(packed, x))
    want = dec.to_dense() @ np.asarray(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape,b", [((96, 24), 7), ((50, 13), 5), ((128, 24), 128),
                                     ((512, 16), 33), ((37, 5), 1)])
def test_fused_decomposition_matches_numpy_apply(shape, b):
    """Fused whole-chain kernel == LCCDecomposition.apply (numpy reference)
    over odd/padded shapes and multi-slice decompositions (acceptance: 1e-5)."""
    rng = np.random.default_rng(shape[0] + b)
    w = rng.standard_normal(shape)
    dec = lcc_decompose(w, algorithm="fp", target_snr_db=35.0)
    assert len(dec.col_slices) >= 2 or shape[1] <= 16  # exercise multi-slice
    packed = ops.pack_decomposition(dec)
    x = jnp.asarray(rng.standard_normal((shape[1], b)), jnp.float32)
    want = dec.apply(np.asarray(x, np.float64))
    got = np.asarray(ops.apply_packed_decomposition(packed, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_equals_per_factor_loop():
    """The single-launch kernel and the per-factor pallas_call loop are two
    implementations of the same chain — bitwise-comparable f32 results."""
    rng = np.random.default_rng(21)
    w = rng.standard_normal((160, 40))
    dec = lcc_decompose(w, algorithm="fp", target_snr_db=35.0, slice_width=11)
    packed = ops.pack_decomposition(dec)
    x = jnp.asarray(rng.standard_normal((40, 19)), jnp.float32)
    fused = np.asarray(ops.apply_packed_decomposition(packed, x))
    loop = np.asarray(ops.apply_packed_decomposition(packed, x, fused=False))
    np.testing.assert_allclose(fused, loop, rtol=1e-6, atol=1e-6)


def test_fused_chain_padded_rows_stay_zero():
    """sign==0 invariant: rows beyond every factor's true out_dim decompress
    to zero and stay exactly zero through the whole chain."""
    from repro.kernels.lcc_chain_matmul import lcc_chain_matmul

    rng = np.random.default_rng(22)
    w = rng.standard_normal((200, 16))  # out_dim 200 pads to n_pad 256
    dec = lcc_decompose(w, algorithm="fp", target_snr_db=30.0, slice_width=16)
    pc = ops.pack_chain(dec.slices[0], block=128)
    n_pad = pc.idx.shape[1]
    assert n_pad > pc.out_dim  # the invariant must have real rows to bite on
    x = jnp.zeros((1, pc.d_pad, 8), jnp.float32).at[0, : pc.in_dim].set(
        jnp.asarray(rng.standard_normal((pc.in_dim, 8)), jnp.float32))
    y = np.asarray(lcc_chain_matmul(pc.idx[None], pc.exp[None], pc.sign[None], x,
                                    block_b=8, first_width=pc.first_width))
    assert y.shape[0] == n_pad
    np.testing.assert_array_equal(y[pc.out_dim:], 0.0)
    want = dec.slices[0].apply(np.asarray(x[0, : pc.in_dim], np.float64))
    np.testing.assert_allclose(y[: pc.out_dim], want, rtol=1e-5, atol=1e-5)


def test_grouped_launch_matches_per_decomposition():
    """lcc_group_matmul applies G whole decompositions in ONE launch == the
    per-decomposition fused path, across mixed shapes, chain lengths, slice
    counts and an FS-only (dense-fallback) member."""
    rng = np.random.default_rng(31)
    decs = []
    for g, (shape, algo, sw) in enumerate([((24, 16), "fp", None),
                                           ((8, 16), "fp", None),
                                           ((24, 40), "fs", 8),
                                           ((12, 12), "fp", None)]):
        decs.append(lcc_decompose(rng.standard_normal(shape), algorithm=algo,
                                  target_snr_db=35.0, slice_width=sw))
    packed = [ops.pack_decomposition(d) for d in decs]
    pg = ops.pack_group(packed)
    xs = [jnp.asarray(rng.standard_normal((d.shape[1], 5)), jnp.float32)
          for d in decs]
    ys = ops.apply_packed_group(pg, xs)
    for g, (d, x, y) in enumerate(zip(decs, xs, ys)):
        want = np.asarray(ops.apply_packed_decomposition(packed[g], x))
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6, atol=1e-6,
                                   err_msg=f"group member {g}")


def _chain_streams(rng, g, e, p, n, w, *, out_dim=None, lengths=None,
                   live_slices=None):
    """Random [G, E, P, N, 2] term streams with the packers' invariants:
    rows past ``out_dim`` carry sign 0, slice (g, e) is a chain of
    ``lengths[g][e]`` factors continued by identity factors, and group g
    has ``live_slices[g]`` slices (the rest all sign 0, a zero input)."""
    idx = rng.integers(0, n, (g, e, p, n, 2))
    idx[:, :, 0] = rng.integers(0, w, (g, e, n, 2))
    exp = rng.integers(-3, 2, idx.shape).astype(np.int8)
    sign = rng.choice(np.array([-1, 0, 1], np.int8), idx.shape)
    if out_dim is not None:
        sign[..., out_dim:, :] = 0
    for gi, row in enumerate(lengths or []):
        for ei, length in enumerate(row):
            idx[gi, ei, length:, :, 0] = np.arange(n)
            idx[gi, ei, length:, :, 1] = 0
            sign[gi, ei, length:] = [1, 0]
    for gi, live in enumerate(live_slices or []):
        sign[gi, live:] = 0
    return idx.astype(np.int32), exp, sign


# group members as ops packs them: the FS member (24 x 40, slices of 8) has
# no FP slice, so its slices in the launch are all sign 0 and its output is
# its dense slices alone, which ops adds outside the launch
OPS_MEMBERS = [((24, 16), "fp", None), ((8, 16), "fp", None),
               ((24, 40), "fs", 8), ((12, 12), "fp", None)]


def _member_streams(rng, members, b):
    """``ops.pack_group`` of the ``members``' decompositions, [K, b] inputs
    for them, and those inputs cut into slabs as ``ops.apply_packed_group``
    cuts them."""
    import warnings

    decs = [lcc_decompose(rng.standard_normal(shape), algorithm=alg,
                          target_snr_db=35.0, slice_width=width)
            for shape, alg, width in members]
    with warnings.catch_warnings():  # the badly matched group is the point
        warnings.simplefilter("ignore")
        pg = ops.pack_group([ops.pack_decomposition(d) for d in decs])
    xs = [rng.standard_normal((d.shape[1], b)).astype(np.float32)
          for d in decs]
    rows = -(-pg.first_width // 8) * 8
    slabs = np.zeros(pg.idx.shape[:2] + (rows, b), np.float32)
    for gi, (m, xm) in enumerate(zip(pg.members, xs)):
        for ei, (c0, c1) in enumerate(m.col_slices):
            slabs[gi, ei, : c1 - c0] = xm[c0:c1]
    return decs, pg, xs, slabs


# (g, e, p, n_pad, first_width, x rows, batch, extra): chains are groups of
# one; n_pad 200 is not a multiple of an SMEM tile, 256 is; a first factor
# wider than DENSE_FIRST_MAX columns is gathered too, narrower ones are tiles
@pytest.mark.parametrize("g,e,p,n,w,d,b,extra", [
    (1, 3, 4, 200, 16, 200, 8,
     dict(out_dim=190, lengths=[[4, 2, 1]])),  # identity-padded, sign-0 rows
    (1, 2, 3, 64, 8, 8, 5, {}),  # batch not a multiple of 8, x of 8 rows
    (1, 2, 2, 32, 8, 32, 130, {}),  # batch over one 128-lane block
    (1, 3, 1, 96, 16, 96, 8, {}),  # a single factor
    (1, 2, 3, 32, 40, 40, 8, {}),  # slices wider than the output
    (1, 2, 3, 256, 520, 520, 8, {}),  # a first factor too wide for a tile
    (3, 4, 3, 48, 12, 48, 8, dict(live_slices=[4, 2, 0])),  # zero slices
    (2, 2, 6, 256, 13, 256, 32, dict(lengths=[[6, 3], [2, 6]])),
    (4, 6, 9, 24, 5, 8, 5, dict(members=OPS_MEMBERS, seed=31)),  # FS member
], ids=["chain-padded", "chain-b5", "chain-b130", "chain-p1", "chain-wide",
        "chain-first-gather", "group-zero-slices", "group-n256",
        "group-ops-members"])
def test_row_gather_body_matches_gather_and_ref(g, e, p, n, w, d, b, extra):
    """The compiled row-gather body, run under the interpreter, equals the
    interpreter's gather body and the dense oracle, chains and groups."""
    from repro.kernels.lcc_chain_matmul import lcc_chain_matmul
    from repro.kernels.lcc_group_matmul import lcc_group_matmul

    rng = np.random.default_rng(extra.get("seed", n + b + g))
    bb = min(b, 128)
    b_pad = -(-b // bb) * bb
    if "members" in extra:
        decs, pg, xs, x = _member_streams(rng, extra["members"], b_pad)
        idx, exp, sign = (np.asarray(a) for a in (pg.idx, pg.exp, pg.sign))
        assert idx.shape[:4] == (g, e, p, n) and x.shape[2] == d
        assert pg.first_width == w
    else:
        idx, exp, sign = _chain_streams(rng, g, e, p, n, w, **extra)
        x = np.zeros((g, e, d, b_pad), np.float32)
        x[:, :, :w, :b] = rng.standard_normal((g, e, w, b))
        x[sign.reshape(g, e, -1).any(-1) == 0] = 0.0  # a dead slice: no input
    want = np.zeros((g, n, b_pad), np.float32)
    for gi in range(g):
        for ei in range(e):
            want[gi] += np.asarray(ref.lcc_chain_apply_ref(
                list(zip(idx[gi, ei], exp[gi, ei], sign[gi, ei])), x[gi, ei]))
    kw = dict(block_b=bb, first_width=w, interpret=True)
    terms = pack_terms(idx, exp, sign)
    if g == 1:
        def run(**k):
            return np.asarray(lcc_chain_matmul(idx[0], exp[0], sign[0], x[0],
                                               **kw, **k))[None]
        terms = terms[0]
    else:
        def run(**k):
            return np.asarray(lcc_group_matmul(idx, exp, sign, x, **kw, **k))
    rows = run(use_gather=False, terms=terms)
    gather = run(use_gather=True)
    np.testing.assert_allclose(rows, gather, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(rows, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="pass terms"):
        run(use_gather=False)  # the row gather reads no other streams
    if "members" in extra:
        for gi, (dec, m, xm) in enumerate(zip(decs, pg.members, xs)):
            if not m.col_slices:  # the FS member: nothing in the launch
                np.testing.assert_array_equal(rows[gi], 0.0)
            np.testing.assert_allclose(  # with its dense slices, through ops
                np.asarray(ops.apply_packed_group(pg, xs)[gi]),
                dec.to_dense() @ xm, rtol=1e-5, atol=1e-5)
    if extra.get("out_dim"):
        np.testing.assert_array_equal(rows[:, extra["out_dim"]:], 0.0)
    for gi, live in enumerate(extra.get("live_slices", [])):
        if live == 0:
            np.testing.assert_array_equal(rows[gi], 0.0)


def test_pack_terms_word_layout():
    """A term word is the float32 bits of sign * 2^exp with the column in
    the mantissa, term-major over rows padded to an SMEM tile; unused slots
    are 0."""
    from repro.kernels.lcc_chain_matmul import term_rows

    idx = np.array([[[[3, 7], [0, 5]]]], np.int32)  # [E=1, P=1, N=2, S=2]
    exp = np.array([[[[-2, 1], [0, 0]]]], np.int8)
    sign = np.array([[[[1, -1], [0, 1]]]], np.int8)
    words = pack_terms(idx, exp, sign)
    rows = term_rows(2)
    assert rows == 128
    assert words.shape == (1, 1, 1, 2 * rows) and words.dtype == np.int32
    live = words[0, 0, 0].reshape(2, rows)[:, :2]  # [term, row]
    np.testing.assert_array_equal((live & -(1 << 23)).view(np.float32),
                                  [[0.25, 0.0], [-2.0, 1.0]])
    np.testing.assert_array_equal(live & ((1 << 23) - 1), [[3, 0], [7, 5]])
    assert words.reshape(2, rows)[:, 2:].sum() == 0
    with pytest.raises(ValueError):
        pack_terms(idx, np.full_like(exp, -127), sign)


def test_fused_kernel_interpret_override_matches():
    """Explicit interpret=True equals the auto-detected default on this host."""
    rng = np.random.default_rng(23)
    w = rng.standard_normal((64, 12))
    dec = lcc_decompose(w, algorithm="fp", target_snr_db=35.0)
    packed = ops.pack_decomposition(dec)
    x = jnp.asarray(rng.standard_normal((12, 6)), jnp.float32)
    auto = np.asarray(ops.apply_packed_decomposition(packed, x))
    forced = np.asarray(ops.apply_packed_decomposition(packed, x, interpret=True))
    np.testing.assert_allclose(auto, forced, rtol=1e-6, atol=1e-6)


def test_apply_packed_chain_matches_chain_apply():
    """Single-chain API: fused and per-factor paths == LCCChain.apply."""
    rng = np.random.default_rng(25)
    w = rng.standard_normal((96, 12))
    dec = lcc_decompose(w, algorithm="fp", target_snr_db=40.0, slice_width=12)
    chain = dec.slices[0]
    pc = ops.pack_chain(chain)
    x = jnp.asarray(rng.standard_normal((12, 9)), jnp.float32)
    want = chain.apply(np.asarray(x, np.float64))
    for fused in (True, False):
        got = np.asarray(ops.apply_packed_chain(pc, x, fused=fused))
        assert got.shape == (96, 9)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_mlp_forward_compressed_matches_dense():
    """models/ wiring: fc1 through the fused kernel tracks the dense forward
    at the decomposition's SNR and preserves argmax decisions."""
    import jax as _jax
    from repro.models.mlp import init_mlp, mlp_forward, mlp_forward_compressed

    rng = np.random.default_rng(26)
    params = init_mlp(_jax.random.PRNGKey(0), in_dim=48, hidden=64, classes=10)
    dec = lcc_decompose(np.asarray(params["fc1"]["w"], np.float64),
                        algorithm="fp", target_snr_db=50.0)
    packed = ops.pack_decomposition(dec)
    x = jnp.asarray(rng.standard_normal((5, 48)), jnp.float32)
    ref = mlp_forward(params, x)
    got = mlp_forward_compressed(params, packed, x)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0.05, atol=0.05)
    np.testing.assert_array_equal(np.argmax(np.asarray(got), -1),
                                  np.argmax(np.asarray(ref), -1))


def test_lcc_matvec_vector_input_with_sharing():
    """serving LCCMatvec: 1-D input works with and without weight sharing."""
    from repro import core
    from repro.serving.engine import LCCMatvec

    rng = np.random.default_rng(27)
    w = rng.standard_normal((40, 24))
    for share in (False, True):
        cd = core.compress_dense_matrix(
            f"t.share{share}", w,
            core.CompressionConfig(algorithm="fp", weight_sharing=share), None)
        mv = LCCMatvec(cd)
        x = rng.standard_normal(24)
        got = np.asarray(mv(jnp.asarray(x, jnp.float32)))
        want = cd.apply(x)
        assert got.shape == (40,)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fused_vector_input_and_fs_dense_fallback():
    """1-D input squeeze + FS slices combine through the dense fallback."""
    rng = np.random.default_rng(24)
    w = rng.standard_normal((48, 10))
    dec = lcc_decompose(w, algorithm="fs", target_snr_db=35.0)
    packed = ops.pack_decomposition(dec)
    assert packed.dense  # FS programs run via their dense equivalent
    x = rng.standard_normal(10)
    got = np.asarray(ops.apply_packed_decomposition(packed, jnp.asarray(x, jnp.float32)))
    want = dec.apply(x)
    assert got.shape == (48,)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k,b,c", [(128, 128, 128), (256, 64, 128), (128, 32, 256)])
def test_cluster_segment_sum(k, b, c):
    rng = np.random.default_rng(k + b + c)
    labels = jnp.asarray(rng.integers(0, c, k), jnp.int32)
    x = jnp.asarray(rng.standard_normal((k, b)), jnp.float32)
    got = cluster_segment_sum(labels, x, num_clusters=c)
    want = ref.cluster_segment_sum_ref(labels, x, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_shared_matmul_unaligned():
    """ops wrapper pads ragged (K, C, B) to block multiples."""
    rng = np.random.default_rng(9)
    cents = jnp.asarray(rng.standard_normal((33, 10)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 50), jnp.int32)
    x = jnp.asarray(rng.standard_normal((50, 9)), jnp.float32)
    got = np.asarray(ops.shared_matmul_tpu(cents, labels, x))
    want = np.asarray(cents) @ np.asarray(ref.cluster_segment_sum_ref(labels, x, 10))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("g,m", [(256, 64), (512, 33), (256, 301)])
@pytest.mark.parametrize("t", [0.0, 0.5, 10.0])
def test_group_prox_kernel(g, m, t):
    rng = np.random.default_rng(g + m)
    a = jnp.asarray(rng.standard_normal((g, m)), jnp.float32)
    got = group_prox(a, t)
    want = ref.group_prox_ref(a, t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_group_prox_bf16():
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((256, 128)), jnp.bfloat16)
    got = group_prox(a, 1.3)
    want = ref.group_prox_ref(a, 1.3)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_factor_stream_roundtrip():
    """Deployment byte stream: serialize -> parse -> identical dense factor."""
    rng = np.random.default_rng(12)
    w = rng.standard_normal((64, 8))
    dec = lcc_decompose(w, algorithm="fp", target_snr_db=35.0)
    from repro.core.lcc import LCCChain
    chain = next(s for s in dec.slices if isinstance(s, LCCChain))
    for f in chain.factors:
        blob = ops.factor_to_stream(f)
        f2 = ops.stream_to_factor(blob)
        np.testing.assert_array_equal(f.to_dense(), f2.to_dense())
        # stream size ~= the 3-bytes-per-term model (+1/row +12 header)
        nnz = int((f.sign != 0).sum())
        assert len(blob) == 12 + f.out_dim + 3 * nnz


def test_group_prox_zero_rows_boundary_unaligned():
    """Parity with ``group_prox_rows_np`` on the hard cases: zero-norm rows
    (exact 0 out, no NaN), rows at/near the threshold boundary, and a group
    count that is not a block multiple (the wrapper pads and slices)."""
    from repro.core.group_lasso import group_prox_rows_np

    rng = np.random.default_rng(5)
    a = rng.standard_normal((37, 16))
    a[[3, 17, 36]] = 0.0  # structurally-pruned groups
    a[5] *= 2.0 / np.linalg.norm(a[5])     # exactly at the threshold
    a[9] *= 1.995 / np.linalg.norm(a[9])   # just under -> zeroed
    a[11] *= 2.005 / np.linalg.norm(a[11])  # just over -> survives, tiny
    af = np.asarray(a, np.float32)
    got = np.asarray(group_prox(jnp.asarray(af), 2.0))
    want = group_prox_rows_np(af, 2.0)
    assert np.isfinite(got).all()
    assert (got[[3, 17, 36]] == 0.0).all()
    assert (got[9] == 0.0).all()
    assert np.abs(got[11]).max() > 0.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# CSD shift-add chains through lcc_chain_matmul vs ref oracles (bitwise)
# ---------------------------------------------------------------------------

def _csd_chain(idx, exp, sgn, x, *, use_gather):
    """Apply a raw CSD chain [P, R, S] to x [K, B] in one ``lcc_chain_matmul``
    launch: the interpreter's gather body, or the compiled row gather run
    under the interpreter (with its first factor as one-hot tiles)."""
    from repro.kernels.lcc_chain_matmul import lcc_chain_matmul

    idx, exp, sgn = (np.asarray(a, t)[None]
                     for a, t in ((idx, np.int32), (exp, np.int8),
                                  (sgn, np.int8)))
    k, b = x.shape
    rows = -(-max(k, idx.shape[2]) // 8) * 8
    xp = np.zeros((1, rows, b), np.float32)
    xp[0, :k] = x
    y = lcc_chain_matmul(idx, exp, sgn, jnp.asarray(xp), block_b=b,
                         first_width=k, interpret=True, use_gather=use_gather,
                         terms=None if use_gather else pack_terms(idx, exp,
                                                                  sgn))
    return np.asarray(y)


@pytest.mark.parametrize("use_gather", [True, False],
                         ids=["gather", "row_gather"])
def test_chain_matmul_csd_shift_add_bitwise_vs_ref(use_gather):
    """A CSD shift-add chain matches the densify-then-matmul oracle BITWISE
    on both kernel bodies: every operand is a signed power of two times an
    integer input, all intermediates are dyadic rationals far inside the f32
    mantissa, so both evaluation orders are exact and must agree to the last
    bit."""
    rng = np.random.default_rng(11)
    k_in, r, p, s, b = 8, 8, 3, 2, 5
    idx = rng.integers(0, k_in, (p, r, s))
    exp = rng.integers(-2, 3, (p, r, s))
    sgn = rng.choice([-1, 0, 1], (p, r, s))
    sgn[1, 2] = 0  # a fully-dead row: must decompress to exactly 0.0
    x = np.asarray(rng.integers(-4, 5, (k_in, b)), np.float32)

    factors = [(jnp.asarray(idx[q], jnp.int32), jnp.asarray(exp[q], jnp.int8),
                jnp.asarray(sgn[q], jnp.int8)) for q in range(p)]
    want = np.asarray(ref.lcc_chain_apply_ref(factors, jnp.asarray(x)))

    got = _csd_chain(idx, exp, sgn, x, use_gather=use_gather)[:r]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[2], 0.0)


@pytest.mark.parametrize("use_gather", [True, False],
                         ids=["gather", "row_gather"])
def test_chain_matmul_csd_digits_reproduce_constants(use_gather):
    """A one-factor chain built from ``csd_digits`` of real coefficients
    applies exactly c * x on both kernel bodies: shift-add reconstruction of
    a CSD-coded scalar is bitwise identical to the direct multiply for dyadic
    c and integer x."""
    from repro.core.csd import csd_digits

    consts = [2.5, -3.75, 0.625, 1.0]
    digits = [csd_digits(c) for c in consts]
    s = max(len(d) for d in digits)
    r = len(consts)
    idx = np.zeros((1, r, s), np.int64)  # every row reads input row 0
    exp = np.zeros((1, r, s), np.int64)
    sgn = np.zeros((1, r, s), np.int64)
    for i, dig in enumerate(digits):
        for j, (e, z) in enumerate(dig):
            exp[0, i, j], sgn[0, i, j] = e, z

    rng = np.random.default_rng(3)
    x = np.asarray(rng.integers(-8, 9, (1, 6)), np.float32)
    got = _csd_chain(idx, exp, sgn, x, use_gather=use_gather)[:r]
    want = np.asarray(consts, np.float32)[:, None] * x
    np.testing.assert_array_equal(got, want)
