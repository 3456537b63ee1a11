"""jit'd public wrappers around the Pallas kernels.

Bridges ``repro.core.lcc`` decomposition objects (numpy, offline) to the TPU
runtime format: pads factors to block multiples, packs (idx, exp, sign) into
the stacked whole-chain layout of ``lcc_chain_matmul``, applies chains /
decompositions fused (one launch per decomposition), and evaluates
weight-shared layers (paper eq. (10)) as segment-sum + centroid matmul.

Packed layout (see ``lcc_chain_matmul``'s module docstring for the kernel-side
contract): all FP slices of a decomposition stack into [E, P, N_pad, S]
streams; chains shorter than P are right-padded with identity factors, unused
term slots and padded rows carry sign == 0.  FS programs have no factor-chain
form — they fall back to their dense equivalent (an offline/storage format;
DESIGN.md Sec. 2) and are combined outside the fused launch.

Every ``interpret`` parameter defaults to ``None`` = auto-detect: compiled
Pallas on TPU, interpreter on CPU/GPU (``repro.kernels.dispatch``).
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.lcc import LCCChain, LCCDecomposition

from .dispatch import record_launch
from .group_prox import group_prox
from .lcc_chain_matmul import lcc_chain_matmul, pack_terms
from .lcc_group_matmul import lcc_group_matmul
from .lcc_matmul import lcc_factor_matmul
from .shared_matmul import cluster_segment_sum

__all__ = [
    "PackedChain",
    "PackedDecomposition",
    "PackedGroup",
    "pack_chain",
    "pack_decomposition",
    "pack_group",
    "apply_packed_chain",
    "apply_packed_decomposition",
    "apply_packed_group",
    "segment_sum_tpu",
    "shared_matmul_tpu",
    "group_prox",
]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_dim(n: int, block: int) -> int:
    """Seed padding convention: multiples of min(block, n) — small dims stay
    small (interpret mode), dims >= block become block multiples (TPU tiling)."""
    return _round_up(n, min(block, max(n, 1)))


class _TermWords:
    """The ``terms`` of a packed (idx, exp, sign) stream set."""

    @functools.cached_property
    def terms(self) -> np.ndarray:
        """The streams as the compiled row-gather body reads them
        (``lcc_chain_matmul.pack_terms``), laid out once, on the host."""
        return pack_terms(self.idx, self.exp, self.sign)


@dataclass(frozen=True)
class PackedChain(_TermWords):
    """One FP chain in the stacked kernel layout: factor axis leading."""

    idx: jnp.ndarray  # [P, N_pad, S] int32
    exp: jnp.ndarray  # [P, N_pad, S] int8
    sign: jnp.ndarray  # [P, N_pad, S] int8
    in_dim: int  # unpadded
    out_dim: int  # unpadded
    d_pad: int  # width of the running vector the kernel carries
    first_width: int  # padded input width addressable by the first factor
    n_factors: int  # real (un-padded) chain length

    @property
    def compact_bytes(self) -> int:
        """HBM bytes in the deployment stream format (int16 idx + int8 code)."""
        return int(3 * int(np.asarray(self.sign != 0).sum()))


@dataclass(frozen=True)
class PackedDecomposition(_TermWords):
    """Whole decomposition: FP slices stacked for one fused launch + dense rest."""

    idx: jnp.ndarray  # [E, P, N_pad, S] int32
    exp: jnp.ndarray  # [E, P, N_pad, S] int8
    sign: jnp.ndarray  # [E, P, N_pad, S] int8
    col_slices: tuple[tuple[int, int], ...]  # E entries (FP slices only)
    dense: tuple[tuple[tuple[int, int], jnp.ndarray], ...]  # non-FP fallback
    in_dim: int
    out_dim: int
    d_pad: int
    first_width: int  # padded max slice width (first-factor column span)
    chain_lengths: tuple[int, ...]  # real factor count per FP slice


def _stack_chain(chain: LCCChain, n_pad: int, s_max: int, p_max: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack one chain's factors into [P, N_pad, S]; identity-pad to p_max."""
    idx = np.zeros((p_max, n_pad, s_max), np.int32)
    exp = np.zeros((p_max, n_pad, s_max), np.int8)
    sgn = np.zeros((p_max, n_pad, s_max), np.int8)
    for p, f in enumerate(chain.factors):
        idx[p, : f.out_dim, : f.s_terms] = f.idx
        exp[p, : f.out_dim, : f.s_terms] = f.exp
        sgn[p, : f.out_dim, : f.s_terms] = f.sign
    for p in range(len(chain.factors), p_max):  # identity wiring: y = prev
        idx[p, :, 0] = np.arange(n_pad)
        sgn[p, :, 0] = 1
    return idx, exp, sgn


def pack_chain(chain: LCCChain, block: int = 128) -> PackedChain:
    """Pack one FP chain into the stacked fused-kernel layout."""
    out_dim = chain.factors[-1].out_dim if chain.factors else chain.in_dim
    n_pad = _pad_dim(max([f.out_dim for f in chain.factors] or [chain.in_dim]),
                     block)
    s_max = max([f.s_terms for f in chain.factors] or [1])
    p_max = max(len(chain.factors), 1)
    k_pad = _pad_dim(chain.in_dim, block)
    d_pad = max(n_pad, k_pad)
    # an empty chain packs as one identity factor whose rows span n_pad
    first_width = k_pad if chain.factors else n_pad
    idx, exp, sgn = _stack_chain(chain, n_pad, s_max, p_max)
    return PackedChain(jnp.asarray(idx), jnp.asarray(exp), jnp.asarray(sgn),
                       in_dim=chain.in_dim, out_dim=out_dim, d_pad=d_pad,
                       first_width=first_width,
                       n_factors=max(len(chain.factors), 1))


def pack_decomposition(dec: LCCDecomposition, block: int = 128
                       ) -> PackedDecomposition:
    """Pack every FP slice chain into ONE stacked multi-slice layout."""
    fp = [((c0, c1), s) for (c0, c1), s in zip(dec.col_slices, dec.slices)
          if isinstance(s, LCCChain)]
    dense = tuple(((c0, c1), jnp.asarray(s.to_dense(), jnp.float32))
                  for (c0, c1), s in zip(dec.col_slices, dec.slices)
                  if not isinstance(s, LCCChain))
    n, k = dec.shape
    if not fp:
        return PackedDecomposition(
            jnp.zeros((0, 1, 1, 1), jnp.int32), jnp.zeros((0, 1, 1, 1), jnp.int8),
            jnp.zeros((0, 1, 1, 1), jnp.int8), (), dense,
            in_dim=k, out_dim=n, d_pad=1, first_width=1, chain_lengths=())
    all_factors = [f for _, ch in fp for f in ch.factors]
    n_pad = _pad_dim(max([f.out_dim for f in all_factors] or [n]), block)
    s_max = max([f.s_terms for f in all_factors] or [1])
    p_max = max(max(len(ch.factors) for _, ch in fp), 1)
    w_pad = _pad_dim(max(c1 - c0 for (c0, c1), _ in fp), block)
    d_pad = max(n_pad, w_pad)
    stacked = [_stack_chain(ch, n_pad, s_max, p_max) for _, ch in fp]
    return PackedDecomposition(
        idx=jnp.asarray(np.stack([s[0] for s in stacked])),
        exp=jnp.asarray(np.stack([s[1] for s in stacked])),
        sign=jnp.asarray(np.stack([s[2] for s in stacked])),
        col_slices=tuple(cs for cs, _ in fp),
        dense=dense, in_dim=k, out_dim=n, d_pad=d_pad, first_width=w_pad,
        chain_lengths=tuple(max(len(ch.factors), 1) for _, ch in fp))


def _pad_batch(b: int, block: int) -> tuple[int, int]:
    bb = min(block, b)
    return bb, _round_up(b, bb)


@dataclass(frozen=True)
class PackedGroup(_TermWords):
    """G packed decompositions re-padded to common dims for ONE grouped launch.

    ``members`` keeps each decomposition's original packing metadata
    (col_slices over its own input, FS dense fallbacks, true in/out dims);
    the stacked (idx, exp, sign) carry the shared-padded factor streams that
    :func:`~repro.kernels.lcc_group_matmul.lcc_group_matmul` consumes.  The
    streams are kept as *numpy* arrays: groups are assembled lazily — often
    inside an active jit trace — and cached numpy constants embed per-trace
    instead of leaking tracers.
    """

    idx: np.ndarray  # [G, E, P, N_pad, S] int32
    exp: np.ndarray  # [G, E, P, N_pad, S] int8
    sign: np.ndarray  # [G, E, P, N_pad, S] int8
    members: tuple[PackedDecomposition, ...]
    d_pad: int
    first_width: int
    waste: dict | None = None  # padding-waste fractions (see pack_group)

    @property
    def n_groups(self) -> int:
        return len(self.members)


def pack_group(members: list[PackedDecomposition]) -> PackedGroup:
    """Re-pad G packed decompositions to common (E, P, N, S, D) dims.

    Padding preserves the kernel invariants: extra term slots and extra rows
    carry sign == 0 (decompress to zero), chains are right-extended with
    identity factors over the shared N_pad, and whole missing slices are
    all-zero-sign (a zero factor chain on zero input — contributes nothing).
    """
    if not members:
        raise ValueError("pack_group needs at least one member")
    e_max = max([m.idx.shape[0] for m in members] + [1])
    p_max = max([m.idx.shape[1] for m in members if m.idx.shape[0]] + [1])
    n_max = max([m.idx.shape[2] for m in members if m.idx.shape[0]] + [1])
    s_max = max([m.idx.shape[3] for m in members if m.idx.shape[0]] + [1])
    d_pad = max([m.d_pad for m in members if m.idx.shape[0]] + [n_max])
    first_width = max([m.first_width for m in members if m.idx.shape[0]] + [1])
    gi = np.zeros((len(members), e_max, p_max, n_max, s_max), np.int32)
    ge = np.zeros(gi.shape, np.int8)
    gs = np.zeros(gi.shape, np.int8)
    ident = np.arange(n_max, dtype=np.int32)
    for g, m in enumerate(members):
        e, p, n, s = m.idx.shape
        if e == 0:
            continue  # FS-only member: dense fallback handles everything
        gi[g, :e, :p, :n, :s] = np.asarray(m.idx)
        ge[g, :e, :p, :n, :s] = np.asarray(m.exp)
        gs[g, :e, :p, :n, :s] = np.asarray(m.sign)
        # chains shorter than the group max continue as identity factors
        gi[g, :e, p:, :, 0] = ident
        gs[g, :e, p:, :, 0] = 1
    # padding-waste accounting: a (slice, factor, row) slot whose sign terms
    # are all zero does no work but is still streamed and iterated — report
    # the fraction per group so badly-matched group members are visible
    zero_rows = (gs == 0).all(axis=-1)  # [G, E, P, N]
    zero_slices = zero_rows.all(axis=(2, 3))  # [G, E]
    row_frac = zero_rows.reshape(len(members), -1).mean(axis=1)
    slice_frac = zero_slices.mean(axis=1)
    waste = {
        "row_waste": [float(f) for f in row_frac],
        "slice_waste": [float(f) for f in slice_frac],
        "mean_row_waste": float(row_frac.mean()),
        "shape": list(gi.shape),
    }
    if waste["mean_row_waste"] > 0.5:
        warnings.warn(
            f"pack_group: {waste['mean_row_waste']:.0%} of padded rows carry "
            f"sign==0 across {len(members)} members (shape {gi.shape}) — "
            "group members are badly matched; consider splitting the group",
            stacklevel=2)
    return PackedGroup(idx=gi, exp=ge, sign=gs, members=tuple(members),
                       d_pad=d_pad, first_width=first_width, waste=waste)


def apply_packed_group(pg: PackedGroup, xs, *, block: int = 128,
                       interpret: bool | None = None,
                       name: str = "_lcc_group_matmul") -> list[jnp.ndarray]:
    """y_g = W_hat_g @ xs[g] for every group member — ONE fused launch
    (called ``name`` in the compiled program and the device trace).

    ``xs`` is a per-member list of [K_g, B] inputs (all the same B; K_g is the
    member's own in_dim — members need not agree on input width because each
    slices/pads its own columns).  FS dense-fallback slices are added per
    member outside the launch, exactly like :func:`apply_packed_decomposition`.
    """
    if len(xs) != len(pg.members):
        raise ValueError(f"{len(pg.members)} group members, {len(xs)} inputs")
    b = xs[0].shape[1]
    bb, b_pad = _pad_batch(b, block)
    e_max = pg.idx.shape[1]
    any_fp = any(m.col_slices for m in pg.members)
    y = None
    if any_fp:
        rows = _round_up(pg.first_width, 8)  # all the kernel reads of a slab
        stacks = []
        for m, x in zip(pg.members, xs):
            if x.shape[0] != m.in_dim:
                raise ValueError(f"x has {x.shape[0]} rows, member expects "
                                 f"in_dim={m.in_dim}")
            slabs = [jnp.pad(x[c0:c1].astype(jnp.float32),
                             ((0, rows - (c1 - c0)), (0, b_pad - b)))
                     for c0, c1 in m.col_slices]
            slabs += [jnp.zeros((rows, b_pad), jnp.float32)
                      ] * (e_max - len(slabs))
            stacks.append(jnp.stack(slabs))
        y = lcc_group_matmul(pg.idx, pg.exp, pg.sign, jnp.stack(stacks),
                             block_b=bb, first_width=pg.first_width,
                             interpret=interpret, name=name,
                             terms=pg.terms)  # [G, N_pad, B_pad]
    outs = []
    for g, (m, x) in enumerate(zip(pg.members, xs)):
        yg = y[g, : m.out_dim, :b] if (y is not None and m.col_slices) else None
        for (c0, c1), w in m.dense:
            part = w @ x[c0:c1].astype(jnp.float32)
            yg = part if yg is None else yg + part
        if yg is None:
            raise ValueError("empty decomposition in group: no FP or dense slices")
        outs.append(yg)
    return outs


def _apply_stacked_per_factor(idx, exp, sign, x_pad, chain_lengths, *,
                              block: int, interpret: bool | None):
    """Per-factor launch loop over the stacked layout — the pre-fusion runtime,
    kept as the fused kernel's wall-clock baseline (benchmarks) and as an
    independent second implementation for equivalence tests.  Launches only
    each chain's REAL factors (identity padding exists for the fused stack's
    benefit; a pre-fusion runtime never ran it)."""
    e_slices, _, n_pad, _ = idx.shape
    _, d_pad, b_pad = x_pad.shape
    y = jnp.zeros((n_pad, b_pad), jnp.float32)
    bb = min(block, b_pad)
    for e in range(e_slices):
        cur = x_pad[e]
        for p in range(chain_lengths[e]):
            record_launch()  # one pallas_call per (slice, factor)
            out = lcc_factor_matmul(idx[e, p], exp[e, p], sign[e, p], cur,
                                    block_n=min(block, n_pad),
                                    block_k=min(block, d_pad),
                                    block_b=bb, interpret=interpret)
            cur = jnp.pad(out, ((0, d_pad - n_pad), (0, 0)))
        y = y + cur[:n_pad]
    return y


def apply_packed_chain(pc: PackedChain, x: jnp.ndarray, *, block: int = 128,
                       interpret: bool | None = None,
                       fused: bool = True) -> jnp.ndarray:
    """y[N, B] = (F_P ... F_1) @ x[K, B] — the whole chain in one fused launch.

    Padded rows carry sign==0 slots (value 0) so they stay exactly zero through
    the chain; the final slice recovers the true output dim.
    """
    k, b = x.shape
    if k != pc.in_dim:
        raise ValueError(f"x has {k} rows, chain expects in_dim={pc.in_dim}")
    bb, b_pad = _pad_batch(b, block)
    # the fused kernel reads first_width rows; the per-factor loop carries D_pad
    rows = _round_up(pc.first_width, 8) if fused else pc.d_pad
    x_pad = jnp.pad(x.astype(jnp.float32),
                    ((0, rows - k), (0, b_pad - b)))[None]
    if fused:
        y = lcc_chain_matmul(pc.idx[None], pc.exp[None], pc.sign[None], x_pad,
                             block_b=bb, first_width=pc.first_width,
                             interpret=interpret, terms=pc.terms[None])
    else:
        y = _apply_stacked_per_factor(pc.idx[None], pc.exp[None], pc.sign[None],
                                      x_pad, (pc.n_factors,), block=block,
                                      interpret=interpret)
    return y[: pc.out_dim, :b]


def apply_packed_decomposition(packed: PackedDecomposition, x: jnp.ndarray, *,
                               block: int = 128, interpret: bool | None = None,
                               fused: bool = True,
                               name: str = "_lcc_chain_matmul") -> jnp.ndarray:
    """y = W_hat @ x for a packed decomposition; x [K, B] (or [K] vector).

    All FP slices run in a single ``lcc_chain_matmul`` launch (``fused=True``,
    the default; called ``name`` in the compiled program and the device
    trace); ``fused=False`` runs the legacy one-``pallas_call``-per-factor
    loop for comparison.  Dense-fallback slices (FS programs) are added on top.
    """
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    k, b = x.shape
    if k != packed.in_dim:
        raise ValueError(f"x has {k} rows, decomposition expects "
                         f"in_dim={packed.in_dim}")
    y = None
    e_slices = len(packed.col_slices)
    if e_slices:
        bb, b_pad = _pad_batch(b, block)
        # the fused kernel reads first_width rows; the per-factor loop
        # carries D_pad
        rows = (_round_up(packed.first_width, 8) if fused else packed.d_pad)
        x_pad = jnp.stack([
            jnp.pad(x[c0:c1].astype(jnp.float32),
                    ((0, rows - (c1 - c0)), (0, b_pad - b)))
            for c0, c1 in packed.col_slices])
        if fused:
            y = lcc_chain_matmul(packed.idx, packed.exp, packed.sign, x_pad,
                                 block_b=bb, first_width=packed.first_width,
                                 interpret=interpret, name=name,
                                 terms=packed.terms)
        else:
            y = _apply_stacked_per_factor(packed.idx, packed.exp, packed.sign,
                                          x_pad, packed.chain_lengths,
                                          block=block, interpret=interpret)
        y = y[: packed.out_dim, :b]
    for (c0, c1), w in packed.dense:
        part = w @ x[c0:c1].astype(jnp.float32)
        y = part if y is None else y + part
    if y is None:
        raise ValueError("empty decomposition: no FP or dense slices to apply")
    return y[:, 0] if squeeze else y


def segment_sum_tpu(labels: jnp.ndarray, x: jnp.ndarray, num_clusters: int,
                    *, interpret: bool | None = None) -> jnp.ndarray:
    """Kernel segment-sum with ragged (K, C, B) padded to block multiples.

    Padded K rows are labeled c_pad - 1; when num_clusters is already a block
    multiple that id aliases the last *real* cluster, which stays correct only
    because the padded x rows are zero — keep that invariant when changing the
    padding.
    """
    record_launch()  # cluster_segment_sum is one pallas_call
    k, b = x.shape
    bc = min(128, num_clusters)
    c_pad = _round_up(num_clusters, bc)
    bk = min(128, k)
    k_pad = _round_up(k, bk)
    bb = min(128, b)
    b_pad = _round_up(b, bb)
    lab = jnp.pad(labels.astype(jnp.int32), (0, k_pad - k), constant_values=c_pad - 1) \
        if k_pad != k else labels.astype(jnp.int32)
    xp = jnp.pad(x, ((0, k_pad - k), (0, b_pad - b))) if (k_pad != k or b_pad != b) else x
    agg = cluster_segment_sum(lab, xp, num_clusters=c_pad,
                              block_c=bc, block_k=bk, block_b=bb, interpret=interpret)
    return agg[:num_clusters, :b]


def shared_matmul_tpu(centroids: jnp.ndarray, labels: jnp.ndarray, x: jnp.ndarray,
                      *, interpret: bool | None = None) -> jnp.ndarray:
    """Eq. (10) on TPU: kernel segment-sum then centroid matmul. x [K, B] -> [N, B]."""
    agg = segment_sum_tpu(labels, x, centroids.shape[1], interpret=interpret)
    return centroids.astype(jnp.float32) @ agg


# ---------------------------------------------------------------------------
# deployment byte-stream format (what actually sits in HBM)
# ---------------------------------------------------------------------------


def factor_to_stream(f) -> bytes:
    """Serialize one LCC factor to the compact deployment stream.

    Per nonzero term: int16 column index + int8 code (sign bit << 7 | (exp+32)).
    Row boundaries via a uint8 per-row term count (rows have <= S terms).
    This is the byte count the roofline's weight-streaming term uses.
    """
    import struct

    idx = np.asarray(f.idx)
    exp = np.asarray(f.exp)
    sgn = np.asarray(f.sign)
    out = bytearray()
    out += struct.pack("<III", f.out_dim, f.in_dim, idx.shape[1])
    for r in range(f.out_dim):
        nz = np.nonzero(sgn[r])[0]
        out.append(len(nz))
        for s in nz:
            out += struct.pack("<h", int(idx[r, s]))
            code = (128 if sgn[r, s] < 0 else 0) | (int(exp[r, s]) + 32)
            out += struct.pack("<B", code)
    return bytes(out)


def stream_to_factor(data: bytes):
    """Inverse of factor_to_stream -> core.lcc.LCCFactor."""
    import struct

    from repro.core.lcc import LCCFactor

    out_dim, in_dim, s_terms = struct.unpack_from("<III", data, 0)
    off = 12
    idx = np.zeros((out_dim, s_terms), np.int32)
    exp = np.zeros((out_dim, s_terms), np.int8)
    sgn = np.zeros((out_dim, s_terms), np.int8)
    for r in range(out_dim):
        n = data[off]
        off += 1
        for s in range(n):
            (col,) = struct.unpack_from("<h", data, off)
            code = data[off + 2]
            off += 3
            idx[r, s] = col
            sgn[r, s] = -1 if code & 128 else 1
            exp[r, s] = (code & 127) - 32
    return LCCFactor(idx=idx, exp=exp, sign=sgn, in_dim=in_dim)
