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
    "PackedStage",
    "pack_chain",
    "pack_decomposition",
    "pack_group",
    "pack_stage",
    "pack_layer",
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
# layer plans: every compressed site of a layer stage in ONE buffer
# ---------------------------------------------------------------------------
#
# ``pack_group`` still pays one launch per *region* (q/k/v, gate/up, ...).  A
# layer plan goes further: all sites that consume the same activation are
# flattened into a single gather/shift-add *stage*, and all L identical layers
# stack along a leading axis so one ``pallas_call`` with grid (L,) executes the
# whole decode step.  The stage representation is specialized to the ternary /
# CSD structure (core/csd.py): a row is sum_s sign * 2^exp * prev[idx], so the
# kernel needs only integer gathers + shift-adds — no sign-padded dense tiles.
#
# Per stage, for layer l:
#
#   prep_src/prep_tgt [L, M]     scatter-add pairs building the stage input
#                                buffer: inbuf[tgt] += src[src'] implements
#                                both kept-column gather and weight-sharing
#                                segment-sum (tgt = cluster label).  Padding
#                                pairs are (0, K_alloc - 1): they add into a
#                                dead row that nothing downstream reads.
#   gidx/gexp/gsgn [L, P, R, S]  every FP slice of every site, concatenated
#                                along the row axis R; level 0 reads inbuf,
#                                levels >= 1 read the running work buffer.
#                                sign == 0 marks unused slots (rows decompress
#                                to zero); short chains continue as identity.
#   outg [L, J, O]               output gather: out[o] = sum_j work[outg[j,o]]
#                                (J = max FP-slice count of any site; padded
#                                entries point at the all-zero row R).
#   fs_mat [L, O, K_alloc]       FS-program dense fallback applied to inbuf
#                                (column K_alloc - 1, the dead row, is zero).
#   dw_mat [L, O, D_src]         uncovered sites' dense weights (w.T) baked in
#                                so the stage still produces the full output.
#   bias [L, O]                  site biases, summed at their output offsets.


# per-level gather volume (P * R * S instruction slots) above which a stage
# decodes through its folded effective matrix instead of the segment path:
# on the interpreter host per-op dispatch and gather traffic (which scales
# with batch), not arithmetic, bound decode wall-clock, so past this size one
# GEMM per stage-layer wins; below it the segment path is already cheap and
# stays the exercised representation
EFF_GATHER_CUTOFF = 32_768


@dataclass(frozen=True)
class PackedStage:
    """One layer stage (e.g. fused q+k+v) stacked over L layers.

    All arrays are numpy: stages are trace-time constants (they embed in the
    jitted step) and must survive artifact save/load round trips.

    ``segs`` (segment-packed layout, optional): per (layer, level) the row
    space is run-length sorted at pack time — instructions laid out by
    descending chain depth so every level splits into a contiguous *active*
    prefix (rows with a real CSD level, the short irregular gather) followed
    by a contiguous *identity* run (rows whose chains already ended: a plain
    slice copy) and a zero tail.  ``segs[l, p] = (active_end, rows_used,
    live_terms)``.  The descriptors are static: the kernel slices its traced
    operands to the active prefix at the live term width, skips pure-identity
    levels entirely, and lowers contiguous output windows to ``lax.slice``.
    Stages without it (PR 8-era artifacts) evaluate through the original
    full-gather operand path, bit-for-bit unchanged.
    """

    prep_src: np.ndarray | None  # [L, M] int32
    prep_tgt: np.ndarray | None  # [L, M] int32
    gidx: np.ndarray | None  # [L, P, R, S] int32
    gexp: np.ndarray | None  # [L, P, R, S] int8
    gsgn: np.ndarray | None  # [L, P, R, S] int8
    outg: np.ndarray | None  # [L, J, O] int32
    fs_mat: np.ndarray | None  # [L, O, K_alloc] f32
    dw_mat: np.ndarray | None  # [L, O, D_src] f32
    bias: np.ndarray | None  # [L, O] f32
    k_alloc: int  # inbuf rows incl. trailing dead row
    d_src: int  # stage input rows
    out_dim: int  # stage output rows O
    n_layers: int
    site_names: tuple[str, ...]  # compressed sites this stage covers
    segs: np.ndarray | None = None  # [L, P, 3] int32 segment descriptors
    seg_stats: dict | None = None  # run-length stats (not persisted)
    waste: dict | None = None  # padding-waste report (not persisted)

    @property
    def has_prep(self) -> bool:
        return self.prep_src is not None

    @property
    def has_fp(self) -> bool:
        return self.gidx is not None

    @functools.cached_property
    def gcoef(self) -> np.ndarray:
        """``sign * 2**exp`` as f32 [L, P, R, S] — precomputed (exactly: a
        signed power of two is exact in f32) so the kernel pays a single load
        per term instead of two int8 converts, an exp2 and a multiply."""
        return (self.gsgn.astype(np.float32)
                * np.exp2(self.gexp.astype(np.float32)))

    @functools.cached_property
    def _prep_mats(self) -> np.ndarray | None:
        """Prep scatter-add pairs as selection matrices [L, K_alloc, D_src]
        (kept-column gather + weight-sharing segment-sum, dead row zero)."""
        if not self.has_prep:
            return None
        mats = np.zeros((self.n_layers, self.k_alloc, self.d_src), np.float32)
        for l in range(self.n_layers):
            tgt = self.prep_tgt[l].astype(np.int64)
            src = self.prep_src[l].astype(np.int64)
            real = tgt < self.k_alloc - 1  # padding pairs hit the dead row
            np.add.at(mats[l], (tgt[real], src[real]), 1.0)
        return mats

    @functools.cached_property
    def eff(self) -> np.ndarray | None:
        """Whole-stage folded effective matrix [L, O, D_src], or ``None``.

        A decode stage is a fixed linear map: prep scatter-add, P shift-add
        levels (each row ``sum_s sign * 2**exp * prev[idx]``), output gather,
        plus the FS / uncovered-dense fallbacks.  Composing those maps at
        pack time — applying each level's instruction stream to a running
        ``[rows, D_src]`` matrix via gathers, never materializing the
        ``[R, R]`` per-level map — yields one matrix per layer, so the plan
        kernel spends ONE matmul where the segment path spends ~2P gathers
        and einsums whose traffic scales with batch.  The chains stay the
        artifact's source of truth (per-region kernels, roofline, hardware
        export); this is a dispatch-for-memory trade for the interpreter
        host, taken only when the per-level gather volume exceeds
        ``EFF_GATHER_CUTOFF`` so small stages keep exercising the segment
        layout."""
        if not self.has_fp:
            return None
        n_l, n_p, r_max, s = self.gidx.shape
        if n_p * r_max * s <= EFF_GATHER_CUTOFF:
            return None
        w = np.zeros((n_l, self.out_dim, self.d_src), np.float32)
        chunk = 4096  # bounds the [rows, S, D_src] gather transient
        for l in range(n_l):
            m = (self._prep_mats[l] if self.has_prep
                 else np.eye(self.d_src, dtype=np.float32))
            for p in range(n_p):
                idx = self.gidx[l, p].astype(np.int64)
                coef = (self.gcoef[l, p]
                        * (self.gsgn[l, p] != 0)
                        * (idx < m.shape[0]))
                safe = np.clip(idx, 0, m.shape[0] - 1)
                nxt = np.empty((r_max, m.shape[1]), np.float32)
                for r0 in range(0, r_max, chunk):
                    r1 = min(r0 + chunk, r_max)
                    nxt[r0:r1] = np.einsum(
                        "rsd,rs->rd", m[safe[r0:r1]], coef[r0:r1])
                m = nxt
            e = self.outg[l].astype(np.int64)  # [J, O]
            valid = e < r_max  # padded entries read the zero row
            w[l] = np.einsum("jod,jo->od",
                             m[np.clip(e, 0, r_max - 1)],
                             valid.astype(np.float32))
        if self.fold_dense is not None:
            w += self.fold_dense
        return w

    @functools.cached_property
    def fold_dense(self) -> np.ndarray | None:
        """FS fallback (re-based from inbuf to the stage input) + uncovered
        dense weights as one [L, O, D_src] block, folded into ``eff``."""
        if self.fs_mat is None and self.dw_mat is None:
            return None
        d = np.zeros((self.n_layers, self.out_dim, self.d_src), np.float32)
        if self.fs_mat is not None:
            for l in range(self.n_layers):
                d[l] += self.fs_mat[l] @ self._prep_mats[l]
        if self.dw_mat is not None:
            d += self.dw_mat
        return d

    def operands(self) -> list[np.ndarray]:
        """Kernel operands in canonical order (mirrored by layer_plan)."""
        if self.eff is not None:
            ops_ = [self.eff]
            if self.bias is not None:
                ops_.append(self.bias)
            return ops_
        ops_ = []
        if self.has_prep:
            ops_ += [self.prep_src, self.prep_tgt]
        if self.has_fp:
            ops_ += [self.gidx, self.gcoef, self.outg]
        if self.fs_mat is not None:
            ops_.append(self.fs_mat)
        if self.dw_mat is not None:
            ops_.append(self.dw_mat)
        if self.bias is not None:
            ops_.append(self.bias)
        return ops_


def _fuse_csd_levels(idx: np.ndarray, exp: np.ndarray, sgn: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fuse adjacent CSD levels pairwise: two 2-term shift-add levels become
    one 4-term level (``exp`` summed, signs multiplied — still exact signed
    powers of two), halving the sequential depth at an identical add count.
    A term whose parent row is all-dead composes to sign 0, exactly matching
    the sequential evaluation (the parent row decompresses to zero).  An odd
    trailing level rides along unfused.  Arrays are [P, rows, S]."""
    pm, rows, s = idx.shape
    if pm < 2:
        return (idx.astype(np.int64), exp.astype(np.int32),
                sgn.astype(np.int32))
    out_i, out_e, out_s = [], [], []
    p = 0
    while p < pm:
        if p + 1 == pm:
            out_i.append(idx[p].astype(np.int64))
            out_e.append(exp[p].astype(np.int32))
            out_s.append(sgn[p].astype(np.int32))
            break
        a_i, a_e, a_s = (idx[p].astype(np.int64), exp[p].astype(np.int32),
                         sgn[p].astype(np.int32))
        b_i, b_e, b_s = (idx[p + 1].astype(np.int64),
                         exp[p + 1].astype(np.int32),
                         sgn[p + 1].astype(np.int32))
        j = np.clip(b_i, 0, rows - 1)  # dead terms may carry junk indices
        ci = a_i[j]  # [rows, S, S]
        ce = b_e[:, :, None] + a_e[j]
        cs = b_s[:, :, None] * a_s[j]
        live = cs != 0
        out_i.append(np.where(live, ci, 0).reshape(rows, s * s))
        out_e.append(np.where(live, ce, 0).reshape(rows, s * s))
        out_s.append(cs.reshape(rows, s * s))
        p += 2
    s_new = max(a.shape[1] for a in out_i)
    fi = np.zeros((len(out_i), rows, s_new), np.int64)
    fe = np.zeros((len(out_i), rows, s_new), np.int32)
    fs = np.zeros((len(out_i), rows, s_new), np.int32)
    for q, (ai, ae, as_) in enumerate(zip(out_i, out_e, out_s)):
        fi[q, :, : ai.shape[1]] = ai
        fe[q, :, : ae.shape[1]] = ae
        fs[q, :, : as_.shape[1]] = as_
    return fi, fe, fs


def pack_stage(layer_sites: list[list[dict]], *, d_src: int, out_dim: int
               ) -> PackedStage:
    """Flatten per-layer site lists into one stacked stage.

    ``layer_sites[l]`` is the sites of layer l, each a dict:

      {"kind": "lcc", "name", "out_off", "src_off", "kept" [ints],
       "labels" [ints]|None, "n_clusters" int, "packed" PackedDecomposition,
       "bias" [out]|None}
      {"kind": "dense", "out_off", "src_off", "w" [in, out], "bias"|None}

    Sites write disjoint [out_off, out_off + site_out) row ranges of the
    stage output and read [src_off, ...) of the shared stage input.
    """
    n_layers = len(layer_sites)
    built = []  # per-layer dict of intermediate layout
    any_bias = any_fs = any_dw = False
    names: list[str] = []
    for sites in layer_sites:
        in_off = 0
        prep_pairs: list[tuple[np.ndarray, np.ndarray]] = []
        insts: list[dict] = []  # one per FP slice, in site order
        site_slices: list[tuple[int, int, list[int]]] = []  # (out_off, odim, inst ids)
        fs_entries: list[tuple[int, int, int, np.ndarray]] = []
        dw_entries: list[tuple[int, int, np.ndarray]] = []
        bias_vec = None
        for st in sites:
            b = st.get("bias")
            if b is not None:
                any_bias = True
                if bias_vec is None:
                    bias_vec = np.zeros(out_dim, np.float32)
                b = np.asarray(b, np.float32)
                bias_vec[st["out_off"]: st["out_off"] + b.size] += b
            if st["kind"] == "dense":
                any_dw = True
                w = np.asarray(st["w"], np.float32)
                dw_entries.append((st["out_off"], st["src_off"], w.T))
                continue
            names.append(st["name"])
            kept = np.asarray(st["kept"], np.int64)
            labels = st.get("labels")
            packed = st["packed"]
            tgt = (np.asarray(labels, np.int64) if labels is not None
                   else np.arange(kept.size))
            n_in = int(st["n_clusters"]) if labels is not None else kept.size
            if packed.in_dim != n_in:
                raise ValueError(f"{st['name']}: packed.in_dim={packed.in_dim}"
                                 f" != aggregated input {n_in}")
            prep_pairs.append((st["src_off"] + kept, in_off + tgt))
            idx = np.asarray(packed.idx)
            exp = np.asarray(packed.exp)
            sgn = np.asarray(packed.sign)
            ids = []
            for e, (c0, c1) in enumerate(packed.col_slices):
                # one pairwise pass only: deeper fusion squares the terms per
                # row, and the wider gathers cost more than the saved levels
                fi, fe, fsg = _fuse_csd_levels(idx[e], exp[e], sgn[e])
                ids.append(len(insts))
                insts.append({"in0": in_off + c0, "width": c1 - c0,
                              "idx": fi, "exp": fe, "sgn": fsg,
                              "n_pad": idx.shape[2]})
            site_slices.append((st["out_off"], packed.out_dim, ids))
            for (c0, c1), w in packed.dense:
                any_fs = True
                fs_entries.append((st["out_off"], packed.out_dim,
                                   in_off + c0, np.asarray(w, np.float32)))
            in_off += n_in
        built.append({"k_used": in_off, "prep": prep_pairs, "insts": insts,
                      "site_slices": site_slices, "fs": fs_entries,
                      "dw": dw_entries, "bias": bias_vec})

    has_prep = any(bl["k_used"] for bl in built)
    has_fp = any(bl["insts"] for bl in built)
    k_alloc = (max(bl["k_used"] for bl in built) + 1) if has_prep else 0
    m_max = max([sum(p[0].size for p in bl["prep"]) for bl in built] + [1])
    r_max = max([sum(i["n_pad"] for i in bl["insts"]) for bl in built] + [1])
    p_max = max([i["idx"].shape[0] for bl in built for i in bl["insts"]] + [1])
    s_max = max([i["idx"].shape[2] for bl in built for i in bl["insts"]] + [1])
    j_max = max([len(ids) for bl in built for _, _, ids in bl["site_slices"]]
                + [1])

    prep_src = prep_tgt = gidx = gexp = gsgn = outg = None
    fs_mat = dw_mat = bias = None
    if has_prep:
        prep_src = np.zeros((n_layers, m_max), np.int32)
        prep_tgt = np.full((n_layers, m_max), k_alloc - 1, np.int32)
    if has_fp:
        gidx = np.zeros((n_layers, p_max, r_max, s_max), np.int32)
        gexp = np.zeros((n_layers, p_max, r_max, s_max), np.int8)
        gsgn = np.zeros((n_layers, p_max, r_max, s_max), np.int8)
        outg = np.full((n_layers, j_max, out_dim), r_max, np.int32)
    if any_fs:
        fs_mat = np.zeros((n_layers, out_dim, k_alloc), np.float32)
    if any_dw:
        dw_mat = np.zeros((n_layers, out_dim, d_src), np.float32)
    if any_bias:
        bias = np.zeros((n_layers, out_dim), np.float32)

    segs = np.zeros((n_layers, max(p_max, 1), 3), np.int32)
    runs_before: list[int] = []  # active-run lengths, original site order
    runs_after: list[int] = []  # active-run lengths after depth sorting
    for l, bl in enumerate(built):
        if bl["prep"]:
            src = np.concatenate([p[0] for p in bl["prep"]])
            tgt = np.concatenate([p[1] for p in bl["prep"]])
            prep_src[l, : src.size] = src
            prep_tgt[l, : tgt.size] = tgt
        # segment packing: lay instructions out by descending (fused) chain
        # depth so at every level the rows with a real CSD level form ONE
        # contiguous prefix and the ended chains one contiguous identity run
        order = sorted(range(len(bl["insts"])),
                       key=lambda i: (-bl["insts"][i]["idx"].shape[0], i))
        work_offs: dict[int, int] = {}
        wo = 0
        for inst_id in order:
            inst = bl["insts"][inst_id]
            work_offs[inst_id] = wo
            np_, sm = inst["n_pad"], inst["idx"].shape[2]
            pm = inst["idx"].shape[0]
            for p in range(p_max):
                if p < pm:
                    ii = inst["idx"][p].astype(np.int64)
                    ss = inst["sgn"][p]
                    ee = inst["exp"][p]
                    if p == 0:
                        # level 0 reads inbuf at the slice's column window;
                        # identity-padded level-0 rows of 0-factor chains can
                        # span n_pad > width — mask them so they never read a
                        # neighbouring site's region (the zero-padded-slab
                        # semantics of the per-region kernels)
                        live = (ss != 0) & (ii < inst["width"])
                        comp, safe = inst["in0"] + ii, inst["in0"]
                    else:
                        live = ss != 0
                        comp, safe = wo + ii, wo
                    gidx[l, p, wo: wo + np_, :sm] = np.where(live, comp, safe)
                    gsgn[l, p, wo: wo + np_, :sm] = np.where(live, ss, 0)
                    gexp[l, p, wo: wo + np_, :sm] = np.where(live, ee, 0)
                else:  # identity continuation over the stage's extra levels
                    gidx[l, p, wo: wo + np_, 0] = wo + np.arange(np_)
                    gsgn[l, p, wo: wo + np_, 0] = 1
            wo += np_
        r_used = wo
        depths = [inst["idx"].shape[0] for inst in bl["insts"]]
        pads = [inst["n_pad"] for inst in bl["insts"]]
        for p in range(max(p_max, 1)):
            a_end = sum(pads[i] for i in order if depths[i] > p)
            s_live = 1
            if has_fp and a_end:
                nz = np.nonzero(gsgn[l, p, :a_end, :])[1]
                s_live = int(nz.max()) + 1 if nz.size else 1
            segs[l, p] = (a_end, r_used, s_live)
            runs_after.extend(_active_runs(
                [depths[i] > p for i in order], [pads[i] for i in order]))
            runs_before.extend(_active_runs(
                [d > p for d in depths], pads))
        for out_off, odim, ids in bl["site_slices"]:
            for j, inst_id in enumerate(ids):
                outg[l, j, out_off: out_off + odim] = \
                    work_offs[inst_id] + np.arange(odim)
        for out_off, odim, i0, w in bl["fs"]:
            fs_mat[l, out_off: out_off + odim, i0: i0 + w.shape[1]] = w
        for out_off, src_off, wt in bl["dw"]:
            dw_mat[l, out_off: out_off + wt.shape[0],
                   src_off: src_off + wt.shape[1]] = wt
        if bl["bias"] is not None:
            bias[l] = bl["bias"]

    seg_stats = _segment_stats(runs_before, runs_after, gsgn, segs) \
        if has_fp else None
    waste = _stage_waste(gsgn, segs, prep_tgt, k_alloc) if has_fp else None
    return PackedStage(prep_src=prep_src, prep_tgt=prep_tgt, gidx=gidx,
                       gexp=gexp, gsgn=gsgn, outg=outg, fs_mat=fs_mat,
                       dw_mat=dw_mat, bias=bias, k_alloc=k_alloc, d_src=d_src,
                       out_dim=out_dim, n_layers=n_layers,
                       site_names=tuple(names), segs=segs,
                       seg_stats=seg_stats, waste=waste)


def _active_runs(active: list[bool], pads: list[int]) -> list[int]:
    """Maximal contiguous runs (in rows) of instructions with a live level."""
    runs, cur = [], 0
    for a, n in zip(active, pads):
        if a:
            cur += n
        elif cur:
            runs.append(cur)
            cur = 0
    if cur:
        runs.append(cur)
    return runs


def _pct(xs: list[int], q: float) -> int:
    return int(np.percentile(np.asarray(xs), q)) if xs else 0


def _segment_stats(runs_before, runs_after, gsgn, segs) -> dict:
    """Gather run-length telemetry: how contiguous the per-level active row
    space is before vs after depth sorting, and what the packed layout skips."""
    n_layers, p_max = gsgn.shape[0], gsgn.shape[1]
    r_max = gsgn.shape[2]
    total = n_layers * p_max * r_max
    active = int(sum(int(segs[l, p, 0]) for l in range(n_layers)
                     for p in range(p_max)))
    return {
        "p50_run_before": _pct(runs_before, 50),
        "p99_run_before": _pct(runs_before, 99),
        "p50_run_after": _pct(runs_after, 50),
        "p99_run_after": _pct(runs_after, 99),
        "n_runs_before": len(runs_before),
        "n_runs_after": len(runs_after),
        "gathered_rows": active,
        "total_rows": total,
        "gather_frac": round(active / total, 4) if total else 0.0,
    }


def _stage_waste(gsgn, segs, prep_tgt, k_alloc) -> dict:
    """Per-stage padding-waste report (mirrors ``pack_group``'s keys): the
    fraction of gather rows that are pure identity/zero padding and the dead
    terms inside the active region — what re-padding to the stacked stage
    layout costs relative to its live CSD work."""
    n_layers, p_max, r_max, _ = gsgn.shape
    total_rows = n_layers * p_max * r_max
    active_rows = int(sum(int(segs[l, p, 0]) for l in range(n_layers)
                          for p in range(p_max)))
    live = dead = 0
    for l in range(n_layers):
        for p in range(p_max):
            a_end, _, s_live = segs[l, p]
            blk = gsgn[l, p, :a_end, :s_live]
            live += int(np.count_nonzero(blk))
            dead += int(blk.size - np.count_nonzero(blk))
    slots = live + dead
    prep_pad = 0.0
    if prep_tgt is not None and prep_tgt.size:
        prep_pad = float(np.mean(prep_tgt == k_alloc - 1))
    return {
        "row_waste": round(1.0 - active_rows / total_rows, 4) if total_rows
        else 0.0,
        "slice_waste": round(dead / slots, 4) if slots else 0.0,
        "mean_row_waste": round(prep_pad, 4),
        "shape": tuple(int(s) for s in gsgn.shape),
    }


def pack_layer(stage_specs: dict[str, tuple[list[list[dict]], int, int]]
               ) -> dict[str, PackedStage]:
    """Pack every stage of a layer plan: name -> (layer_sites, d_src, out_dim)."""
    return {name: pack_stage(sites, d_src=d_src, out_dim=out_dim)
            for name, (sites, d_src, out_dim) in stage_specs.items()}


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
