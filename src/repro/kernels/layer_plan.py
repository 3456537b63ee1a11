"""Pallas kernels for layer plans: ONE launch per decode step / MoE layer.

The per-region runtime (``lcc_group_matmul`` per q/k/v, per gate/up, per down,
plus segment-sum and per-site launches) still pays many dispatches per layer.
On the measured CPU-interpreter floor each dispatch unrolls into its own chunk
of XLA ops, so dispatch count — not arithmetic — dominates decode wall-clock.
These kernels collapse the whole transformer decode step into a single
``pallas_call``: every layer of the stacked ``[L, …]`` plan buffers executes
in sequence inside one kernel body (pre-norm, fused q+k+v, rope, KV merge,
attention, o-proj, post-norm, fused gate+up, SwiGLU, down, residuals), with
the running hidden state ``x`` carried as a kernel-local value; only token
embeddings, the KV cache view and the new K/V rows cross the boundary.  The
layer loop lives *inside* the kernel rather than on a ``grid=(L,)``: the
interpreter materializes every operand block per grid step, which measures
~1.5x slower than slicing the stacked buffers in-kernel.

Inside a stage the inner loop is specialized to the ternary/CSD structure
(``core/csd.py``): factor rows are ``sum_s sign * 2^exp * prev[idx]``, i.e. a
sign gather + shift-add — evaluated directly from the packed (idx, exp, sign)
streams of :class:`repro.kernels.ops.PackedStage` with no sign-padded dense
tiles and no per-site slab padding.  Pack time fuses adjacent CSD levels
pairwise (``ops._fuse_csd_levels``) — exponents add, signs multiply — so the
kernel walks half the sequential depth at the same add count.  FS-program
slices and uncovered sites ride along as baked dense blocks so the stage
always emits the layer's full output.

These kernels are gather/scatter-shaped and target the *interpreter* path
(the environment this repo benches on); compiled Mosaic keeps the per-region
grouped kernels and their row-gather body.  The executor gates plan
construction on ``resolve_interpret``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .dispatch import record_launch, resolve_interpret
from .ops import PackedStage

__all__ = ["step_plan_matmul", "moe_plan_matmul", "stage_matmul"]

_NEG = -1e30


def _stage_apply(ps: PackedStage, ops_l, src, layer: int = 0):
    """Evaluate one stage for one layer: src [D_src, B] -> [O, B].

    ``ops_l`` holds the stage's operand arrays in :meth:`PackedStage.operands`
    order, already sliced to this layer (leading layer axis stripped).
    Segment-packed stages (``ps.segs is not None``) evaluate through the
    specialized path below: the descriptors statically trim and restructure
    how the traced operands are consumed.
    """
    if ps.eff is not None:
        return _stage_apply_eff(ps, ops_l, src, layer)
    if ps.segs is not None:
        return _stage_apply_seg(ps, ops_l, src, layer)
    cur = [0]

    def nxt():
        a = ops_l[cur[0]]
        cur[0] += 1
        return a

    b = src.shape[1]
    out = jnp.zeros((ps.out_dim, b), jnp.float32)
    inbuf = None
    if ps.has_prep:
        psrc, ptgt = nxt(), nxt()
        # kept-column gather + weight-sharing segment-sum in one scatter-add;
        # padding pairs add src[0] into the dead row k_alloc-1 (never read)
        inbuf = jnp.zeros((ps.k_alloc, b), jnp.float32).at[ptgt].add(src[psrc])
    if ps.has_fp:
        gidx, gcoef, outg = nxt(), nxt(), nxt()
        n_lv, r_rows, s_terms = gidx.shape
        work = None
        for p in range(n_lv):  # CSD shift-add: sum_s sign * 2^exp * prev[idx]
            buf = inbuf if p == 0 else work
            g = buf[gidx[p].reshape(-1)].reshape(r_rows, s_terms, b)
            # einsum: XLA lowers the S-contraction to a batched dot, which
            # vectorizes ~2.5x better on CPU than broadcast-multiply-sum
            work = jnp.einsum("rs,rsb->rb", gcoef[p], g)
        wext = jnp.concatenate([work, jnp.zeros((1, b), jnp.float32)], axis=0)
        n_j = outg.shape[0]
        out = out + wext[outg.reshape(-1)].reshape(n_j, ps.out_dim, b).sum(axis=0)
    if ps.fs_mat is not None:
        out = out + nxt() @ inbuf
    if ps.dw_mat is not None:
        out = out + nxt() @ src
    if ps.bias is not None:
        out = out + nxt()[:, None]
    return out


def _stage_apply_eff(ps: PackedStage, ops_l, src, layer: int):
    """Folded-effective stage evaluation: src [D_src, B] -> [O, B].

    One GEMM against the stage's composed effective matrix
    (:attr:`PackedStage.eff`) — the minimum-dispatch lowering for the
    interpreter path, where per-op dispatch and batch-scaled gather traffic,
    not arithmetic, bound the decode step."""
    out = ops_l[0] @ src
    if ps.bias is not None:
        if np.any(ps.bias[layer]):
            out = out + ops_l[1][:, None]
    return out


def _stage_apply_seg(ps: PackedStage, ops_l, src, layer: int):
    """Segment-packed stage evaluation: src [D_src, B] -> [O, B].

    Index *values* come from the traced operands (Pallas forbids closed-over
    constants), but the ``segs`` descriptors and the numpy mirrors on ``ps``
    are static, so the structure specializes at trace time: the per-level
    gather shrinks to the run-length-sorted active prefix at its live term
    width, pure-identity levels are skipped, ended chains continue as a
    contiguous slice copy instead of an identity gather, contiguous output
    windows lower to ``lax.slice``, and all-zero dense blocks drop out.
    """
    cur = [0]

    def nxt():
        a = ops_l[cur[0]]
        cur[0] += 1
        return a

    b = src.shape[1]
    out = jnp.zeros((ps.out_dim, b), jnp.float32)
    inbuf = None
    if ps.has_prep:
        psrc_t, ptgt_t = nxt(), nxt()
        tgt = ps.prep_tgt[layer].astype(np.int64)
        real = tgt < ps.k_alloc - 1  # padding pairs target the dead row
        k_used = int(tgt[real].max()) + 1 if real.any() else 0
        if (k_used == int(real.sum())
                and np.array_equal(tgt[:k_used], np.arange(k_used))):
            # no weight sharing and pairs laid out in target order: the
            # scatter-add collapses to a gather (+ zero-fill when padded)
            inbuf = src[psrc_t[:k_used]]
            if k_used < ps.k_alloc:
                inbuf = jnp.concatenate(
                    [inbuf,
                     jnp.zeros((ps.k_alloc - k_used, b), jnp.float32)])
        else:
            inbuf = jnp.zeros((ps.k_alloc, b), jnp.float32) \
                .at[ptgt_t].add(src[psrc_t])
    if ps.has_fp:
        gidx_t, gcoef_t, outg_t = nxt(), nxt(), nxt()
        r_max = ps.gidx.shape[2]
        work = None
        for p in range(ps.gidx.shape[1]):
            a_end, r_used, s_live = (int(v) for v in ps.segs[layer, p])
            buf = inbuf if p == 0 else work
            if p > 0 and a_end == 0:
                continue  # every chain already ended: identity level (rows
                # past r_used are already zero in the carried work buffer)
            s_l = max(s_live, 1)  # identity rows still read term column 0
            # two lowerings of the same level, chosen by gather volume:
            # the row-segmented form saves the einsum over the identity run
            # and zero tail but costs extra slice/concat ops — on the
            # op-overhead-dominated interpreter that only pays off once the
            # rows saved carry enough data; otherwise keep the 2-op full-row
            # einsum, column-trimmed to the live term width.
            seg_rows = (r_used > a_end and p > 0) or r_max > r_used
            if seg_rows and (r_max - a_end) * s_l * b >= 65536:
                pieces = []
                if a_end:
                    g = buf[gidx_t[p, :a_end, :s_l].reshape(-1)] \
                        .reshape(a_end, s_l, b)
                    pieces.append(
                        jnp.einsum("rs,rsb->rb", gcoef_t[p, :a_end, :s_l], g))
                if r_used > a_end:  # ended chains: contiguous identity run
                    if p == 0:  # 0-depth chains gather their own inbuf rows
                        pieces.append(buf[gidx_t[p, a_end:r_used, 0]])
                    else:
                        pieces.append(buf[a_end:r_used])
                if r_max > r_used:
                    pieces.append(jnp.zeros((r_max - r_used, b), jnp.float32))
                work = (pieces[0] if len(pieces) == 1
                        else jnp.concatenate(pieces))
            else:
                g = buf[gidx_t[p, :, :s_l].reshape(-1)].reshape(r_max, s_l, b)
                work = jnp.einsum("rs,rsb->rb", gcoef_t[p, :, :s_l], g)
        arange_o = np.arange(ps.out_dim)
        outg_np = ps.outg[layer].astype(np.int64)
        kept = [j for j in range(outg_np.shape[0])
                if not np.all(outg_np[j] == r_max)]  # drop all-padding rows
        if len(kept) == 1 and np.array_equal(
                outg_np[kept[0]], arange_o + outg_np[kept[0], 0]):
            # single contiguous window: one slice, no padding row needed
            out = out + jax.lax.slice_in_dim(
                work, int(outg_np[kept[0], 0]),
                int(outg_np[kept[0], 0]) + ps.out_dim, axis=0)
        elif kept:
            src_buf = work
            if any(np.any(outg_np[j] == r_max) for j in kept):
                # padded entries read the appended zero row
                src_buf = jnp.concatenate(
                    [work, jnp.zeros((1, b), jnp.float32)], axis=0)
            idx = outg_t[np.asarray(kept)] if len(kept) < outg_np.shape[0] \
                else outg_t
            out = out + src_buf[idx.reshape(-1)] \
                .reshape(len(kept), ps.out_dim, b).sum(axis=0)
    if ps.fs_mat is not None:
        m = nxt()
        if np.any(ps.fs_mat[layer]):
            out = out + m @ inbuf
    if ps.dw_mat is not None:
        m = nxt()
        if np.any(ps.dw_mat[layer]):
            out = out + m @ src
    if ps.bias is not None:
        v = nxt()
        if np.any(ps.bias[layer]):
            out = out + v[:, None]
    return out


def _load_refs(refs):
    """Read operand refs once; per-layer slices are taken off the values."""
    return [r[...] for r in refs]


def step_plan_matmul(stages: dict[str, PackedStage], *, n_heads: int,
                     n_kv_heads: int, head_dim: int, d_ff: int, norm: str,
                     rope: bool, x0, pos, cos, sin, ln1, ln2, kc, vc, kpos,
                     moe: dict | None = None, window: int | None = None,
                     interpret: bool | None = None):
    """Whole decode step in ONE launch for all L identical layers.

      x0   [d, B] f32    embedded tokens (feature-major)
      pos  [B] int32     decode positions (-1 = inactive slot)
      cos/sin [B, hd/2]  rope tables for ``pos`` (None when rope=False)
      ln1/ln2 [L, d]     rms weights (None when norm == "nonparam")
      kc/vc [L, B, S, Hkv, hd], kpos [L, B, S]   KV cache view

    ``moe`` (whole-step MoE plans): replaces the dense FFN with the full
    routed block *in-kernel* — router logits/softmax/top-k, rank-and-scatter
    dispatch at capacity B (drop-free), the two expert super-stages ("eg" fused
    gate+up over all experts e-major, SwiGLU, "ed" downs) and the gated
    combine — so an MoE layer costs zero extra launches.  Keys: ``router``
    [L, d, E] f32 numpy, ``n_experts``, ``top_k``, ``norm_topk``, ``d_ff``
    (= E * d_ff_expert).  The routing math mirrors ``models.moe.moe_ffn``
    exactly (no row dropped), so plan and fallback decode agree.

    Returns (y [d, B] f32, k_new [L, B, Hkv, hd] f32, v_new …): the final
    hidden state and the per-layer K/V rows for the caller to scatter back
    into the cache (contiguous or paged) outside the kernel.
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "step plans target the interpreter path; compiled TPU uses the "
            "per-region grouped kernels")
    record_launch()  # the whole step is ONE pallas_call
    n_layers, b, smax, n_kv, hd = kc.shape
    d = x0.shape[0]
    half = hd // 2
    stage_order = ("qkv", "o", "eg", "ed") if moe is not None \
        else ("qkv", "o", "gu", "dn")
    if moe is not None:
        n_exp, top_k = moe["n_experts"], moe["top_k"]
        cap = b  # a token picks an expert at most once: nothing drops
        eff_total = moe["d_ff"]  # E * d_ff_expert

    inputs = [x0.astype(jnp.float32), pos.astype(jnp.int32)]
    if rope:
        inputs += [cos.astype(jnp.float32), sin.astype(jnp.float32)]
    if norm == "rms":
        inputs += [jnp.asarray(ln1, jnp.float32), jnp.asarray(ln2, jnp.float32)]
    inputs += [kc.astype(jnp.float32), vc.astype(jnp.float32),
               kpos.astype(jnp.int32)]
    if moe is not None:
        inputs.append(jnp.asarray(moe["router"], jnp.float32))  # [L, d, E]
    counts = []
    for name in stage_order:
        ops_ = stages[name].operands()
        counts.append(len(ops_))
        inputs += [jnp.asarray(a) for a in ops_]

    def kernel(*refs):
        i = [0]

        def take(n=1):
            r = refs[i[0]: i[0] + n]
            i[0] += n
            return r if n > 1 else r[0]

        x0_ref, pos_ref = take(), take()
        cos_ref = sin_ref = None
        if rope:
            cos_ref, sin_ref = take(), take()
        ln1_ref = ln2_ref = None
        if norm == "rms":
            ln1_ref, ln2_ref = take(), take()
        kc_ref, vc_ref, kp_ref = take(), take(), take()
        router_ref = take() if moe is not None else None
        stage_refs = {}
        for name, n in zip(stage_order, counts):
            stage_refs[name] = refs[i[0]: i[0] + n]
            i[0] += n
        y_ref, kn_ref, vn_ref = refs[i[0]:]

        def norm_fn(v, w):
            if norm == "rms":
                var = jnp.mean(v * v, axis=0, keepdims=True)
                return v * jax.lax.rsqrt(var + 1e-6) * w[:, None]
            mu = jnp.mean(v, axis=0, keepdims=True)
            var = jnp.mean((v - mu) ** 2, axis=0, keepdims=True)
            return (v - mu) * jax.lax.rsqrt(var + 1e-5)

        pos_v = pos_ref[...]
        cos_v = cos_ref[...][:, None, :] if rope else None
        sin_v = sin_ref[...][:, None, :] if rope else None
        kc_v, vc_v, kp_v = kc_ref[...], vc_ref[...], kp_ref[...]
        ln1_v = ln1_ref[...] if norm == "rms" else None
        ln2_v = ln2_ref[...] if norm == "rms" else None
        sidx = jax.lax.broadcasted_iota(jnp.int32, (b, smax), 1)
        # sliding window: the cache is a ring buffer (slot = pos % smax) and
        # keys older than the window are masked, matching attention_decode
        slot_v = (jnp.where(pos_v >= 0, pos_v % smax, -1)
                  if window is not None else pos_v)
        hit = sidx == slot_v[:, None]
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
        nq = n_heads

        router_v = router_ref[...] if moe is not None else None

        def moe_block(layer, sops, h2):
            """Routed FFN in-kernel: h2 [d, B] -> [d, B] (moe_ffn's math)."""
            router = router_v[layer]  # [d, E]
            xt = h2.T  # [B, d] token-major, matching moe_ffn's layout
            logits = xt @ router  # [B, E]
            probs = jax.nn.softmax(logits, axis=-1)
            gates, sel = jax.lax.top_k(probs, top_k)  # [B, k]
            if moe["norm_topk"]:
                gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
            sel_oh = jax.nn.one_hot(sel, n_exp, dtype=jnp.int32)  # [B, k, E]
            flat_oh = sel_oh.reshape(b * top_k, n_exp)
            ranks = (jnp.cumsum(flat_oh, axis=0) - flat_oh) \
                .reshape(b, top_k, n_exp)
            rank = jnp.sum(ranks * sel_oh, axis=-1)  # [B, k]
            slot = sel * cap + rank
            buf = jnp.zeros((n_exp * cap, d), jnp.float32)
            for j in range(top_k):
                buf = buf.at[slot[:, j]].set(xt)
            # e-major flatten for the expert super-stages: [E*d, C]
            src = buf.reshape(n_exp, cap, d).transpose(0, 2, 1) \
                .reshape(n_exp * d, cap)
            eg = _stage_apply(stages["eg"], sops["eg"], src, layer)
            hf = jax.nn.silu(eg[:eff_total]) * eg[eff_total:]
            ob = _stage_apply(stages["ed"], sops["ed"], hf, layer)
            out_buf = ob.reshape(n_exp, d, cap).transpose(0, 2, 1) \
                .reshape(n_exp * cap, d)
            y = jnp.zeros((b, d), jnp.float32)
            for j in range(top_k):
                g = jnp.take(out_buf, slot[:, j], axis=0)
                y = y + gates[:, j][:, None] * g
            return y.T

        stage_vals = {name: _load_refs(stage_refs[name])
                      for name in stage_order}
        x = x0_ref[...]  # [d, B], carried across the in-kernel layer loop
        for layer in range(n_layers):
            sops = {name: [v[layer] for v in stage_vals[name]]
                    for name in stage_order}
            h = norm_fn(x, ln1_v[layer] if ln1_v is not None else None)
            qkv = _stage_apply(stages["qkv"], sops["qkv"], h, layer)
            qb = qkv[: nq * hd].reshape(nq, hd, b).transpose(2, 0, 1)
            kb = qkv[nq * hd: (nq + n_kv) * hd] \
                .reshape(n_kv, hd, b).transpose(2, 0, 1)
            vb = qkv[(nq + n_kv) * hd:].reshape(n_kv, hd, b).transpose(2, 0, 1)
            if rope:
                def rot(v):
                    v1, v2 = v[..., :half], v[..., half:]
                    return jnp.concatenate([v1 * cos_v - v2 * sin_v,
                                            v2 * cos_v + v1 * sin_v], axis=-1)

                qb, kb = rot(qb), rot(kb)
            kn_ref[layer] = kb
            vn_ref[layer] = vb
            # score the stale cache and patch the current token's column in
            # score space: merging the new K/V row into a [B, S, Hkv, hd]
            # cache copy per layer costs more memory traffic than the whole
            # einsum, and the hit column is one-hot so the patch is exact
            qg = qb.reshape(b, n_kv, nq // n_kv, hd)
            scores = jnp.einsum("bhgd,bshd->bhgs", qg, kc_v[layer])
            s_new = jnp.einsum("bhgd,bhd->bhg", qg, kb)
            scores = jnp.where(hit[:, None, None, :], s_new[..., None], scores)
            ok = (kp_v[layer] >= 0) & (kp_v[layer] <= pos_v[:, None])
            if window is not None:
                ok = ok & (kp_v[layer] > pos_v[:, None] - window)
            valid = jnp.where(hit, pos_v[:, None] >= 0, ok)
            mask = jnp.where(valid, 0.0, _NEG)
            probs = jax.nn.softmax(scores * scale + mask[:, None, None, :],
                                   axis=-1)
            hitf = hit.astype(jnp.float32)[:, None, None, :]
            p_hit = jnp.sum(probs * hitf, axis=-1)  # weight on the new row
            att = jnp.einsum("bhgs,bshd->bhgd", probs * (1.0 - hitf),
                             vc_v[layer]) + p_hit[..., None] * vb[:, :, None, :]
            x = x + _stage_apply(stages["o"], sops["o"],
                                 att.reshape(b, nq * hd).T, layer)
            h2 = norm_fn(x, ln2_v[layer] if ln2_v is not None else None)
            if moe is not None:
                x = x + moe_block(layer, sops, h2)
            else:
                gu = _stage_apply(stages["gu"], sops["gu"], h2, layer)
                hf = jax.nn.silu(gu[:d_ff]) * gu[d_ff:]
                x = x + _stage_apply(stages["dn"], sops["dn"], hf, layer)
        y_ref[...] = x

    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((d, b), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, b, n_kv, hd), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, b, n_kv, hd), jnp.float32),
        ],
        interpret=True,
    )(*inputs)


def moe_plan_matmul(stage_a: PackedStage, stage_b: PackedStage, *,
                    d_ff_total: int, src, interpret: bool | None = None):
    """One MoE layer's expert FFNs in ONE launch: src [E*d, C] -> [E*d, C].

    Stage A emits all experts' gates at rows [0, E*dff) and ups at
    [E*dff, 2*E*dff) (e-major); SwiGLU runs in-kernel; stage B applies the
    down projections.  Replaces the three grouped ``expert_mm`` dispatches.
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "MoE plans target the interpreter path; compiled TPU uses the "
            "per-region grouped kernels")
    record_launch()
    d_src, c = src.shape
    n_a = len(stage_a.operands())
    inputs = [src.astype(jnp.float32)]
    for ps in (stage_a, stage_b):
        inputs += [jnp.asarray(a) for a in ps.operands()]

    def kernel(*refs):
        src_ref = refs[0]
        a_ops = [v[0] for v in _load_refs(refs[1: 1 + n_a])]
        b_ops = [v[0] for v in _load_refs(refs[1 + n_a: -1])]
        out_ref = refs[-1]
        h = _stage_apply(stage_a, a_ops, src_ref[...])
        hf = jax.nn.silu(h[:d_ff_total]) * h[d_ff_total:]
        out_ref[...] = _stage_apply(stage_b, b_ops, hf)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((stage_b.out_dim, c), jnp.float32),
        interpret=True,
    )(*inputs)


def stage_matmul(ps: PackedStage, src, *, interpret: bool | None = None):
    """Apply one stage standalone: src [L, D_src, B] -> [L, O, B].

    Unit-test surface for the stage contract (and a building block for
    plans over non-transformer families).
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError("stage plans target the interpreter path")
    record_launch()
    n_layers, d_src, b = src.shape
    inputs = [src.astype(jnp.float32)] + [jnp.asarray(a) for a in ps.operands()]

    def kernel(*refs):
        src_v = refs[0][...]
        vals = _load_refs(refs[1:-1])
        for layer in range(n_layers):
            refs[-1][layer] = _stage_apply(ps, [v[layer] for v in vals],
                                           src_v[layer], layer)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_layers, ps.out_dim, b), jnp.float32),
        interpret=True,
    )(*inputs)
