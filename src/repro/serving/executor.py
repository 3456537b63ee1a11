"""Site-keyed compressed execution: route EVERY compressed site through fused
kernels at serving time.

The PR-2 engine routed only dense-family FFN projections through the fused
LCC chain; every other site an adapter can compress (attention q/k/v/o, MoE
experts, RWKV/Mamba mixes, Whisper decoder, ResNet convs) fell back to its
dense-effective weights — the artifact saved memory but not computation.
:class:`CompressedExecutor` closes that gap: built from any
:class:`~repro.core.artifact.CompressedModel`, it maps every adapter site name
(the keys of ``artifact.records``, produced by
``models.compress_adapters.sites_for``) to a fused-kernel callable, and the
model decode paths consult it *inside* the jitted step.

Three kernel routes:

* :class:`LCCMatvec` — one dense site: prune gather -> eq. (10) segment-sum ->
  the whole FP chain in ONE ``lcc_chain_matmul`` launch.
* :class:`GroupedLCCMatvec` — one *fused region*: several sites (an MoE
  layer's experts, an attention layer's q/k/v, RWKV's r/k/v/g) apply their
  chains in ONE ``lcc_group_matmul`` launch, so a decode step pays one
  dispatch per region instead of one per site.
* :class:`ConvLCC` — a conv site executed in the compressed domain: the
  FK/PK reshape of ``core.conv_reshape`` turns the conv into per-channel
  CMVMs and all decomposed channels run as one grouped launch.

Models never import this module — they receive the executor as an opaque
object with the protocol ``matvec(name)``, ``grouped(names)``, ``conv(name)``
(each returning a callable or None) so the dependency stays
serving -> models, never the reverse.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CompressedExecutor", "LCCMatvec", "GroupedLCCMatvec", "ConvLCC",
           "StepPlan", "MoEPlan", "matvecs_from_artifact"]


def kernel_name(kernel: str, sites) -> str:
    """The launch name of an LCC kernel over ``sites``: ``kernel`` and the
    sites' projection names, so the device trace names each site group
    (``_lcc_group_matmul_qkv``, ``_lcc_chain_matmul_o``,
    ``_lcc_group_matmul_gate_up``, ``_lcc_chain_matmul_down``) and a layer's
    index never enters it: every layer's launch of a group shares one name.
    """
    projs = list(dict.fromkeys(n.split(".")[1] if "." in n else n
                               for n in sites))
    sep = "" if all(len(p) == 1 for p in projs) else "_"
    return f"{kernel}_{sep.join(projs)}"


class LCCMatvec:
    """One compressed projection as a fused-kernel matvec: x [K, B] -> [N, B].

    Prune (kept_columns gather) -> optional weight-sharing segment-sum (paper
    eq. (10)) -> the whole FP decomposition in a single ``lcc_chain_matmul``
    launch.  Built from a ``core.compress.CompressedDense`` record; pass
    ``packed=`` to reuse an artifact's pre-packed kernel buffers instead of
    re-packing the decomposition.

    ``B`` is bucketed to powers of two (pad + slice), so serving many distinct
    decode/prefill batch widths compiles at most log2 variants of the fused
    chain instead of one per width.
    """

    def __init__(self, cd, *, packed=None, block: int = 128,
                 interpret: bool | None = None):
        from repro.kernels import ops

        self.name = cd.name
        self.packed = (packed if packed is not None
                       else ops.pack_decomposition(cd.decomposition, block))
        self.kept = jnp.asarray(np.asarray(cd.kept_columns), jnp.int32)
        self.labels = (jnp.asarray(cd.shared.labels, jnp.int32)
                       if cd.shared is not None else None)
        self.n_clusters = cd.shared.n_clusters if cd.shared is not None else 0
        self.interpret = interpret
        self.kernel_name = kernel_name("_lcc_chain_matmul", [cd.name])
        # jit the whole chain (gather -> segment-sum -> fused kernel) so a
        # per-token decode loop pays one dispatch, not one per slice/stage
        self._fn = jax.jit(self._run)

    def _run(self, x: jnp.ndarray) -> jnp.ndarray:
        from repro.kernels import ops

        xk = x[self.kept]
        if self.labels is not None:
            xk = ops.segment_sum_tpu(self.labels, xk, self.n_clusters,
                                     interpret=self.interpret)
        return ops.apply_packed_decomposition(self.packed, xk,
                                              interpret=self.interpret,
                                              name=self.kernel_name)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        vec = x.ndim == 1
        if vec:
            x = x[:, None]
        b = x.shape[1]
        b_pad = 1 << (b - 1).bit_length()  # next power of two (b=1 -> 1)
        if b_pad != b:
            x = jnp.pad(x, ((0, 0), (0, b_pad - b)))
        y = self._fn(x)
        return y[:, 0] if vec else y[:, :b]


class GroupedLCCMatvec:
    """Several compressed sites applied in ONE fused launch (a *fused region*).

    Call with a per-site list of features-major inputs ``[K_g, B]`` (all the
    same batch width; input widths may differ — each member gathers its own
    kept columns and segment-sums its own clusters before the shared
    ``lcc_group_matmul`` dispatch).  Returns the per-site ``[N_g, B]`` outputs.
    """

    def __init__(self, records, *, packed=None, block: int = 128,
                 interpret: bool | None = None):
        from repro.kernels import ops

        packed = packed or [None] * len(records)
        members = [pk if pk is not None
                   else ops.pack_decomposition(cd.decomposition, block)
                   for cd, pk in zip(records, packed)]
        self.names = tuple(cd.name for cd in records)
        self.group = ops.pack_group(members)
        # cached state stays numpy: groups are assembled lazily — the first
        # decode trace to name this fused region builds the object — and numpy
        # constants embed per-trace instead of leaking that trace's tracers
        self.kept = [np.asarray(cd.kept_columns, np.int32) for cd in records]
        self.labels = [np.asarray(cd.shared.labels, np.int32)
                       if cd.shared is not None else None for cd in records]
        self.n_clusters = [cd.shared.n_clusters if cd.shared is not None else 0
                           for cd in records]
        self.interpret = interpret
        self.kernel_name = kernel_name("_lcc_group_matmul", self.names)
        self._fn = jax.jit(self._run)

    def _run(self, xs):
        from repro.kernels import ops

        prep = []
        for x, kept, labels, nc in zip(xs, self.kept, self.labels,
                                       self.n_clusters):
            xk = x[kept]
            if labels is not None:
                xk = ops.segment_sum_tpu(labels, xk, nc,
                                         interpret=self.interpret)
            prep.append(xk)
        return tuple(ops.apply_packed_group(self.group, prep,
                                            interpret=self.interpret,
                                            name=self.kernel_name))

    def __call__(self, xs) -> list[jnp.ndarray]:
        b = xs[0].shape[1]
        b_pad = 1 << (b - 1).bit_length()
        if b_pad != b:
            xs = [jnp.pad(x, ((0, 0), (0, b_pad - b))) for x in xs]
        ys = self._fn(tuple(xs))
        return [y[:, :b] for y in ys]


class ConvLCC:
    """One compressed conv layer executed in the compressed domain.

    Decomposed input channels run their FK/PK CMVM chains in ONE grouped
    launch over ``core.conv_reshape``'s window extraction; channels without a
    decomposition (subsampled-out or pruned) go through a dense conv on the
    residual kernel.  Matches ``lax.conv`` SAME/VALID semantics including
    stride, so ``resnet_forward(..., executor=...)`` is a drop-in.
    """

    def __init__(self, name: str, kernel: np.ndarray, record: dict,
                 method: str, *, block: int = 128,
                 interpret: bool | None = None):
        from repro.kernels import ops

        self.name = name
        self.method = method
        self.n, _, self.o, _ = kernel.shape
        self.channels = sorted(record["decompositions"])
        packed = [ops.pack_decomposition(record["decompositions"][ch], block)
                  for ch in self.channels]
        self.group = ops.pack_group(packed) if packed else None
        rest = np.asarray(kernel, np.float32).copy()
        rest[:, self.channels] = 0.0  # chain channels leave the dense conv
        self.rest = jnp.asarray(rest)
        self.has_rest = bool(np.abs(rest).max() > 0)
        self.interpret = interpret
        self._fn = jax.jit(self._run, static_argnames=("stride", "padding"))

    def _run(self, x: jnp.ndarray, stride: int = 1, padding: str = "SAME"
             ) -> jnp.ndarray:
        from jax import lax

        from repro.core.conv_reshape import (extract_patches,
                                             extract_vert_windows, same_pad_2d)
        from repro.kernels import ops

        b, k, z, _ = x.shape
        o = self.o
        if padding == "SAME":
            lo, hi = same_pad_2d(z, o, stride)
            xp = jnp.pad(x, ((0, 0), (0, 0), (lo, hi), (lo, hi)))
        else:
            xp = x
        zp = xp.shape[2]
        p = (zp - o) // stride + 1
        y = None
        if self.has_rest:
            y = lax.conv_general_dilated(
                xp.astype(jnp.float32), self.rest, (stride, stride), "VALID",
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if self.group is not None:
            xc = xp[:, jnp.asarray(self.channels, jnp.int32)]
            if self.method == "fk":
                pat = extract_patches(xc, o, stride)  # [B, C, P, P, O, O]
                xs = [pat[:, i].reshape(b * p * p, o * o).T
                      for i in range(len(self.channels))]
                ys = ops.apply_packed_group(self.group, xs,
                                            interpret=self.interpret)
                yc = sum(ys)  # [N, B*P*P]
                yc = jnp.moveaxis(yc.T.reshape(b, p, p, self.n), -1, 1)
            else:  # pk: rows (n, j) are kernel columns over vertical windows
                win = extract_vert_windows(xc, o, stride)  # [B, C, P, Zp, O]
                xs = [win[:, i].reshape(b * p * zp, o).T
                      for i in range(len(self.channels))]
                ys = ops.apply_packed_group(self.group, xs,
                                            interpret=self.interpret)
                # sum channel parts, then gather the j-offset columns:
                # y[b, n, p, q] = sum_j part[b, p, q*stride + j, n, j]
                part = sum(ys)  # [N*O, B*P*Zp]
                part = part.reshape(self.n, o, b, p, zp)
                part = jnp.transpose(part, (2, 3, 4, 0, 1))  # [B, P, Zp, N, O]
                cq = stride * jnp.arange(p)[:, None] + jnp.arange(o)[None, :]
                sel = part[:, :, cq]  # [B, P, Q, O(j), N, O(j')]
                yc = jnp.moveaxis(jnp.einsum("bpqjnj->bpqn", sel), -1, 1)
            y = yc if y is None else y + yc
        if y is None:
            raise ValueError(f"conv site {self.name!r}: nothing to execute")
        return y.astype(x.dtype)

    def __call__(self, x: jnp.ndarray, *, stride: int = 1,
                 padding: str = "SAME") -> jnp.ndarray:
        return self._fn(x, stride=stride, padding=padding)


def _mesh_wrap(fn, b: int, *, batch_axes, out_axes, replicate=False,
               mesh=None):
    """Wrap a layer-plan kernel call in ``shard_map`` when serving under a
    device mesh, so each shard runs the one-launch plan over its local slots.

    ``batch_axes``/``out_axes`` give the batch(-slot) axis position of each
    positional argument / output.  Stage buffers are trace-time constants and
    embed replicated per shard.  ``replicate=True`` (MoE plans) keeps the
    batch axis unsplit: the router's ranks are global-batch ops, so
    slot-splitting would change the dispatch — every shard then computes the
    identical full step, which still dodges the GSPMD partitioner that the
    interpreter-mode kernel cannot pass through.
    """
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.distributed import act_shard
    from repro.distributed import sharding as shd

    if mesh is None:
        mesh = act_shard.get_mesh()
    if mesh is None:
        return fn
    bspec = None if replicate else shd.plan_batch_spec(mesh, b)

    def pspec(ax):
        if bspec is None:
            return P()
        return P(*([None] * ax + [bspec]))

    return compat.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(pspec(a) for a in batch_axes),
        out_specs=tuple(pspec(a) for a in out_axes),
        check_vma=False)


class StepPlan:
    """Whole-decode-step layer plan for the dense transformer family.

    Packs every site of every layer — attention q/k/v/o and FFN gate/up/down,
    compressed (CSD shift-add segments) or not (baked dense blocks) — into
    four stacked :class:`~repro.kernels.ops.PackedStage` buffers and executes
    the full step as ONE ``pallas_call`` with grid ``(L,)``
    (:func:`repro.kernels.layer_plan.step_plan_matmul`).  KV write-back runs
    outside the kernel, vectorized over layers, for both contiguous and paged
    caches.

    MoE families (``cfg.moe``): the FFN stages become the two *expert
    super-stages* — "eg" (all experts' gates+ups, e-major ``[E*d] ->
    [2*E*dff]``) and "ed" (all downs, ``[E*dff] -> [E*d]``) — and the router
    weights ride along as a trace-time constant so the whole routed block
    (softmax/top-k, drop-free dispatch, SwiGLU, gated combine) runs *inside*
    the single step launch.

    Under a device mesh, :meth:`decode_layers` wraps the kernel in
    ``shard_map``: activations and the KV view split on the batch/slot axis
    over ("pod","data") while the stage buffers — trace-time constants —
    embed replicated per shard, so distributed serving keeps the one
    launch-per-plan step.
    """

    def __init__(self, executor, cfg):
        from repro.kernels import ops

        self.executor = executor
        self.cfg = cfg
        art = executor.artifact
        blocks = art.params["blocks"]
        d, dff = cfg.d_model, cfg.d_ff
        nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        covered: list[str] = []

        def spec(name, w_stack, li, out_off, src_off=0, bias_stack=None):
            rec = art.records.get(name)
            # np.asarray BEFORE indexing: the plan may build lazily inside a
            # jit trace, where even slicing a concrete constant binds a traced
            # op — converting the whole stack first keeps the build pure-host
            bias = (np.asarray(bias_stack, np.float32)[li]
                    if bias_stack is not None else None)
            if rec is None or not hasattr(rec, "decomposition"):
                # uncovered site: bake its dense weights into the stage so the
                # plan still emits the layer's full output
                return {"kind": "dense", "out_off": out_off,
                        "src_off": src_off,
                        "w": np.asarray(w_stack, np.float32)[li],
                        "bias": bias}
            covered.append(name)
            packed = art.packed.get(name)
            if packed is None:
                packed = ops.pack_decomposition(rec.decomposition,
                                                executor.block)
            return {"kind": "lcc", "name": name, "out_off": out_off,
                    "src_off": src_off,
                    "kept": np.asarray(rec.kept_columns, np.int64),
                    "labels": (np.asarray(rec.shared.labels, np.int64)
                               if rec.shared is not None else None),
                    "n_clusters": (rec.shared.n_clusters
                                   if rec.shared is not None else 0),
                    "packed": packed, "bias": bias}

        ab, fb = blocks["attn"], blocks["ffn"]
        qkv, o_ = [], []
        for li in range(cfg.n_layers):
            qkv.append([spec(f"attn.q.l{li}", ab["q"]["w"], li, 0,
                             bias_stack=ab["q"].get("b")),
                        spec(f"attn.k.l{li}", ab["k"]["w"], li, nq * hd,
                             bias_stack=ab["k"].get("b")),
                        spec(f"attn.v.l{li}", ab["v"]["w"], li, (nq + nkv) * hd,
                             bias_stack=ab["v"].get("b"))])
            o_.append([spec(f"attn.o.l{li}", ab["o"]["w"], li, 0,
                            bias_stack=ab["o"].get("b"))])
        stage_specs = {"qkv": (qkv, d, (nq + 2 * nkv) * hd),
                       "o": (o_, nq * hd, d)}
        self.moe = None
        if getattr(cfg, "moe", None) is None:
            gu, dn = [], []
            for li in range(cfg.n_layers):
                gu.append([spec(f"ffn.gate.l{li}", fb["gate"]["w"], li, 0,
                                bias_stack=fb["gate"].get("b")),
                           spec(f"ffn.up.l{li}", fb["up"]["w"], li, dff,
                                bias_stack=fb["up"].get("b"))])
                dn.append([spec(f"ffn.down.l{li}", fb["down"]["w"], li, 0,
                                bias_stack=fb["down"].get("b"))])
            stage_specs["gu"] = (gu, d, 2 * dff)
            stage_specs["dn"] = (dn, dff, d)
        else:
            ne, edff = cfg.moe.n_experts, cfg.moe.d_ff_expert
            gw = np.asarray(fb["gate"], np.float32)  # [L, E, d, dff]
            uw = np.asarray(fb["up"], np.float32)
            dw = np.asarray(fb["down"], np.float32)  # [L, E, dff, d]
            eg, ed = [], []
            for li in range(cfg.n_layers):
                a_sites, b_sites = [], []
                for ei in range(ne):
                    a_sites.append(spec(f"moe.gate.l{li}.e{ei}", gw[:, ei],
                                        li, ei * edff, ei * d))
                    a_sites.append(spec(f"moe.up.l{li}.e{ei}", uw[:, ei],
                                        li, ne * edff + ei * edff, ei * d))
                    b_sites.append(spec(f"moe.down.l{li}.e{ei}", dw[:, ei],
                                        li, ei * d, ei * edff))
                eg.append(a_sites)
                ed.append(b_sites)
            stage_specs["eg"] = (eg, ne * d, 2 * ne * edff)
            stage_specs["ed"] = (ed, ne * edff, ne * d)
            self.moe = {"router": np.asarray(fb["router"], np.float32),
                        "n_experts": ne, "top_k": cfg.moe.top_k,
                        "norm_topk": cfg.moe.norm_topk, "d_ff": ne * edff}
        pre = art.plans.get("step") if hasattr(art, "plans") else None
        if (pre is not None and set(pre) == set(stage_specs)
                and all(ps.n_layers == cfg.n_layers for ps in pre.values())):
            self.stages = pre  # artifact shipped plan-ready packed buffers
        else:
            self.stages = ops.pack_layer(stage_specs)
            if hasattr(art, "plans"):
                art.plans["step"] = self.stages
        stats = getattr(art, "pipeline_stats", None)
        if stats is not None:
            for name, ps in self.stages.items():
                if ps.waste is not None:
                    stats.setdefault("padding_waste",
                                     {})[f"plan.{name}"] = ps.waste
                if ps.seg_stats is not None:
                    stats.setdefault("segment_layout",
                                     {})[f"plan.{name}"] = ps.seg_stats
        self.ln1 = (np.asarray(blocks["ln1"], np.float32)
                    if cfg.norm == "rms" else None)
        self.ln2 = (np.asarray(blocks["ln2"], np.float32)
                    if cfg.norm == "rms" else None)
        self.covered = frozenset(covered)

    def decode_layers(self, state, x, pos):
        """x [B, 1, d] embedded tokens -> (x' [B, 1, d], new kv state)."""
        from repro.kernels import layer_plan
        from repro.models.layers import _rope_sincos

        cfg = self.cfg
        self.executor.routed.update(self.covered)
        k_state, v_state, kpos = state["k"], state["v"], state["kpos"]
        tbl = state.get("block_tbl")
        nl, nkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        b = x.shape[0]
        if tbl is not None:  # paged: pre-gather the table's view for the kernel
            kc = k_state[:, tbl].reshape(nl, b, -1, nkv, hd)
            vc = v_state[:, tbl].reshape(nl, b, -1, nkv, hd)
        else:
            kc, vc = k_state, v_state
        pos = pos.astype(jnp.int32)
        cos = sin = None
        rope = cfg.pos == "rope"
        if rope:
            sin, cos = _rope_sincos(pos, hd, cfg.rope_theta)

        def run(x0, pos_, cos_, sin_, kc_, vc_, kpos_):
            return layer_plan.step_plan_matmul(
                self.stages, n_heads=cfg.n_heads, n_kv_heads=nkv, head_dim=hd,
                d_ff=cfg.d_ff, norm=cfg.norm, rope=rope,
                x0=x0, pos=pos_, cos=cos_, sin=sin_,
                ln1=self.ln1, ln2=self.ln2, kc=kc_, vc=vc_, kpos=kpos_,
                moe=self.moe, window=cfg.attn_window,
                interpret=self.executor.interpret)

        args = (x[:, 0, :].astype(jnp.float32).T, pos, cos, sin, kc, vc, kpos)
        run = _mesh_wrap(run, b, batch_axes=(1, 0, 0, 0, 1, 1, 1),
                         out_axes=(1, 1, 1),
                         replicate=self.moe is not None,
                         mesh=self.executor.mesh)
        y, kn, vn = run(*args)
        dt = k_state.dtype
        kn, vn = kn.astype(dt), vn.astype(dt)
        win = cfg.attn_window
        if tbl is None:
            smax = k_state.shape[2]
            # sliding window: the cache is a ring buffer, slot = pos % smax
            slot = (jnp.where(pos >= 0, pos % smax, -1) if win is not None
                    else pos)
            # row scatter, not a one-hot merge: rewriting the full [L,B,S,...]
            # cache twice per step costs more than the attention einsums
            active = slot >= 0
            safe = jnp.where(active, slot, 0)
            bi = jnp.arange(b)
            am = active[None, :, None, None]
            new = {"k": k_state.at[:, bi, safe].set(
                       jnp.where(am, kn, k_state[:, bi, safe])),
                   "v": v_state.at[:, bi, safe].set(
                       jnp.where(am, vn, v_state[:, bi, safe])),
                   "kpos": kpos.at[:, bi, safe].set(
                       jnp.where(active[None], pos[None], kpos[:, bi, safe]))}
        else:
            bs = k_state.shape[2]
            w = jnp.maximum(pos, 0)
            if win is not None:
                w = w % kpos.shape[2]  # ring over the paged view
            bidx = jnp.take_along_axis(tbl, (w // bs)[:, None], axis=1)[:, 0]
            # inactive slots (pos == -1) scatter into the null block; their
            # kpos stays -1 so the stale row is never attended to
            bidx = jnp.where(pos >= 0, bidx, 0)
            slot = jnp.where(pos >= 0, w, -1) if win is not None else pos
            sel = jax.nn.one_hot(slot, kpos.shape[2])
            new = {"k": k_state.at[:, bidx, w % bs].set(kn),
                   "v": v_state.at[:, bidx, w % bs].set(vn),
                   "kpos": jnp.where(sel[None] > 0, pos[None, :, None], kpos),
                   "block_tbl": tbl}
        return y.T[:, None, :].astype(x.dtype), new


class MoEPlan:
    """One MoE layer's expert FFNs as a single launch.

    Two stages over flattened expert buffers — A: all gates+ups from
    ``[E*d, C]``, B: all downs from the in-kernel SwiGLU ``[E*dff, C]`` —
    replacing the three grouped ``expert_mm`` dispatches per layer.
    """

    def __init__(self, executor, site_tag: str, *, n_experts: int,
                 d_model: int, d_ff: int):
        from repro.kernels import ops

        self.executor = executor
        art = executor.artifact
        e, d, dff = n_experts, d_model, d_ff

        def spec(name, out_off, src_off):
            rec = art.records[name]
            packed = art.packed.get(name)
            if packed is None:
                packed = ops.pack_decomposition(rec.decomposition,
                                                executor.block)
            return {"kind": "lcc", "name": name, "out_off": out_off,
                    "src_off": src_off,
                    "kept": np.asarray(rec.kept_columns, np.int64),
                    "labels": (np.asarray(rec.shared.labels, np.int64)
                               if rec.shared is not None else None),
                    "n_clusters": (rec.shared.n_clusters
                                   if rec.shared is not None else 0),
                    "packed": packed, "bias": None}

        sa, sb, names = [], [], []
        for ei in range(e):
            sa.append(spec(f"moe.gate.{site_tag}.e{ei}", ei * dff, ei * d))
            sa.append(spec(f"moe.up.{site_tag}.e{ei}",
                           e * dff + ei * dff, ei * d))
            sb.append(spec(f"moe.down.{site_tag}.e{ei}", ei * d, ei * dff))
            names += [f"moe.{p}.{site_tag}.e{ei}"
                      for p in ("gate", "up", "down")]
        key = f"moe:{site_tag}"
        pre = art.plans.get(key) if hasattr(art, "plans") else None
        if pre is not None:
            self.stages = pre
        else:
            self.stages = {
                "a": ops.pack_stage([sa], d_src=e * d, out_dim=2 * e * dff),
                "b": ops.pack_stage([sb], d_src=e * dff, out_dim=e * d)}
            if hasattr(art, "plans"):
                art.plans[key] = self.stages
        self.covered = frozenset(names)
        self.d_ff_total = e * dff

    def __call__(self, buf):
        """buf [E, C, d] dispatched tokens -> [E, C, d] expert outputs."""
        from repro.kernels import layer_plan

        self.executor.routed.update(self.covered)
        e, c, d = buf.shape
        src = buf.astype(jnp.float32).transpose(0, 2, 1).reshape(e * d, c)
        out = layer_plan.moe_plan_matmul(
            self.stages["a"], self.stages["b"], d_ff_total=self.d_ff_total,
            src=src, interpret=self.executor.interpret)
        return out.reshape(e, d, c).transpose(0, 2, 1).astype(buf.dtype)


def matvecs_from_artifact(artifact, *, include=None, block: int = 128,
                          interpret: bool | None = None) -> dict[str, LCCMatvec]:
    """Per-site :class:`LCCMatvec` table for an artifact's dense records.

    The one place the (name -> record, ``packed=`` lookup) wiring lives —
    both :class:`CompressedExecutor` and the legacy
    ``compress_ffn_for_serving`` build their tables through it.  ``include``
    filters site names (callable or prefix string).
    """
    from repro.core.compress import CompressedDense

    keep = (include if callable(include)
            else (lambda n: n.startswith(include)) if include is not None
            else (lambda n: True))
    return {name: LCCMatvec(rec, packed=artifact.packed.get(name),
                            block=block, interpret=interpret)
            for name, rec in artifact.records.items()
            if isinstance(rec, CompressedDense) and keep(name)}


def _plan_ineligible_reason(cfg, has_sites: bool) -> str | None:
    """Why ``cfg`` cannot take the whole-step plan route (None = eligible).

    The reason strings feed ``serving_plan_fallbacks_total{reason}`` and
    ``Engine.plan_stats()``, so a bench row can explain a missing plan.
    """
    if getattr(cfg, "mla", None) is not None:
        return "mla"
    if getattr(cfg, "n_dense_layers", 0):
        return "leading_dense"  # the plan stacks L identical layers
    family = getattr(cfg, "family", "")
    if family in ("ssm", "hybrid"):
        return f"family:{family}"
    if getattr(cfg, "enc_layers", 0) != 0:
        return "encoder_decoder"
    pos = getattr(cfg, "pos", "rope")
    if pos not in ("rope", "none"):
        return f"pos:{pos}"
    norm = getattr(cfg, "norm", "rms")
    if norm not in ("rms", "nonparam"):
        return f"norm:{norm}"
    if jnp.zeros((), cfg.cdtype).dtype != jnp.float32:
        return "cdtype"
    moe = getattr(cfg, "moe", None)
    if moe is not None:
        if getattr(cfg, "moe_manual", False):
            return "moe_manual"  # manual EP shards experts across devices
        if getattr(moe, "n_shared", 0) > 0:
            return "moe_shared"  # shared experts keep their own site route
    if not has_sites:
        return "no_sites"
    return None


class CompressedExecutor:
    """Site-keyed registry mapping every compressed site of an artifact to a
    fused-kernel callable.

    Protocol consumed by the model decode paths (duck-typed — models never
    import serving):

    * ``matvec(name)``   -> features-major callable ``[K, B] -> [N, B]`` or
      None when the site is not compressed (dense fallback).
    * ``grouped(names)`` -> one-launch callable over a *fused region* (list of
      per-site ``[K_g, B]`` inputs -> list of ``[N_g, B]`` outputs), or None
      unless every name is a compressed dense site.
    * ``conv(name)``     -> :class:`ConvLCC` or None.

    ``routed`` records (at trace time) every site actually served by a fused
    kernel — tests assert it covers the artifact, and the engine reports it.

    Layer plans (``use_plans=True``, the default): on the interpreter path
    the executor additionally builds *layer plans* — ``step_plan(cfg)``
    collapses a whole dense-family decode step into one launch,
    ``moe_plan(...)`` collapses an MoE layer's expert FFNs — and the models
    consult them before falling back to the per-region grouped route.
    Compiled TPU keeps the per-region kernels (the plan kernels are
    gather/scatter-shaped, which Mosaic does not support in-kernel), so
    ``use_plans`` is ANDed with ``resolve_interpret``.
    """

    def __init__(self, artifact, *, block: int = 128,
                 interpret: bool | None = None, use_plans: bool = True,
                 mesh=None):
        from repro.kernels.dispatch import resolve_interpret

        self.artifact = artifact
        self.block = block
        self.interpret = interpret
        # device mesh for plan shard_map (serving engines pass theirs; None
        # falls back to the act_shard context, e.g. under launch/train)
        self.mesh = mesh
        self.use_plans = bool(use_plans) and resolve_interpret(interpret)
        # plan key ("step" / "moe:<tag>") -> why it fell back to the
        # per-region route; the engine publishes these as
        # serving_plan_fallbacks_total{reason} and plan_stats() reports them
        self.plan_fallbacks: dict[str, str] = {}
        self._disabled_reason = (
            None if self.use_plans
            else ("plans_disabled" if not use_plans else "not_interpret"))
        self._plans: dict[str, object] = {}
        self._matvecs = matvecs_from_artifact(artifact, block=block,
                                              interpret=interpret)
        # record ineligibility eagerly so engines over families whose decode
        # path never consults step_plan() (ssm/hybrid/...) still surface a
        # reason in plan_stats() / serving_plan_fallbacks_total
        if self.use_plans and hasattr(artifact.config, "family"):
            reason = _plan_ineligible_reason(artifact.config,
                                             bool(self._matvecs))
            if reason is not None:
                self.plan_fallbacks.setdefault("step", reason)
        self._convs: dict[str, ConvLCC] = {}
        self._groups: dict[tuple, GroupedLCCMatvec | None] = {}
        self.routed: set[str] = set()
        conv_names = [n for n, r in artifact.records.items()
                      if not hasattr(r, "decomposition")]
        if conv_names:
            from repro.models import compress_adapters as ca

            kernels = {s.name: s.kernel(artifact.params)
                       for s in ca.sites_for(artifact.params, artifact.config)
                       if isinstance(s, ca.ConvSite)}
            for name in conv_names:
                cv = ConvLCC(
                    name, kernels[name], artifact.records[name],
                    artifact.unit_config_for(name).conv_method,
                    block=block, interpret=interpret)
                self._convs[name] = cv
                if cv.group is not None and cv.group.waste is not None:
                    artifact.pipeline_stats.setdefault(
                        "padding_waste", {})[name] = cv.group.waste

    @property
    def sites(self) -> set[str]:
        """Every site this executor can serve through a fused kernel."""
        return set(self._matvecs) | set(self._convs)

    def __contains__(self, name: str) -> bool:
        return name in self._matvecs or name in self._convs

    def matvec(self, name: str):
        fn = self._matvecs.get(name)
        if fn is not None:
            self.routed.add(name)
        return fn

    def grouped(self, names):
        names = tuple(names)
        if names not in self._groups:
            if all(n in self._matvecs for n in names) and names:
                recs = [self.artifact.records[n] for n in names]
                # reuse the eagerly-packed per-site buffers: group assembly
                # happens at trace time and must only touch concrete arrays
                packed = [self._matvecs[n].packed for n in names]
                g = GroupedLCCMatvec(recs, packed=packed, block=self.block,
                                     interpret=self.interpret)
                self._groups[names] = g
                if g.group.waste is not None:
                    self.artifact.pipeline_stats.setdefault(
                        "padding_waste", {})["+".join(names)] = g.group.waste
            else:
                self._groups[names] = None
        g = self._groups[names]
        if g is not None:
            self.routed.update(names)
        return g

    def conv(self, name: str):
        fn = self._convs.get(name)
        if fn is not None:
            self.routed.add(name)
        return fn

    # -- layer plans --------------------------------------------------------

    def step_plan(self, cfg):
        """Whole-decode-step plan for the transformer families, or None.

        Built once per executor and cached; eligibility is conservative —
        anything the step kernel does not model (MLA/ssm/hybrid layers,
        manual-EP or shared-expert MoE, encoder-decoder, learned positions,
        non-f32 compute dtype, compiled TPU backend) falls back to the
        per-region grouped route, which covers every family.  Every fallback
        records its reason in :attr:`plan_fallbacks`; an eligible plan whose
        build fails raises.
        """
        if not self.use_plans:
            self.plan_fallbacks.setdefault("step", self._disabled_reason)
            return None
        if "step" not in self._plans:
            reason = _plan_ineligible_reason(cfg, bool(self._matvecs))
            plan = None
            if reason is None:
                plan = StepPlan(self, cfg)
            else:
                self.plan_fallbacks["step"] = reason
            self._plans["step"] = plan
        plan = self._plans["step"]
        if plan is not None:
            self.routed.update(plan.covered)
        return plan

    def moe_plan(self, site_tag: str, *, n_experts: int, d_model: int,
                 d_ff: int):
        """Single-launch plan for one MoE layer's expert FFNs, or None."""
        key = f"moe:{site_tag}"
        if not self.use_plans:
            self.plan_fallbacks.setdefault(key, self._disabled_reason)
            return None
        if key not in self._plans:
            names = [f"moe.{p}.{site_tag}.e{e}" for e in range(n_experts)
                     for p in ("gate", "up", "down")]
            plan, reason = None, None
            if not all(n in self._matvecs for n in names):
                reason = "moe_sites_missing"
            elif jnp.zeros((), self.artifact.config.cdtype).dtype \
                    != jnp.float32:
                reason = "cdtype"
            else:
                plan = MoEPlan(self, site_tag, n_experts=n_experts,
                               d_model=d_model, d_ff=d_ff)
            if reason is not None:
                self.plan_fallbacks[key] = reason
            self._plans[key] = plan
        plan = self._plans[key]
        if plan is not None:
            self.routed.update(plan.covered)
        return plan

    @property
    def n_layer_plans(self) -> int:
        """Distinct layer plans built (a whole-step plan counts once)."""
        return sum(1 for p in self._plans.values() if p is not None)
