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
           "matvecs_from_artifact"]


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

    Every backend takes the same route: the model decode paths ask for one
    launch per fused region (a layer's q/k/v, o, gate/up and down; an MoE
    layer's gate, up and down, each over all its experts), so a dense
    transformer step with every site compressed emits four LCC launches a
    layer.
    """

    def __init__(self, artifact, *, block: int = 128,
                 interpret: bool | None = None):
        self.artifact = artifact
        self.block = block
        self.interpret = interpret
        self._matvecs = matvecs_from_artifact(artifact, block=block,
                                              interpret=interpret)
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
