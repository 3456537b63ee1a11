"""Per-family compressible-unit adapters for the Algorithm-1 pipeline.

Every architecture family registers two things with ``models.api``:

* ``sites(params, cfg)`` — the list of :class:`DenseSite` / :class:`ConvSite`
  records naming each compressible matrix (or conv kernel), where it lives in
  the params pytree, and how it is stored (stacked layer/expert axes, the
  ``dense_init`` [K, N] layout vs the paper's [N, K] ``y = W x`` layout).
* a generic ``rebind`` built on those same sites: write a compressed unit's
  dense-effective map back into a (functionally updated) params pytree, so the
  stock XLA forward serves the compressed model with zero code changes.

Coverage per family (the hard-coded FFN walk this replaces handled only the
dense-transformer FFN):

====================  =====================================================
dense / vlm           FFN gate/up/down + attention q/k/v/o (or MLA projs)
moe                   per-expert gate/up/down, shared experts, attention
ssm (rwkv6)           channel-mix k/v/r + time-mix r/k/v/g/o
hybrid (zamba2)       mamba in/out projections + the weight-shared
                      attention+MLP block
audio (whisper)       encoder & decoder MLP fc1/fc2 + self/cross attention
resnet                every conv kernel (FK/PK reshaping) + the linear head
====================  =====================================================

Sites are deterministic functions of (params, cfg): ``rebind`` re-derives them
by name, so unit names double as stable artifact keys.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.compress import CompressibleConv, CompressibleDense
from repro.core.conv_reshape import conv_fk_matrices, conv_pk_matrices

__all__ = ["DenseSite", "ConvSite", "sites_for", "units_from_sites",
           "rebind_site", "rebind_site_traced", "effective_conv_kernel",
           "FAMILY_SITE_FNS"]


@dataclass(frozen=True)
class DenseSite:
    """One dense matrix: ``params[path...][index...]`` viewed as y = W x."""

    name: str
    path: tuple  # keys into the params pytree down to the array
    index: tuple = ()  # leading indices into stacked axes (layer, expert, ...)
    transpose: bool = True  # True: stored [K, N] (dense_init layout)

    def weight(self, params) -> np.ndarray:
        a = _lookup(params, self.path)
        for i in self.index:
            a = a[i]
        w = np.asarray(a, np.float64)
        return w.T if self.transpose else w


@dataclass(frozen=True)
class ConvSite:
    """One conv kernel [N, K, O, O] (NCHW/OIHW models)."""

    name: str
    path: tuple
    index: tuple = ()

    def kernel(self, params) -> np.ndarray:
        a = _lookup(params, self.path)
        for i in self.index:
            a = a[i]
        return np.asarray(a, np.float64)


def _lookup(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set_in(tree, path, value):
    """Functional nested update; dict levels are copied, list levels rebuilt."""
    if not path:
        return value
    k, rest = path[0], path[1:]
    if isinstance(tree, list):
        out = list(tree)
        out[k] = _set_in(tree[k], rest, value)
        return out
    out = dict(tree)
    out[k] = _set_in(tree[k], rest, value)
    return out


def rebind_site(params, site: DenseSite | ConvSite, effective: np.ndarray):
    """Write a dense-effective weight (or conv kernel) back at ``site``.

    ``effective`` is [N, K_orig] for dense sites (pruned columns already
    zero-expanded) and [N, K, O, O] for conv sites.  Returns a new params
    pytree; the original is untouched.
    """
    arr = _lookup(params, site.path)
    new = np.asarray(effective)
    if isinstance(site, DenseSite) and site.transpose:
        new = new.T
    leaf = jnp.asarray(new, jnp.asarray(arr).dtype)
    if site.index:
        idx = site.index if len(site.index) > 1 else site.index[0]
        leaf = jnp.asarray(arr).at[idx].set(leaf)
    return _set_in(params, site.path, leaf)


def rebind_site_traced(params, site: DenseSite | ConvSite, effective):
    """jit-traceable :func:`rebind_site`: same semantics, but ``effective`` may
    be a traced jnp array (no host round-trip), so recovery fine-tuning can
    rebuild the loss through the rebind and differentiate w.r.t. the
    compressed parameterization."""
    arr = _lookup(params, site.path)
    new = effective
    if isinstance(site, DenseSite) and site.transpose:
        new = jnp.swapaxes(new, -1, -2)
    leaf = new.astype(arr.dtype)
    if site.index:
        idx = site.index if len(site.index) > 1 else site.index[0]
        leaf = arr.at[idx].set(leaf)
    return _set_in(params, site.path, leaf)


def units_from_sites(params, sites) -> list[CompressibleDense | CompressibleConv]:
    out: list[CompressibleDense | CompressibleConv] = []
    for s in sites:
        if isinstance(s, DenseSite):
            out.append(CompressibleDense(name=s.name, weight=s.weight(params)))
        else:
            out.append(CompressibleConv(name=s.name, kernel=s.kernel(params)))
    return out


def effective_conv_kernel(kernel: np.ndarray, conv_record: dict,
                          method: str = "pk") -> np.ndarray:
    """Dense-equivalent kernel of a ``compress_conv_kernel`` record.

    Channels with a decomposition are replaced by the decomposition's dense
    equivalent (inverting the FK/PK reshape); subsampled or pruned-out
    channels keep their original values — the accounting already covers them.
    """
    n, k, oh, ow = kernel.shape
    eff = np.array(kernel, np.float64, copy=True)
    for ch, dec in conv_record["decompositions"].items():
        m = dec.to_dense()
        if method == "fk":
            eff[:, ch] = m.reshape(n, oh, ow)
        else:  # pk rows are (n, j): kernel columns of length oh
            eff[:, ch] = m.reshape(n, ow, oh).transpose(0, 2, 1)
    return eff


# ---------------------------------------------------------------------------
# per-family site enumerations
# ---------------------------------------------------------------------------


def _attn_sites(cfg, base_path, layer_index, tag) -> list[DenseSite]:
    projs = ("q", "dkv", "kr", "uk", "uv", "o") if cfg.mla is not None \
        else ("q", "k", "v", "o")
    return [DenseSite(name=f"{tag}.{p}.l{layer_index[-1]}" if layer_index
                      else f"{tag}.{p}",
                      path=base_path + (p, "w"), index=layer_index)
            for p in projs]


def _ffn_sites(layer_index, tag="ffn", projs=("gate", "up", "down"),
               base=("blocks", "ffn")) -> list[DenseSite]:
    li = layer_index[-1] if layer_index else None
    return [DenseSite(name=f"{tag}.{p}.l{li}" if layer_index else f"{tag}.{p}",
                      path=base + (p, "w"), index=layer_index)
            for p in projs]


def _dense_sites(params, cfg) -> list[DenseSite]:
    sites: list[DenseSite] = []
    for li in range(cfg.n_layers):
        sites += _ffn_sites((li,))
        sites += _attn_sites(cfg, ("blocks", "attn"), (li,), "attn")
    return sites


def _moe_sites(params, cfg) -> list[DenseSite]:
    """Leading dense blocks (``dense_blocks``, layers 0..nd-1) as dense
    layers; MoE block j is layer nd + j in every site name."""
    nd = cfg.n_dense_layers
    sites: list[DenseSite] = []
    for li in range(nd):
        sites += _ffn_sites((li,), base=("dense_blocks", "ffn"))
        sites += _attn_sites(cfg, ("dense_blocks", "attn"), (li,), "attn")
    ffn = params["blocks"]["ffn"]
    for li in range(nd, cfg.n_layers):
        j = li - nd
        for p in ("gate", "up", "down"):
            for e in range(cfg.moe.n_experts):
                # expert stacks are raw [L, E, in, out] arrays (no "w" level)
                sites.append(DenseSite(name=f"moe.{p}.l{li}.e{e}",
                                       path=("blocks", "ffn", p),
                                       index=(j, e)))
        if "shared" in ffn:
            sites += [DenseSite(name=f"moe.shared.{p}.l{li}",
                                path=("blocks", "ffn", "shared", p, "w"),
                                index=(j,))
                      for p in ("gate", "up", "down")]
        sites += [DenseSite(name=f"attn.{s.name.split('.')[1]}.l{li}",
                            path=s.path, index=s.index)
                  for s in _attn_sites(cfg, ("blocks", "attn"), (j,), "attn")]
    return sites


def _ssm_sites(params, cfg) -> list[DenseSite]:
    sites: list[DenseSite] = []
    for li in range(cfg.n_layers):
        for p in ("r", "k", "v", "g", "o"):
            sites.append(DenseSite(name=f"tm.{p}.l{li}",
                                   path=("blocks", "tm", p, "w"), index=(li,)))
        for p in ("k", "v", "r"):
            sites.append(DenseSite(name=f"cm.{p}.l{li}",
                                   path=("blocks", "cm", p, "w"), index=(li,)))
    return sites


def _hybrid_sites(params, cfg) -> list[DenseSite]:
    sites: list[DenseSite] = []
    for li in range(cfg.n_layers):
        for p in ("in_proj", "out_proj"):
            sites.append(DenseSite(name=f"mamba.{p}.l{li}",
                                   path=("blocks", "mamba", p, "w"), index=(li,)))
    # the one weight-shared attention+MLP block (unstacked)
    sites += _ffn_sites((), tag="shared_attn.ffn", base=("shared_attn", "ffn"))
    sites += _attn_sites(cfg, ("shared_attn", "attn"), (), "shared_attn.attn")
    return sites


def _audio_sites(params, cfg) -> list[DenseSite]:
    sites: list[DenseSite] = []
    for li in range(cfg.enc_layers):
        sites += _ffn_sites((li,), tag="enc.mlp", projs=("fc1", "fc2"),
                            base=("enc_blocks", "mlp"))
        sites += _attn_sites(cfg, ("enc_blocks", "attn"), (li,), "enc.attn")
    for li in range(cfg.n_layers):
        sites += _ffn_sites((li,), tag="dec.mlp", projs=("fc1", "fc2"),
                            base=("dec_blocks", "mlp"))
        sites += _attn_sites(cfg, ("dec_blocks", "attn"), (li,), "dec.attn")
        sites += _attn_sites(cfg, ("dec_blocks", "xattn"), (li,), "dec.xattn")
    return sites


def _mlp_sites(params, cfg) -> list[DenseSite]:
    # weights are stored [N, K] acting as y = W x (the paper layout): no
    # transpose.  fc1 is the paper's compression target (Sec. IV-A); fc2 is
    # listed too and filtered via ``include=`` when only fc1 is wanted.
    return [DenseSite(name="fc1", path=("fc1", "w"), transpose=False),
            DenseSite(name="fc2", path=("fc2", "w"), transpose=False)]


def _resnet_sites(params, cfg) -> list[DenseSite | ConvSite]:
    sites: list[DenseSite | ConvSite] = [ConvSite(name="stem", path=("stem",))]
    for i, blk in enumerate(params["blocks"]):
        sites.append(ConvSite(name=f"block{i}.conv1", path=("blocks", i, "conv1")))
        sites.append(ConvSite(name=f"block{i}.conv2", path=("blocks", i, "conv2")))
        if "proj" in blk:
            sites.append(ConvSite(name=f"block{i}.proj", path=("blocks", i, "proj")))
    sites.append(DenseSite(name="head", path=("head", "w"), transpose=False))
    return sites


FAMILY_SITE_FNS = {
    "dense": _dense_sites,
    "vlm": _dense_sites,
    "moe": _moe_sites,
    "ssm": _ssm_sites,
    "hybrid": _hybrid_sites,
    "audio": _audio_sites,
    "resnet": _resnet_sites,
    "mlp": _mlp_sites,
}


def sites_for(params, cfg) -> list[DenseSite | ConvSite]:
    """All compressible sites of (params, cfg); keyed off the family registry."""
    from . import api  # late: api imports this module for registration

    family = api.family_of(cfg)
    try:
        fn = FAMILY_SITE_FNS[family]
    except KeyError:
        raise KeyError(
            f"no compression adapter registered for family {family!r}; "
            f"known: {sorted(FAMILY_SITE_FNS)}") from None
    return fn(params, cfg)


def register_family(family: str, site_fn) -> None:
    """Extension hook: plug a new architecture family into the registry."""
    FAMILY_SITE_FNS[family] = site_fn
