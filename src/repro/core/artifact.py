"""Serializable compressed-model artifact (offline compress once, serve many).

A :class:`CompressedModel` bundles everything the serving engine needs to run
a model compressed by Algorithm 1:

* ``records`` — per-unit :class:`CompressedDense` / conv records (prune
  indices, weight-sharing labels+centroids, the LCC decomposition itself);
* ``packed`` — the fused-kernel buffers (``kernels.ops.PackedDecomposition``)
  ready for ``lcc_chain_matmul`` launches;
* ``params`` — dense-effective weights, a drop-in pytree for the stock XLA
  forward (the non-kernel fallback and everything not compressed);
* ``report`` — the :class:`ModelCostReport` adds/bytes accounting;
* the :class:`CompressionConfig` and the model config that produced it.

Persistence goes through the existing msgpack+crc32 ``Checkpointer``: the
artifact is one array pytree plus a JSON manifest (itself stored as a uint8
leaf), published atomically under ``<dir>/step_<N>/``.  ``load`` walks steps
newest-first and skips corrupted shards with a warning, exactly like training
restore.
"""
from __future__ import annotations

import json
from dataclasses import asdict, dataclass, field
from typing import Any

import jax.numpy as jnp
import numpy as np

from .compress import CompressedDense, CompressionConfig
from .cost import LayerCost, ModelCostReport
from .lcc import FSProgram, LCCChain, LCCDecomposition, LCCFactor
from .weight_sharing import SharedLayer

__all__ = ["CompressedModel"]

_FORMAT_VERSION = 1


# ---------------------------------------------------------------------------
# decomposition <-> (meta, arrays)
# ---------------------------------------------------------------------------


def _dec_to_tree(dec: LCCDecomposition) -> tuple[dict, dict]:
    meta = {
        "shape": list(dec.shape),
        "col_slices": [list(cs) for cs in dec.col_slices],
        "algorithm": dec.algorithm,
        "target_snr_db": dec.target_snr_db,
        "meta": {k: v for k, v in dec.meta.items()
                 if isinstance(v, (int, float, str, bool, type(None)))},
        "slices": [],
    }
    arrays: dict[str, Any] = {}
    for i, s in enumerate(dec.slices):
        key = f"s{i:03d}"
        if isinstance(s, LCCChain):
            meta["slices"].append({"kind": "fp", "in_dim": s.in_dim,
                                   "factor_in_dims": [f.in_dim for f in s.factors]})
            arrays[key] = {f"f{j:02d}": {"idx": f.idx, "exp": f.exp, "sign": f.sign}
                           for j, f in enumerate(s.factors)}
        else:
            meta["slices"].append({"kind": "fs", "n_inputs": s.n_inputs})
            arrays[key] = {"nodes": np.asarray(s.nodes, np.int64).reshape(-1, 6),
                           "outputs": np.asarray(s.outputs, np.int64)}
    return meta, arrays


def _dec_from_tree(meta: dict, arrays: dict) -> LCCDecomposition:
    slices: list[LCCChain | FSProgram] = []
    for i, sm in enumerate(meta["slices"]):
        tree = arrays.get(f"s{i:03d}", {})
        if sm["kind"] == "fp":
            factors = [LCCFactor(idx=np.asarray(tree[k]["idx"], np.int32),
                                 exp=np.asarray(tree[k]["exp"], np.int8),
                                 sign=np.asarray(tree[k]["sign"], np.int8),
                                 in_dim=int(sm["factor_in_dims"][j]))
                       for j, k in enumerate(sorted(tree))]
            slices.append(LCCChain(factors=factors, in_dim=int(sm["in_dim"])))
        else:
            slices.append(FSProgram(n_inputs=int(sm["n_inputs"]),
                                    nodes=np.asarray(tree["nodes"], np.int64).reshape(-1, 6),
                                    outputs=np.asarray(tree["outputs"], np.int64)))
    dec = LCCDecomposition(
        shape=tuple(meta["shape"]),
        col_slices=[tuple(cs) for cs in meta["col_slices"]],
        slices=slices,
        algorithm=meta["algorithm"],
        target_snr_db=float(meta["target_snr_db"]),
    )
    dec.meta.update(meta.get("meta", {}))
    return dec


# ---------------------------------------------------------------------------
# flat-name pytree reconstruction ("blocks/0/conv1" -> list index 0)
# ---------------------------------------------------------------------------


def _unflatten(flat: dict[str, Any]):
    root: dict = {}
    for name, leaf in flat.items():
        parts = name.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf

    def listify(node):
        if not isinstance(node, dict):
            return node
        out = {k: listify(v) for k, v in node.items()}
        if out and all(k.isdigit() for k in out):
            return [out[k] for k in sorted(out, key=int)]
        return out

    return listify(root)


def _report_to_json(report: ModelCostReport) -> list[dict]:
    return [{"name": l.name, "baseline_adds": l.baseline_adds,
             "stage_adds": l.stage_adds, "stage_bytes": l.stage_bytes,
             "extra": {k: v for k, v in l.extra.items()
                       if isinstance(v, (int, float, str, bool, type(None)))}}
            for l in report.layers]


def _report_from_json(rows: list[dict]) -> ModelCostReport:
    rep = ModelCostReport()
    for r in rows:
        lc = LayerCost(name=r["name"], baseline_adds=int(r["baseline_adds"]))
        lc.stage_adds.update({k: int(v) for k, v in r["stage_adds"].items()})
        lc.stage_bytes.update({k: int(v) for k, v in r["stage_bytes"].items()})
        lc.extra.update(r["extra"])
        rep.add(lc)
    return rep


def _config_to_manifest(cfg) -> tuple[str, dict]:
    from repro.configs.base import ArchConfig, arch_to_dict

    if isinstance(cfg, ArchConfig):
        return "arch", arch_to_dict(cfg)
    return type(cfg).__name__, asdict(cfg)


def _config_from_manifest(kind: str, d: dict):
    from repro.configs.base import arch_from_dict

    if kind == "arch":
        return arch_from_dict(d)
    if kind == "ResNetConfig":
        from repro.models.resnet import ResNetConfig

        d = dict(d)
        d["stages"] = tuple(d["stages"])
        d["widths"] = tuple(d["widths"])
        return ResNetConfig(**d)
    if kind == "MLPConfig":
        from repro.models.mlp import MLPConfig

        return MLPConfig(**d)
    raise ValueError(f"unknown config kind {kind!r} in artifact manifest")


# ---------------------------------------------------------------------------
# the artifact
# ---------------------------------------------------------------------------


@dataclass
class CompressedModel:
    config: Any  # ArchConfig | ResNetConfig | MLPConfig
    params: Any  # dense-effective pytree
    records: dict[str, Any]  # unit name -> CompressedDense | conv dict
    packed: dict[str, Any] = field(default_factory=dict)  # name -> PackedDecomposition
    report: ModelCostReport = field(default_factory=ModelCostReport)
    compression: CompressionConfig = field(default_factory=CompressionConfig)
    # per-unit plans (adds-budget allocator output); empty => ``compression``
    # applied globally.  ``unit_config_for`` is the read surface.
    unit_configs: dict[str, CompressionConfig] = field(default_factory=dict)
    pipeline_stats: dict = field(default_factory=dict)  # workers/cache/wall

    def unit_config_for(self, name: str) -> CompressionConfig:
        return self.unit_configs.get(name, self.compression)

    @property
    def family(self) -> str:
        from repro.models import api

        return api.family_of(self.config)

    def dense_unit_names(self) -> list[str]:
        return [n for n, r in self.records.items()
                if isinstance(r, CompressedDense)]

    # ------------------------------------------------------------------ save
    def save(self, directory: str, step: int = 0) -> None:
        from repro.checkpoint.checkpointer import Checkpointer

        units_tree: dict[str, Any] = {}
        conv_tree: dict[str, Any] = {}
        packed_tree: dict[str, Any] = {}
        man_units: dict[str, Any] = {}
        for name, rec in self.records.items():
            if isinstance(rec, CompressedDense):
                dm, da = _dec_to_tree(rec.decomposition)
                t = {"kept": np.asarray(rec.kept_columns, np.int64),
                     "effective": np.asarray(rec.effective, np.float64),
                     "dec": da}
                if rec.shared is not None:
                    t["labels"] = np.asarray(rec.shared.labels)
                    t["centroids"] = np.asarray(rec.shared.centroids, np.float64)
                units_tree[name] = t
                man_units[name] = {"type": "dense", "dec": dm,
                                   "has_shared": rec.shared is not None}
            else:
                chans = {}
                decs_meta = {}
                for ch, dec in rec["decompositions"].items():
                    dm, da = _dec_to_tree(dec)
                    chans[f"ch{ch:04d}"] = da
                    decs_meta[str(ch)] = dm
                conv_tree[name] = chans
                man_units[name] = {
                    "type": "conv", "decs": decs_meta,
                    "channels_nonzero": [int(c) for c in rec["channels_nonzero"]],
                    "baseline_adds": int(rec["baseline_adds"]),
                    "lcc_adds": int(rec["lcc_adds"]),
                    "scale": float(rec["scale"]),
                }
        man_packed: dict[str, Any] = {}
        for name, pk in self.packed.items():
            packed_tree[name] = {
                "idx": np.asarray(pk.idx), "exp": np.asarray(pk.exp),
                "sign": np.asarray(pk.sign),
                "dense": {f"d{i:02d}": np.asarray(w)
                          for i, ((_, _), w) in enumerate(pk.dense)},
            }
            man_packed[name] = {
                "col_slices": [list(cs) for cs in pk.col_slices],
                "dense_slices": [list(cs) for cs, _ in pk.dense],
                "in_dim": pk.in_dim, "out_dim": pk.out_dim, "d_pad": pk.d_pad,
                "first_width": pk.first_width,
                "chain_lengths": list(pk.chain_lengths),
            }
        kind, cfg_dict = _config_to_manifest(self.config)
        manifest = {
            "version": _FORMAT_VERSION,
            "kind": kind,
            "config": cfg_dict,
            "compression": asdict(self.compression),
            "unit_configs": {n: asdict(c) for n, c in self.unit_configs.items()},
            "pipeline_stats": self.pipeline_stats,
            "report": _report_to_json(self.report),
            "units": man_units,
            "packed": man_packed,
        }
        tree = {"manifest": np.frombuffer(
                    json.dumps(manifest).encode(), np.uint8).copy(),
                "params": self.params}
        if units_tree:
            tree["units"] = units_tree
        if conv_tree:
            tree["conv"] = conv_tree
        if packed_tree:
            tree["packed"] = packed_tree
        Checkpointer(directory).save(step, tree, blocking=True)

    # ------------------------------------------------------------------ load
    @classmethod
    def load(cls, directory: str) -> "CompressedModel":
        from repro.checkpoint.checkpointer import Checkpointer

        ckpt = Checkpointer(directory)
        steps = ckpt.all_steps()
        for step in reversed(steps):
            try:
                flat = ckpt.restore_flat(step)
            except Exception as e:  # corrupted shard: fall back to older step
                print(f"[artifact] step {step} unreadable ({e}); trying older")
                continue
            return cls._from_flat(flat)
        raise FileNotFoundError(
            f"no intact compressed-model artifact under {directory!r}")

    @classmethod
    def _from_flat(cls, flat: dict[str, Any]) -> "CompressedModel":
        from repro.kernels.ops import PackedDecomposition

        tree = _unflatten(flat)
        manifest = json.loads(np.asarray(tree.pop("manifest"),
                                         np.uint8).tobytes().decode())
        if manifest["version"] != _FORMAT_VERSION:
            raise ValueError(f"artifact format v{manifest['version']} "
                             f"!= supported v{_FORMAT_VERSION}")
        config = _config_from_manifest(manifest["kind"], manifest["config"])
        records: dict[str, Any] = {}
        for name, um in manifest["units"].items():
            if um["type"] == "dense":
                t = tree["units"][name]
                shared = None
                if um["has_shared"]:
                    shared = SharedLayer(centroids=np.asarray(t["centroids"]),
                                         labels=np.asarray(t["labels"]))
                records[name] = CompressedDense(
                    name=name,
                    kept_columns=np.asarray(t["kept"], np.int64),
                    shared=shared,
                    decomposition=_dec_from_tree(um["dec"], t.get("dec", {})),
                    effective=np.asarray(t["effective"], np.float64),
                )
            else:
                chans = tree.get("conv", {}).get(name, {})
                decs = {int(ch): _dec_from_tree(dm, chans.get(f"ch{int(ch):04d}", {}))
                        for ch, dm in um["decs"].items()}
                records[name] = {
                    "decompositions": decs,
                    "channels_nonzero": list(um["channels_nonzero"]),
                    "baseline_adds": um["baseline_adds"],
                    "lcc_adds": um["lcc_adds"],
                    "scale": um["scale"],
                }
        packed: dict[str, Any] = {}
        for name, pm in manifest.get("packed", {}).items():
            t = tree.get("packed", {}).get(name, {})
            dense_arrs = t.get("dense", {})
            dense = tuple(
                (tuple(cs), jnp.asarray(dense_arrs[f"d{i:02d}"], jnp.float32))
                for i, cs in enumerate(pm["dense_slices"]))
            packed[name] = PackedDecomposition(
                idx=jnp.asarray(t["idx"], jnp.int32),
                exp=jnp.asarray(t["exp"], jnp.int8),
                sign=jnp.asarray(t["sign"], jnp.int8),
                col_slices=tuple(tuple(cs) for cs in pm["col_slices"]),
                dense=dense,
                in_dim=int(pm["in_dim"]), out_dim=int(pm["out_dim"]),
                d_pad=int(pm["d_pad"]), first_width=int(pm["first_width"]),
                chain_lengths=tuple(pm["chain_lengths"]),
            )
        # a "plans" manifest section (and its "plans" arrays), written by
        # older builds, is ignored: the executor packs nothing beyond
        # ``packed``
        comp = CompressionConfig(**manifest["compression"])
        unit_configs = {n: CompressionConfig(**d)
                        for n, d in manifest.get("unit_configs", {}).items()}
        return cls(config=config, params=tree["params"], records=records,
                   packed=packed, report=_report_from_json(manifest["report"]),
                   compression=comp, unit_configs=unit_configs,
                   pipeline_stats=manifest.get("pipeline_stats", {}))
