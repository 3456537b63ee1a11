"""Architecture + shape-cell configuration schema.

One ``ArchConfig`` per assigned architecture (exact published numbers, see the
per-arch modules).  Shape cells (train_4k / prefill_32k / decode_32k /
long_500k) are defined here once; ``input_specs`` builds ShapeDtypeStruct
stand-ins — no device allocation, the dry-run contract.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp

__all__ = ["MoESpec", "MLASpec", "YarnSpec", "SSMSpec", "ArchConfig", "ShapeCell", "SHAPE_CELLS",
           "input_specs", "reduced_config", "arch_to_dict", "arch_from_dict"]


@dataclass(frozen=True)
class MoESpec:
    """Routed experts.  Routing is drop-free: every token reaches each of its
    ``top_k`` experts, whatever else is in the batch.  ``norm_topk``
    renormalises the selected gates to sum to one (Mixtral); DeepSeek-V2
    keeps the raw softmax weights."""
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    norm_topk: bool = True


@dataclass(frozen=True)
class MLASpec:
    kv_lora: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_dim: int = 128


@dataclass(frozen=True)
class YarnSpec:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 configures it:
    ``rope_scaling`` ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMSpec:
    d_inner: int
    d_state: int
    head_dim: int = 64
    d_conv: int = 4


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    qkv_bias: bool = False
    norm: str = "rms"  # rms | nonparam | ln
    pos: str = "rope"  # rope | mrope | learned | none
    rope_theta: float = 10000.0
    yarn: YarnSpec | None = None
    attn_window: int | None = None
    tie_embeddings: bool = False
    moe: MoESpec | None = None
    # leading layers with a dense SwiGLU of width ``d_ff`` before the MoE
    # stack (DeepSeek-V2's ``first_k_dense_replace``); experts use
    # ``moe.d_ff_expert``
    n_dense_layers: int = 0
    mla: MLASpec | None = None
    ssm: SSMSpec | None = None
    hybrid_period: int = 6
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    enc_layers: int = 0  # > 0 => encoder-decoder (whisper)
    max_decoder_len: int = 448
    inputs: str = "tokens"  # tokens | embeds (stubbed modality frontend)
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    q_chunk: int = 1024
    ssm_chunk: int = 256
    remat: bool = True
    causal_chunk_skip: bool = False  # static upper-triangle skip (§Perf lever)
    moe_manual: bool = False  # shard_map MoE dispatch (§Perf lever)
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def sub_quadratic(self) -> bool:
        """Can this arch run the 500k-token cell with bounded state?"""
        return self.family in ("ssm", "hybrid") or self.attn_window is not None


@dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPE_CELLS: dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def cell_supported(cfg: ArchConfig, cell: ShapeCell) -> tuple[bool, str]:
    """(supported, reason-if-not). Mirrors DESIGN.md 'Shape-cell skips'."""
    if cell.name == "long_500k" and not cfg.sub_quadratic():
        return False, "full softmax attention: 500k decode needs sub-quadratic attention"
    if cell.name == "long_500k" and cfg.enc_layers > 0:
        return False, "encoder-decoder: 500k positions out of decoder design range"
    return True, ""


def input_specs(cfg: ArchConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this (arch, cell)."""
    s, b = cell.seq_len, cell.global_batch
    i32 = jnp.int32
    cd = cfg.cdtype
    if cell.kind == "train":
        if cfg.enc_layers > 0:  # whisper: stub frame embeddings + decoder tokens
            return {
                "frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cd),
                "tokens": jax.ShapeDtypeStruct((b, cfg.max_decoder_len), i32),
                "labels": jax.ShapeDtypeStruct((b, cfg.max_decoder_len), i32),
            }
        if cfg.inputs == "embeds":  # vlm: stub patch/token embeddings
            d = {
                "embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), cd),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            }
            if cfg.pos == "mrope":
                d["positions3"] = jax.ShapeDtypeStruct((3, b, s), i32)
            return d
        return {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
    if cell.kind == "prefill":
        if cfg.enc_layers > 0:
            return {"frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cd)}
        if cfg.inputs == "embeds":
            d = {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), cd)}
            if cfg.pos == "mrope":
                d["positions3"] = jax.ShapeDtypeStruct((3, b, s), i32)
            return d
        return {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
    # decode: one new token against a seq_len-deep cache/state
    d = {"token": jax.ShapeDtypeStruct((b, 1), i32),
         "pos": jax.ShapeDtypeStruct((b,), i32)}
    return d


def arch_to_dict(cfg: ArchConfig) -> dict:
    """JSON-serializable form of an ArchConfig (inverse: ``arch_from_dict``).

    Used by the compressed-model artifact so an offline compression run can be
    served later without access to the config object that produced it."""
    from dataclasses import asdict

    d = asdict(cfg)
    d["mrope_sections"] = list(d["mrope_sections"])
    return d


def arch_from_dict(d: dict) -> ArchConfig:
    d = dict(d)
    if d.get("moe") is not None:
        d["moe"] = MoESpec(**d["moe"])
    if d.get("mla") is not None:
        d["mla"] = MLASpec(**d["mla"])
    if d.get("yarn") is not None:
        d["yarn"] = YarnSpec(**d["yarn"])
    if d.get("ssm") is not None:
        d["ssm"] = SSMSpec(**d["ssm"])
    d["mrope_sections"] = tuple(d["mrope_sections"])
    return ArchConfig(**d)


def reduced_config(cfg: ArchConfig, **overrides) -> ArchConfig:
    """Tiny same-family config for CPU smoke tests."""
    small = dict(
        n_layers=min(cfg.n_layers, 2 * max(1, cfg.hybrid_period // 3)) if cfg.family == "hybrid"
        else min(cfg.n_layers, 2),
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        d_ff=256,
        vocab=512,
        head_dim=32,
        q_chunk=64,
        ssm_chunk=32,
        enc_layers=2 if cfg.enc_layers > 0 else 0,
        max_decoder_len=32,
        param_dtype="float32",
        compute_dtype="float32",
    )
    if cfg.moe is not None:
        small["moe"] = MoESpec(n_experts=4, top_k=2, d_ff_expert=64,
                               n_shared=min(cfg.moe.n_shared, 1),
                               norm_topk=cfg.moe.norm_topk)
        small["n_dense_layers"] = min(cfg.n_dense_layers, 1)
    if cfg.mla is not None:
        small["mla"] = MLASpec(kv_lora=32, qk_nope=32, qk_rope=16, v_dim=32)
    if cfg.ssm is not None:
        small["ssm"] = SSMSpec(d_inner=256, d_state=16, head_dim=32, d_conv=4)
    if cfg.family == "hybrid":
        small["hybrid_period"] = 2
        small["n_layers"] = 4
    if cfg.pos == "mrope":
        half = small.get("head_dim", cfg.hd) // 2
        t = max(1, half // 4)
        small["mrope_sections"] = (t, (half - t) // 2, half - t - (half - t) // 2)
    small.update(overrides)
    return replace(cfg, **small)
