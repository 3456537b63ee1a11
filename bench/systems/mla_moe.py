"""A DeepSeek-V2-style decoder (multi-head latent attention with YaRN rope, a
leading dense layer, then drop-free mixture-of-experts layers with shared
experts) served from seeded weights: the program's ``ServingEngine`` over the
weights ``bench/references/<reference>.py`` makes in the serving dtype, on
one chip."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from harness import program
from harness.spec import load_module
from harness.traffic import seed_u32

__all__ = ["System", "arch_config", "build"]


def arch_config(c: dict, name: str):
    """The program's ``ArchConfig`` for a configuration file with DeepSeek-V2
    ``config.json`` keys (plus ``serve_dtype``).  Raises on a setting the
    program does not serve as published."""
    program.import_program()
    from repro.configs.base import ArchConfig, MLASpec, MoESpec, YarnSpec

    fixed = {"scoring_func": "softmax", "topk_method": "greedy",
             "routed_scaling_factor": 1, "moe_layer_freq": 1, "n_group": 1,
             "topk_group": 1, "q_lora_rank": None, "hidden_act": "silu",
             "attention_bias": False, "tie_word_embeddings": False}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"{name}: {key}={c[key]!r} is not served "
                             f"(the program implements {want!r})")
    rs = c.get("rope_scaling")
    yarn = None
    if rs is not None:
        if rs.get("type") != "yarn":
            raise ValueError(f"{name}: rope_scaling {rs!r} is not served")
        yarn = YarnSpec(factor=float(rs["factor"]),
                        original_max_position=int(
                            rs["original_max_position_embeddings"]),
                        beta_fast=float(rs["beta_fast"]),
                        beta_slow=float(rs["beta_slow"]),
                        mscale=float(rs["mscale"]),
                        mscale_all_dim=float(rs["mscale_all_dim"]))
    h = int(c["num_attention_heads"])
    return ArchConfig(
        name=name, family="moe",
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), n_heads=h,
        n_kv_heads=int(c.get("num_key_value_heads", h)),
        d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
        head_dim=int(c["v_head_dim"]), norm="rms",
        rope_theta=float(c["rope_theta"]), yarn=yarn,
        mla=MLASpec(kv_lora=int(c["kv_lora_rank"]),
                    qk_nope=int(c["qk_nope_head_dim"]),
                    qk_rope=int(c["qk_rope_head_dim"]),
                    v_dim=int(c["v_head_dim"])),
        moe=MoESpec(n_experts=int(c["n_routed_experts"]),
                    top_k=int(c["num_experts_per_tok"]),
                    d_ff_expert=int(c["moe_intermediate_size"]),
                    n_shared=int(c["n_shared_experts"]),
                    norm_topk=bool(c["norm_topk_prob"])),
        n_dense_layers=int(c["first_k_dense_replace"]),
        tie_embeddings=False,
        param_dtype=c["serve_dtype"], compute_dtype=c["serve_dtype"])


class System:
    """What a cell serves, and what its check needs afterwards."""

    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed = cell, seed
        self.ref = load_module(cell.root, "references",
                               cell.config["reference"])
        self.spec = self.ref.spec_from_config(cell.config)
        self.cfg = arch_config(cell.config, cell.config_name)
        if cell.chips != 1:
            raise ValueError(f"{cell.name}: an mla_moe system serves one chip")
        self.split: dict[str, float] = {}
        self.engine = None

    def weights(self):
        """The seeded weights in the serving dtype, program layout."""
        return self.ref.make_params(self.spec, seed_u32(self.seed, "weights"),
                                    jnp.dtype(self.cell.config["serve_dtype"]))

    def build(self):
        t0 = time.perf_counter()
        params = self.weights()
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        self.engine = program.build_engine(
            params=params, cfg=self.cfg, deployment=self.cell.deployment,
            seed=self.seed)
        self.split.update(weights_s=t1 - t0,
                          engine_s=time.perf_counter() - t1)
        return self

    def reference_params(self):
        """The served weights made again from the seed; the reference
        reads them as float32 one layer at a time."""
        return self.weights()


def build(cell, seed: int, devices) -> System:
    return System(cell, seed, devices).build()
