"""Operations and bytes of a DeepSeek-V2-style decoder (multi-head latent
attention, a leading dense layer, routed and shared experts), from shapes
alone.

A decoded token costs two FLOPs per weight it multiplies, counted as
published: the MLA projections once per token (q, the latent down-projection
and rope key, the latent's key and value up-projections, o), the dense
layer's SwiGLU, and per MoE layer the router, its ``top_k`` routed experts
and its shared experts; then the output head, not the embedding lookup.
Attention over the context adds 2 * heads * (qk_nope + qk_rope + v_dim)
FLOPs per earlier position per layer (scores and the weighted sum).  How a
program lays the work out — expanding the latent into per-head keys and
values every step, padding, tiles — is never counted.

At DeepSeek-V2-Lite's 27 layers a token multiplies 2.45 B weights; at the
benchmark's 7, 0.789 B.

The grouped expert launches' least work per decode step is counted from the
routing alone: each MoE layer reads the three bf16 matrices of every expert
that received a row, once, plus the rows' bf16 activations in and out of
each launch, and computes 2 * rows * 3 * d_model * d_expert FLOPs.
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["MLAMoEShape", "matmul_params", "decode_flops_per_token",
           "request_decode_flops", "experts_hit", "moe_layer_cost"]

BF16 = 2  # bytes


@dataclass(frozen=True)
class MLAMoEShape:
    layers: int
    n_dense: int
    d_model: int
    heads: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    d_ff: int
    d_expert: int
    n_experts: int
    top_k: int
    n_shared: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "MLAMoEShape":
        return cls(layers=int(c["num_hidden_layers"]),
                   n_dense=int(c["first_k_dense_replace"]),
                   d_model=int(c["hidden_size"]),
                   heads=int(c["num_attention_heads"]),
                   kv_lora=int(c["kv_lora_rank"]),
                   qk_nope=int(c["qk_nope_head_dim"]),
                   qk_rope=int(c["qk_rope_head_dim"]),
                   v_dim=int(c["v_head_dim"]),
                   d_ff=int(c["intermediate_size"]),
                   d_expert=int(c["moe_intermediate_size"]),
                   n_experts=int(c["n_routed_experts"]),
                   top_k=int(c["num_experts_per_tok"]),
                   n_shared=int(c["n_shared_experts"]),
                   vocab=int(c["vocab_size"]))


def _mla_params(m: MLAMoEShape) -> int:
    d, h = m.d_model, m.heads
    return (d * h * (m.qk_nope + m.qk_rope)            # q
            + d * (m.kv_lora + m.qk_rope)              # latent + rope key
            + m.kv_lora * h * (m.qk_nope + m.v_dim)    # key/value up
            + h * m.v_dim * d)                         # o


def matmul_params(m: MLAMoEShape) -> int:
    """Weights a decoded token multiplies: every layer and the head."""
    d = m.d_model
    dense = _mla_params(m) + 3 * d * m.d_ff
    moe = (_mla_params(m) + d * m.n_experts
           + (m.top_k + m.n_shared) * 3 * d * m.d_expert)
    return (m.n_dense * dense + (m.layers - m.n_dense) * moe
            + d * m.vocab)


def _attn_flops_per_position(m: MLAMoEShape) -> float:
    return 2.0 * m.heads * (m.qk_nope + m.qk_rope + m.v_dim)


def decode_flops_per_token(m: MLAMoEShape, context: int) -> float:
    """FLOPs of one decoded token that attends to ``context`` positions."""
    return (2.0 * matmul_params(m)
            + m.layers * _attn_flops_per_position(m) * context)


def request_decode_flops(m: MLAMoEShape, prompt_len: int, n_tok: int
                         ) -> float:
    """FLOPs of a request's first ``n_tok`` decoded tokens: token j attends
    to prompt_len + j positions."""
    ctx_sum = n_tok * prompt_len + n_tok * (n_tok - 1) / 2
    return (2.0 * matmul_params(m) * n_tok
            + m.layers * _attn_flops_per_position(m) * ctx_sum)


def experts_hit(m: MLAMoEShape, tokens: float) -> float:
    """Experts expected to receive at least one of ``tokens`` tokens' rows
    when each token picks ``top_k`` of ``n_experts`` uniformly."""
    return m.n_experts * (1.0 - (1.0 - m.top_k / m.n_experts) ** tokens)


def moe_layer_cost(m: MLAMoEShape, tokens: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one MoE layer's two grouped launches for ``tokens``
    decoded tokens."""
    d, f = m.d_model, m.d_expert
    rows = tokens * m.top_k
    flops = 2.0 * rows * 3 * d * f
    nbytes = (experts_hit(m, tokens) * 3 * d * f * BF16
              + rows * 2 * (d + f) * BF16)  # gate/up: d in, f out; down: f in, d out
    return flops, nbytes
