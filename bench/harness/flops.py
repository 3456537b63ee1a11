"""Operations and bytes the algorithm needs, from shapes alone.

``matmul_params`` copies ``repro.models.flops``'s dense-decoder arithmetic
(attention q/k/v/o and SwiGLU gate/up/down per layer, and the output head).
A decoded token costs two FLOPs per weight it multiplies — every layer's
projections and the output head, not the embedding lookup — plus attention
over its context:
2 * 2 * layers * heads * head_dim per earlier position (scores and the
weighted sum of values).

An LCC site's cost is counted from the decomposition's own content, never
from a kernel's layout: one add per stored term per batch column, and the
stream's bytes at the artifact's bytes per term plus the activations read
and written in f32.  One-hot tiles and padding are never counted; a change
to the stored stream format changes ``LCC_BYTES_PER_TERM`` and needs a
benchmark change.
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["ModelShape", "matmul_params",
           "decode_flops_per_token", "request_decode_flops",
           "LCC_BYTES_PER_TERM", "lcc_site_cost"]

# int32 index + int8 exponent + int8 sign per term, as the packed artifact
# stores it (repro.kernels.ops.PackedDecomposition)
LCC_BYTES_PER_TERM = 6


@dataclass(frozen=True)
class ModelShape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "ModelShape":
        h = int(c["num_attention_heads"])
        d = int(c["hidden_size"])
        return cls(layers=int(c["num_hidden_layers"]), d_model=d, heads=h,
                   kv_heads=int(c.get("num_key_value_heads", h)),
                   head_dim=int(c.get("head_dim") or d // h),
                   d_ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]))


def _layer_params(m: ModelShape) -> int:
    attn = (m.d_model * m.heads * m.head_dim
            + 2 * m.d_model * m.kv_heads * m.head_dim
            + m.heads * m.head_dim * m.d_model)
    return attn + 3 * m.d_model * m.d_ff


def matmul_params(m: ModelShape) -> int:
    """Weights a decoded token multiplies: every layer and the head."""
    return m.layers * _layer_params(m) + m.vocab * m.d_model


def decode_flops_per_token(m: ModelShape, context: int) -> float:
    """FLOPs of one decoded token that attends to ``context`` positions."""
    return (2.0 * matmul_params(m)
            + 4.0 * m.layers * m.heads * m.head_dim * context)


def lcc_site_cost(terms: int, k_in: int, n_out: int, batch: int
                  ) -> tuple[float, float]:
    """(adds, bytes) of one site applied to ``batch`` columns."""
    adds = float(terms) * batch
    nbytes = (float(terms) * LCC_BYTES_PER_TERM
              + 4.0 * batch * (k_in + n_out))
    return adds, nbytes


def request_decode_flops(m: ModelShape, prompt_len: int, n_tok: int
                         ) -> float:
    """FLOPs of a request's first ``n_tok`` decoded tokens: token j attends
    to prompt_len + j positions."""
    ctx_sum = n_tok * prompt_len + n_tok * (n_tok - 1) / 2
    return (2.0 * matmul_params(m) * n_tok
            + 4.0 * m.layers * m.heads * m.head_dim * ctx_sum)
