"""Operations and bytes that the served model needs, counted from its shapes.

Every count here is what the algorithm requires, not what the program does:
recomputed work, padding, capacity slots left empty and copies are not
counted.  The per-layer metrics divide these counts by device time from the
trace, so a program that does less needless work reads higher.

All functions take a :class:`Dims` built from a configuration file's keys.
"""

from __future__ import annotations

import dataclasses

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int              # dense MLP width, or one expert's width
    vocab: int             # the true vocabulary, not a padded one
    experts: int           # 0 for a dense MLP
    top_k: int
    gated: bool            # SwiGLU-style (gate and up) or a plain MLP
    qkv_bias: bool
    layer_norm: bool       # LayerNorm (weight and bias) or RMSNorm (weight)
    itemsize: int          # bytes per weight and per cache entry

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(
            layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            experts=cfg.get("num_local_experts", 0),
            top_k=cfg.get("num_experts_per_tok", 0),
            gated=cfg["mlp"] == "swiglu", qkv_bias=cfg["qkv_bias"],
            layer_norm=cfg["norm"] == "layer_norm",
            itemsize=BYTES[cfg["dtype"]])


def _mlp_matmul_params(d: Dims) -> int:
    """Weights of one dense MLP, or of one expert."""
    return (3 if d.gated else 2) * d.d_model * d.d_ff


def _attn_matmul_params(d: Dims) -> int:
    q_o = 2 * d.d_model * d.heads * d.head_dim
    k_v = 2 * d.d_model * d.kv_heads * d.head_dim
    return q_o + k_v


def linear_flops_per_token(d: Dims) -> int:
    """One token through one layer's projections, MLP or routed experts."""
    mlp = _mlp_matmul_params(d)
    if d.experts:
        mlp = d.top_k * mlp + d.d_model * d.experts      # experts + router
    return 2 * (_attn_matmul_params(d) + mlp)


def attention_flops(d: Dims, batch: int, seq: int) -> int:
    """Causal self-attention over ``seq`` tokens, one layer: QK^T and PV
    over the seq * (seq + 1) / 2 query-key pairs that the mask keeps."""
    pairs = seq * (seq + 1) // 2
    return 2 * 2 * batch * d.heads * d.head_dim * pairs


def prefill_flops(d: Dims, batch: int, seq: int) -> int:
    """A prefill of ``batch`` prompts of ``seq`` tokens: every layer for
    every token, and the logits of each prompt's last token only."""
    layers = d.layers * (batch * seq * linear_flops_per_token(d)
                         + attention_flops(d, batch, seq))
    return layers + 2 * batch * d.d_model * d.vocab


def flash_flops(d: Dims, batch: int, seq: int) -> int:
    """One call of the causal flash kernel: one layer's attention."""
    return attention_flops(d, batch, seq)


def flash_bytes(d: Dims, batch: int, seq: int) -> int:
    """One call of the flash kernel reads q, k and v once and writes o."""
    per_token = 2 * d.heads * d.head_dim + 2 * d.kv_heads * d.head_dim
    return batch * seq * per_token * d.itemsize


def expected_experts(d: Dims, tokens: int) -> float:
    """Distinct experts that ``tokens`` tokens touch, each choosing top_k of
    the experts, with routing uniform over them."""
    if not d.experts:
        return 0.0
    return d.experts * (1.0 - (1.0 - d.top_k / d.experts) ** tokens)


def decode_weight_bytes(d: Dims, batch: int) -> float:
    """Weights that one decode step of ``batch`` tokens has to read once:
    every layer's projections, norms and MLP (or the experts its tokens
    touch, and the router), the final norm, the output head over the true
    vocabulary, and the ``batch`` embedding rows it looks up."""
    norm = (4 if d.layer_norm else 2) * d.d_model
    bias = (d.heads + 2 * d.kv_heads) * d.head_dim if d.qkv_bias else 0
    if d.experts:
        mlp = (expected_experts(d, batch) * _mlp_matmul_params(d)
               + d.d_model * d.experts)
    else:
        mlp = _mlp_matmul_params(d)
    layer = _attn_matmul_params(d) + bias + norm + mlp
    head = d.d_model * d.vocab + d.d_model + batch * d.d_model
    return (d.layers * layer + head) * d.itemsize


def kv_entry_bytes(d: Dims) -> int:
    """K and V of one token in one layer."""
    return 2 * d.kv_heads * d.head_dim * d.itemsize


def decode_kv_bytes(d: Dims, batch: int, kv_len: int) -> int:
    """One decode step with ``kv_len`` entries already cached: it writes the
    new token's K and V and reads the kv_len + 1 valid entries, in every
    layer.  The rest of a preallocated cache is not counted, nor is a copy
    of the cache."""
    return d.layers * batch * (kv_len + 2) * kv_entry_bytes(d)


def decode_step_bytes(d: Dims, batch: int, kv_len: int) -> float:
    return decode_weight_bytes(d, batch) + decode_kv_bytes(d, batch, kv_len)
