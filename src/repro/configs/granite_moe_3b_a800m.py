"""Granite-3.0-3B-A800M: 32L, d=1536, 24H (GQA kv=8), fine-grained MoE:
40 experts top-8, d_ff=512 per expert, vocab 49155.

[hf:ibm-granite/granite-3.0-3b-a800m-base]  The published model ties its
embeddings, routes dropless (``capacity_factor=None``) and scales by four
multipliers: ``embedding_multiplier`` 12, ``attention_multiplier``
0.015625, ``residual_multiplier`` 0.22 and ``logits_scaling`` 6, set as
overrides where the model is run: ``benchmarks/chip/configs/
granite-3.0-3b-a800m.json`` states them, and where it departs from them
and why.  This module keeps the
identity multipliers, a capacity factor of 1.25 and an untied head, which
the tests' small expert configurations use.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite_moe_3b_a800m", family="moe",
    num_layers=32, d_model=1536, num_heads=24, num_kv_heads=8,
    d_ff=512, vocab_size=49155, mlp="swiglu",
    num_experts=40, top_k=8,
    source="hf:ibm-granite/granite-3.0-3b-a800m-base",
)
