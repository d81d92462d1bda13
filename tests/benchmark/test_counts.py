"""The benchmark's FLOP and byte counts against hand arithmetic at a tiny
configuration."""

import dataclasses

import pytest

from benchmarks.chip import counts

# d_model 8, 2 query heads and 1 KV head of 4, MLP 16, vocabulary 10.
DENSE = counts.Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                    d_ff=16, vocab=10, experts=0, top_k=0, gated=False,
                    qkv_bias=True, layer_norm=True, itemsize=2)
MOE = dataclasses.replace(DENSE, experts=4, top_k=2, gated=True,
                          qkv_bias=False, layer_norm=False)


def test_linear_flops_per_token():
    # q, o: 8x8 each; k, v: 8x4 each -> 192; GELU MLP 2 x 8x16 = 256.
    assert counts.linear_flops_per_token(DENSE) == 2 * (192 + 256)
    # two of four SwiGLU experts (3 x 8x16 each) and an 8x4 router.
    assert counts.linear_flops_per_token(MOE) == 2 * (192 + 2 * 384 + 32)


def test_causal_attention_counts_kept_pairs_only():
    # batch 3, 5 tokens: 15 query-key pairs, 2 matmuls x 2 FLOPs x 2 heads
    # x head 4.
    assert counts.attention_flops(DENSE, 3, 5) == 2 * 2 * 3 * 2 * 4 * 15
    assert counts.flash_flops(DENSE, 3, 5) == 1440


def test_prefill_flops():
    layer = 3 * 5 * 896 + 1440
    logits = 2 * 3 * 8 * 10              # last token of each prompt only
    assert counts.prefill_flops(DENSE, 3, 5) == 2 * layer + logits


def test_flash_bytes_read_q_k_v_once_and_write_o():
    # per token: q and o 2 x 8, k and v 2 x 4, in bf16.
    assert counts.flash_bytes(DENSE, 3, 5) == 3 * 5 * 24 * 2


def test_decode_weight_bytes():
    layer = 192 + (2 + 2) * 4 + 4 * 8 + 256      # attn, biases, 2 LNs, MLP
    head = 8 * 10 + 8 + 3 * 8                    # unembed, final norm, rows
    assert counts.decode_weight_bytes(DENSE, 3) == (2 * layer + head) * 2


@pytest.mark.parametrize("tokens,touched", [(1, 2.0), (2, 3.0), (3, 3.5)])
def test_expected_experts(tokens, touched):
    # each token picks 2 of 4: an expert is missed with chance (1/2)^tokens.
    assert counts.expected_experts(MOE, tokens) == pytest.approx(touched)


def test_decode_moe_weights_count_touched_experts():
    one = counts.decode_weight_bytes(MOE, 1)
    two = counts.decode_weight_bytes(MOE, 2)
    # one more expert (3 x 8x16) per layer, one more embedding row.
    assert two - one == pytest.approx((2 * 384 + 8) * 2)


def test_decode_kv_counts_valid_entries_not_the_cache():
    # kv_len 5: 6 entries read (the new one among them), 1 written, per
    # layer and sequence; K and V of 1 head of 4 in bf16 is 16 bytes.
    assert counts.decode_kv_bytes(DENSE, 3, 5) == 2 * 3 * 7 * 16
    whole_cache_and_copy = 2 * 3 * (64 + 2 * 64) * 16   # a 64-entry cache
    step = counts.decode_step_bytes(DENSE, 3, 5)
    assert step == counts.decode_weight_bytes(DENSE, 3) + 672
    assert step < counts.decode_weight_bytes(DENSE, 3) + whole_cache_and_copy
