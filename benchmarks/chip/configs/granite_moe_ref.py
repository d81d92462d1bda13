"""Plain float32 reference of Granite-3.0-MoE (``model_type: granitemoe``):
the decoder of ``decoder_ref`` with the family's four multipliers and
dropless top-k routing.

It imports nothing of the program under test.  It reads the weights the
benchmark made, with the program's names (``decoder_ref``'s docstring), and
the configuration's keys:

    x = embed[tokens] * embedding_multiplier
    each layer:
        x = x + residual_multiplier * attn(rms(x))    scores * attention_multiplier
        x = x + residual_multiplier * moe(rms(x))
    logits = rms(x) @ unembed / logits_scaling

``moe``: router logits (float32), the top ``num_experts_per_tok`` experts
of each token, their gates the softmax of the chosen logits, and no token
dropped whatever the load.  Every expert is evaluated on every token and
weighted by its gate (zero when not chosen), as ``decoder_ref._experts``
does, a block of tokens at a time so that it fits beside the weights.

Everything is float32 with matmuls at ``highest`` precision.  ``fp8=True``
rounds every linear layer's operands, the router's among them, and the
cached K and V to float8 e4m3 as ``decoder_ref`` does: the precision one
step below the configuration's bfloat16, which the comparison must reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.chip.configs import decoder_ref as dr

TOKEN_BLOCK = 1024        # tokens per block of the every-expert evaluation


def _experts(m, h, cfg, fp8):
    """Dropless top-k routing over all experts, in blocks of tokens."""
    E, K = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    shape = h.shape
    h = h.reshape(-1, shape[-1])
    n = h.shape[0]
    nb = -(-n // TOKEN_BLOCK)
    hb = jnp.pad(h, ((0, nb * TOKEN_BLOCK - n), (0, 0))).reshape(
        nb, TOKEN_BLOCK, shape[-1])
    wg, wu, wo = (m[k].astype(jnp.float32) for k in ("wi_gate", "wi_up", "wo"))
    if fp8:
        wg, wu, wo = dr._fp8(wg, 1), dr._fp8(wu, 1), dr._fp8(wo, 1)

    def block(hx):
        top, idx = jax.lax.top_k(dr._linear(hx, m["router"], fp8), K)
        gates = jax.nn.softmax(top, axis=-1)
        weight = jnp.einsum("tk,tke->te", gates,
                            jax.nn.one_hot(idx, E, dtype=jnp.float32))
        if fp8:
            hx = dr._fp8(hx, -1)
        a = (jax.nn.silu(jnp.einsum("td,edf->tef", hx, wg))
             * jnp.einsum("td,edf->tef", hx, wu))
        if fp8:
            a = dr._fp8(a, -1)
        return jnp.einsum("tef,efd->td", a * weight[..., None], wo)

    return jax.lax.map(block, hb).reshape(-1, shape[-1])[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _layer(blocks, l, x, *, cfg_items, fp8):
    cfg = dict(cfg_items)
    blk = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, l, keepdims=False), blocks)
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    n, T, _ = x.shape
    at = blk["attn"]
    h = dr._rms(x, blk["norm1"])
    q, k, v = (dr._linear(h, at[w], fp8) for w in ("wq", "wk", "wv"))
    q = dr._rope(q.reshape(n, T, H, hd), cfg["rope_theta"])
    k = dr._rope(k.reshape(n, T, KV, hd), cfg["rope_theta"])
    v = v.reshape(n, T, KV, hd)
    if fp8:                                  # an fp8 KV cache, per entry
        k, v = dr._fp8(k, -1), dr._fp8(v, -1)
    # decoder_ref's attention scales scores by 1/sqrt(hd): q carries the
    # rest of the attention multiplier
    q = q * (cfg["attention_multiplier"] * math.sqrt(hd))
    o = dr._attention(q.reshape(n, T, KV, H // KV, hd), k, v, None)
    r = cfg["residual_multiplier"]
    x = x + r * dr._linear(o, at["wo"], fp8)
    h = dr._rms(x, blk["norm2"])
    return x + r * _experts(blk["moe"], h, cfg, fp8)


@functools.partial(jax.jit, static_argnames=("vocab", "scaling", "fp8"))
def _head(emb, final_norm, x, *, vocab, scaling, fp8):
    x = dr._rms(x, final_norm)
    w = emb["unembed"][:, :vocab] if "unembed" in emb else (
        emb["embed"][:vocab].T)
    return dr._linear(x, w, fp8) / scaling


def logits(params, cfg: dict, tokens, *, prompt_len: int, start: int,
           fp8: bool = False, rows_tokens: int = 8192):
    """Teacher-forced logits of ``tokens`` (n, T): (n, T - start, vocab)
    float32 for positions start..T-1.  Routing is dropless, so
    ``prompt_len`` changes nothing.  Sequences go through in groups of
    about ``rows_tokens`` tokens."""
    del prompt_len
    if cfg.get("capacity_factor") is not None:
        raise ValueError("this reference routes dropless only")
    cfg_items = tuple(sorted(
        (k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))))
    n, T = tokens.shape
    rows = max(1, rows_tokens // T)
    out = []
    with jax.default_matmul_precision("highest"):
        for r in range(0, n, rows):
            x = jnp.take(params["embedding"]["embed"],
                         jnp.asarray(tokens[r:r + rows]),
                         axis=0).astype(jnp.float32)
            x = x * cfg["embedding_multiplier"]
            for l in range(cfg["num_hidden_layers"]):
                x = _layer(params["blocks"], l, x, cfg_items=cfg_items,
                           fp8=fp8)
            out.append(_head(params["embedding"], params["final_norm"],
                             x[:, start:], vocab=cfg["vocab_size"],
                             scaling=cfg["logits_scaling"], fp8=fp8))
    return jnp.concatenate(out, axis=0)
