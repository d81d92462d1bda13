"""Zamba2-style hybrid stack: Mamba2 backbone + ONE shared attention block.

Structure (arXiv:2411.15242): ``num_layers`` Mamba2 blocks; after every
``attn_every`` blocks, a SINGLE shared transformer block (attention + MLP,
parameters reused at every application) refreshes global context.  The stack
is scanned over groups of ``attn_every`` Mamba blocks (plus a Mamba-only
tail when ``num_layers % attn_every != 0``), with the shared block applied
once per group.

Decode state: per-Mamba-layer (conv tail, GLA state) — O(1) in sequence —
plus one KV cache per shared-attention application (num_groups caches).
Attention KV grows with context, but only num_groups ~= 6 of them exist, so
the 500k shape stays feasible (DESIGN.md §3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain_batch, constrain_logits
from repro.models import layers as L
from repro.models.ssm import CONV_K, init_mamba2, mamba2_fwd


def _attn_cfg(cfg: ModelConfig) -> L.AttnConfig:
    return L.AttnConfig(d_model=cfg.d_model, num_heads=cfg.num_heads,
                        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.hd,
                        rope_theta=cfg.rope_theta, causal=True)


def init_mamba_block(cfg: ModelConfig, key):
    p = L.ParamFactory(key)
    mp, ma = init_mamba2(p._split(), cfg.d_model, cfg.ssm_state,
                         cfg.ssm_heads, expand=cfg.ssm_expand)
    p.params["mamba"], p.axes["mamba"] = mp, ma
    p.zeros("norm", (cfg.d_model,), ("embed",))
    return p.params, p.axes


def init_shared_attn(cfg: ModelConfig, key):
    p = L.ParamFactory(key)
    ap, aa = L.init_attention(p._split(), _attn_cfg(cfg))
    p.params["attn"], p.axes["attn"] = ap, aa
    mp, ma = L.init_mlp(p._split(), cfg.d_model, cfg.d_ff, cfg.mlp)
    p.params["mlp"], p.axes["mlp"] = mp, ma
    p.zeros("norm1", (cfg.d_model,), ("embed",))
    p.zeros("norm2", (cfg.d_model,), ("embed",))
    return p.params, p.axes


def init_hybrid_lm(cfg: ModelConfig, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params, axes = {}, {}
    ep, ea = L.init_embedding(k1, cfg.padded_vocab, cfg.d_model,
                              cfg.tie_embeddings)
    params["embedding"], axes["embedding"] = ep, ea
    n_groups = cfg.num_layers // cfg.attn_every
    tail = cfg.num_layers - n_groups * cfg.attn_every

    def init_group(k):
        return L.stack_layer_params(lambda kk: init_mamba_block(cfg, kk), k,
                                    cfg.attn_every)

    gp, ga = L.stack_layer_params(init_group, k2, n_groups)
    params["groups"], axes["groups"] = gp, ga
    sp, sa = init_shared_attn(cfg, k3)  # ONE shared block (reused)
    params["shared_attn"], axes["shared_attn"] = sp, sa
    if tail:
        tp, ta = L.stack_layer_params(lambda kk: init_mamba_block(cfg, kk),
                                      k4, tail)
        params["tail"], axes["tail"] = tp, ta
    params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.bfloat16)
    axes["final_norm"] = ("embed",)
    return params, axes


def hybrid_state(cfg: ModelConfig, batch: int, cache_len: int,
                 dtype=jnp.bfloat16):
    """(mamba carries per layer, shared-attn KV caches per application)."""
    n_groups = cfg.num_layers // cfg.attn_every
    tail = cfg.num_layers - n_groups * cfg.attn_every
    d_inner = cfg.ssm_expand * cfg.d_model
    hd_m = d_inner // cfg.ssm_heads

    def carries(n):
        return (jnp.zeros((n, batch, CONV_K - 1, d_inner), dtype),
                jnp.zeros((n, batch, cfg.ssm_heads, cfg.ssm_state, hd_m),
                          jnp.float32))

    state = {
        "groups_conv": carries(n_groups * cfg.attn_every)[0].reshape(
            n_groups, cfg.attn_every, batch, CONV_K - 1, d_inner),
        "groups_gla": carries(n_groups * cfg.attn_every)[1].reshape(
            n_groups, cfg.attn_every, batch, cfg.ssm_heads, cfg.ssm_state,
            hd_m),
        "attn_k": jnp.zeros((n_groups, batch, cfg.num_kv_heads, cache_len,
                             cfg.hd), dtype),
        "attn_v": jnp.zeros((n_groups, batch, cfg.num_kv_heads, cache_len,
                             cfg.hd), dtype),
    }
    if tail:
        state["tail_conv"], state["tail_gla"] = carries(tail)
    return state


def _mamba_block(cfg, blk, x, carry, decode):
    x = constrain_batch(x)
    out, new_carry = mamba2_fwd(blk["mamba"], L.rms_norm(x, blk["norm"]),
                                state=cfg.ssm_state, num_heads=cfg.ssm_heads,
                                carry=carry, decode=decode)
    return x + out, new_carry


def _shared_attn_fwd(cfg, sp, x, pos):
    x = constrain_batch(x)
    a, kv = L.attention_fwd(sp["attn"], L.rms_norm(x, sp["norm1"]),
                            _attn_cfg(cfg), pos)
    x = x + a
    m = L.mlp_fwd(sp["mlp"], L.rms_norm(x, sp["norm2"]), cfg.mlp)
    return x + m, kv


def _shared_attn_decode(cfg, sp, x, kc, vc, kv_len, pos):
    a, k, v = L.attention_decode(sp["attn"], L.rms_norm(x, sp["norm1"]),
                                 _attn_cfg(cfg), kc, vc, kv_len, pos)
    x = x + a
    m = L.mlp_fwd(sp["mlp"], L.rms_norm(x, sp["norm2"]), cfg.mlp)
    return x + m, k, v


def hybrid_forward(params, cfg: ModelConfig, tokens, embeds=None,
                   remat: bool = True):
    B, S = tokens.shape
    x = L.embed_fwd(params["embedding"], tokens)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    sp = params["shared_attn"]

    def group_body(x, grp):
        def mamba_body(x, blk):
            x, _ = _mamba_block(cfg, blk, x, None, decode=False)
            return x, None

        x, _ = jax.lax.scan(mamba_body, x, grp)
        x, _ = _shared_attn_fwd(cfg, sp, x, pos)
        return x, None

    if remat:
        group_body = L.maybe_remat(group_body, cfg.remat)
    x, _ = jax.lax.scan(group_body, x, params["groups"])
    if "tail" in params:
        def tail_body(x, blk):
            x, _ = _mamba_block(cfg, blk, x, None, decode=False)
            return x, None

        x, _ = jax.lax.scan(tail_body, x, params["tail"])
    x = L.rms_norm(x, params["final_norm"])
    return (constrain_logits(L.unembed_fwd(params["embedding"], x)),
            jnp.zeros((), jnp.float32))


def hybrid_prefill(params, cfg: ModelConfig, tokens, cache_len=None,
                   embeds=None):
    B, S = tokens.shape
    cache_len = cache_len or S
    x = L.embed_fwd(params["embedding"], tokens)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    sp = params["shared_attn"]

    def group_body(x, grp):
        def mamba_body(x, blk):
            x, carry = _mamba_block(cfg, blk, x, None, decode=False)
            return x, carry

        x, carries = jax.lax.scan(mamba_body, x, grp)
        x, (k, v) = _shared_attn_fwd(cfg, sp, x, pos)
        return x, (carries, L.to_cache(k, cache_len),
                   L.to_cache(v, cache_len))

    x, (gc, ks, vs) = jax.lax.scan(group_body, x, params["groups"])
    state = {"groups_conv": gc[0], "groups_gla": gc[1],
             "attn_k": ks, "attn_v": vs}
    if "tail" in params:
        def tail_body(x, blk):
            x, carry = _mamba_block(cfg, blk, x, None, decode=False)
            return x, carry

        x, tc = jax.lax.scan(tail_body, x, params["tail"])
        state["tail_conv"], state["tail_gla"] = tc
    x = L.rms_norm(x, params["final_norm"])
    logits = L.unembed_fwd(params["embedding"], x[:, -1:])[:, 0]
    return logits, state


def hybrid_decode_step(params, cfg: ModelConfig, state, kv_len, token,
                       embeds=None):
    B = token.shape[0]
    x = L.embed_fwd(params["embedding"], token)
    pos = jnp.broadcast_to(jnp.arange(1)[None], (B, 1)) + kv_len
    sp = params["shared_attn"]

    def group_body(x, xs):
        grp, conv, gla, kc, vc = xs

        def mamba_body(x, xs2):
            blk, c, g = xs2
            x, (nc, ng) = _mamba_block(cfg, blk, x, (c, g), decode=True)
            return x, (nc, ng)

        x, (nconv, ngla) = jax.lax.scan(mamba_body, x, (grp, conv, gla))
        x, k, v = _shared_attn_decode(cfg, sp, x, kc, vc, kv_len, pos)
        return x, (nconv, ngla, k, v)

    x, (gc, gg, ks, vs) = jax.lax.scan(
        group_body, x, (params["groups"], state["groups_conv"],
                        state["groups_gla"], state["attn_k"],
                        state["attn_v"]))
    new = dict(state, groups_conv=gc, groups_gla=gg,
               attn_k=L.write_kv(state["attn_k"], ks, kv_len),
               attn_v=L.write_kv(state["attn_v"], vs, kv_len))
    if "tail" in params:
        def tail_body(x, xs2):
            blk, c, g = xs2
            x, (nc, ng) = _mamba_block(cfg, blk, x, (c, g), decode=True)
            return x, (nc, ng)

        x, (tc, tg) = jax.lax.scan(tail_body, x,
                                   (params["tail"], state["tail_conv"],
                                    state["tail_gla"]))
        new["tail_conv"], new["tail_gla"] = tc, tg
    x = L.rms_norm(x, params["final_norm"])
    return L.unembed_fwd(params["embedding"], x)[:, 0], new
