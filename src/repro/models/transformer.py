"""Decoder-only LM covering the dense, MoE, and VLM families.

Layer stacks are SCANNED (params stacked on a leading "layers" axis) so
compile time is O(1) in depth — essential for the 40-cell dry-run of 80-layer
models.  The gemma3-style local:global pattern uses a *grouped* scan: each
group holds (group_size - 1) sliding-window layers plus one global layer, so
decode caches are heterogeneous — window-sized rings for local layers, full
length for global layers — which is what makes the 500k-context shape fit.

Entry points (all pure, pjit-able):
  init_lm(cfg, key)                      -> (params, logical-axes tree)
  lm_forward(params, cfg, tokens, ...)   -> logits          (train)
  lm_init_cache(cfg, batch, cache_len)   -> cache pytree    (ShapeDtypeStruct-safe)
  lm_prefill(params, cfg, tokens, ...)   -> (logits, cache)
  lm_decode_step(params, cfg, cache, kv_len, token) -> (logits, cache)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain_batch, constrain_logits
from repro.models import layers as L
from repro.models.moe import init_moe, moe_fwd

# ---------------------------------------------------------------------------
# Block init.
# ---------------------------------------------------------------------------


def _attn_cfg(cfg: ModelConfig, *, window=None, theta=None) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.hd,
        qkv_bias=cfg.qkv_bias, rope_theta=theta or cfg.rope_theta,
        mrope=cfg.mrope, causal=True, window=window,
        scale=cfg.attention_multiplier)


def init_block(cfg: ModelConfig, key) -> tuple[dict, dict]:
    """One decoder block: norm -> attn -> norm -> mlp/moe."""
    p = L.ParamFactory(key)
    ap, aa = L.init_attention(p._split(), _attn_cfg(cfg))
    p.params["attn"], p.axes["attn"] = ap, aa
    if cfg.norm == "rms":
        p.zeros("norm1", (cfg.d_model,), ("embed",))
        p.zeros("norm2", (cfg.d_model,), ("embed",))
    else:
        p.ones("norm1_w", (cfg.d_model,), ("embed",))
        p.zeros("norm1_b", (cfg.d_model,), ("embed",))
        p.ones("norm2_w", (cfg.d_model,), ("embed",))
        p.zeros("norm2_b", (cfg.d_model,), ("embed",))
    if cfg.family == "moe":
        mp, ma = init_moe(p._split(), cfg.d_model, cfg.d_ff, cfg.num_experts,
                          cfg.top_k, cfg.mlp)
        p.params["moe"], p.axes["moe"] = mp, ma
    else:
        mp, ma = L.init_mlp(p._split(), cfg.d_model, cfg.d_ff, cfg.mlp)
        p.params["mlp"], p.axes["mlp"] = mp, ma
    return p.params, p.axes


@jax.named_scope("norm")
def _norm1(params, cfg, x):
    if cfg.norm == "rms":
        return L.rms_norm(x, params["norm1"])
    return L.layer_norm(x, params["norm1_w"], params["norm1_b"])


@jax.named_scope("norm")
def _norm2(params, cfg, x):
    if cfg.norm == "rms":
        return L.rms_norm(x, params["norm2"])
    return L.layer_norm(x, params["norm2_w"], params["norm2_b"])


def _mix(params, cfg, h):
    if cfg.family == "moe":
        with jax.named_scope("moe"):
            return moe_fwd(params["moe"], h, num_experts=cfg.num_experts,
                           top_k=cfg.top_k, kind=cfg.mlp,
                           capacity_factor=cfg.capacity_factor)
    with jax.named_scope("mlp"):
        m = L.mlp_fwd(params["mlp"], h, cfg.mlp)
    return m, {"aux_loss": jnp.zeros((), jnp.float32)}


def _residual(cfg: ModelConfig, x, out):
    """The residual add of a block's attention or MLP output, scaled by
    the residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def block_fwd(params, x, cfg: ModelConfig, positions, *,
              window=None, theta=None):
    """Full-sequence block.  Returns (x, (k, v), aux_loss)."""
    x = constrain_batch(x)  # keep activations batch-sharded (DP/FSDP)
    acfg = _attn_cfg(cfg, window=window, theta=theta)
    a, kv = L.attention_fwd(params["attn"], _norm1(params, cfg, x), acfg,
                            positions)
    x = _residual(cfg, x, a)
    m, aux = _mix(params, cfg, _norm2(params, cfg, x))
    return _residual(cfg, x, m), kv, aux["aux_loss"]


def block_decode(params, x, cfg: ModelConfig, k_cache, v_cache, kv_len,
                 positions, *, theta=None):
    """One token through a block that reads its layer's cache (a
    sliding-window layer's is a ring of the window's size).  Returns (x,
    k_new, v_new): the new entries, which the caller writes after its scan
    (``L.write_kv``)."""
    acfg = _attn_cfg(cfg, theta=theta)
    a, k_new, v_new = L.attention_decode(
        params["attn"], _norm1(params, cfg, x), acfg, k_cache, v_cache,
        kv_len, positions)
    x = _residual(cfg, x, a)
    m, _ = _mix(params, cfg, _norm2(params, cfg, x))
    return _residual(cfg, x, m), k_new, v_new


# ---------------------------------------------------------------------------
# Model init.
# ---------------------------------------------------------------------------


def init_lm(cfg: ModelConfig, key) -> tuple[dict, dict]:
    keys = jax.random.split(key, 4)
    params: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    ep, ea = L.init_embedding(keys[0], cfg.padded_vocab, cfg.d_model,
                              cfg.tie_embeddings)
    params["embedding"], axes["embedding"] = ep, ea
    if cfg.attention == "local_global":
        gsz = cfg.group_size
        n_groups = cfg.num_layers // gsz
        tail = cfg.num_layers - n_groups * gsz

        def init_local(k):
            return init_block(cfg, k)

        def init_group(k):
            k1, k2 = jax.random.split(k)
            lp, la = L.stack_layer_params(init_local, k1, gsz - 1)
            gp, ga = init_block(cfg, k2)
            return {"local": lp, "global": gp}, {"local": la, "global": ga}

        gp, ga = L.stack_layer_params(init_group, keys[1], n_groups)
        params["groups"], axes["groups"] = gp, ga
        if tail:
            tp, ta = L.stack_layer_params(init_local, keys[2], tail)
            params["tail"], axes["tail"] = tp, ta
    else:
        bp, ba = L.stack_layer_params(lambda k: init_block(cfg, k),
                                      keys[1], cfg.num_layers)
        params["blocks"], axes["blocks"] = bp, ba
    params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.bfloat16)
    axes["final_norm"] = ("embed",)
    return params, axes


def _final(params, cfg, x):
    with jax.named_scope("norm"):
        x = L.rms_norm(constrain_batch(x), params["final_norm"])
    with jax.named_scope("logits"):
        return constrain_logits(L.unembed_fwd(params["embedding"], x,
                                              cfg.logits_scaling))


def _embed(params, cfg, tokens, embeds=None):
    """Token embeddings, scaled by the embedding multiplier; ``embeds`` (B,
    V, d_model), where given, override the first V positions (VLM patch /
    audio frame stub)."""
    with jax.named_scope("embed"):
        x = L.embed_fwd(params["embedding"], tokens)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if embeds is not None:
            V = embeds.shape[1]
            x = jnp.concatenate([embeds.astype(x.dtype), x[:, V:]], axis=1)
        return x


def _positions(cfg: ModelConfig, B: int, S: int, offset=0):
    pos = jnp.arange(S)[None] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope:
        return jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


# ---------------------------------------------------------------------------
# Training forward.
# ---------------------------------------------------------------------------


def lm_forward(params, cfg: ModelConfig, tokens, embeds=None,
               remat: bool = True):
    """tokens: (B, S) int32.  ``embeds``: optional (B, V, d_model) prefix
    embeddings (VLM patch / audio frame stub) overriding the first V slots.
    Returns (logits, aux_loss)."""
    B, S = tokens.shape
    x = _embed(params, cfg, tokens, embeds)
    pos = _positions(cfg, B, S)

    if cfg.attention == "local_global":
        x, aux = _forward_local_global(params, cfg, x, pos, remat)
    else:
        def body(carry, blk):
            x, aux = carry
            x, _, a = block_fwd(blk, x, cfg, pos)
            return (x, aux + a), None

        if remat:
            body = L.maybe_remat(body, cfg.remat)
        with jax.named_scope("layers"):
            (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                       params["blocks"])
    return _final(params, cfg, x), aux


def _forward_local_global(params, cfg, x, pos, remat):
    def group_body(carry, grp):
        x, aux = carry

        def local_body(c, blk):
            xx, aa = c
            xx, _, a = block_fwd(blk, xx, cfg, pos, window=cfg.window,
                                 theta=cfg.rope_theta)
            return (xx, aa + a), None

        (x, aux), _ = jax.lax.scan(local_body, (x, aux), grp["local"])
        x, _, a = block_fwd(grp["global"], x, cfg, pos,
                            theta=cfg.rope_theta_global)
        return (x, aux + a), None

    if remat:
        group_body = L.maybe_remat(group_body, cfg.remat)
    with jax.named_scope("layers"):
        (x, aux), _ = jax.lax.scan(group_body,
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["groups"])
    if "tail" in params:
        def tail_body(c, blk):
            xx, aa = c
            xx, _, a = block_fwd(blk, xx, cfg, pos, window=cfg.window)
            return (xx, aa + a), None

        if remat:
            tail_body = L.maybe_remat(tail_body, cfg.remat)
        with jax.named_scope("layers"):
            (x, aux), _ = jax.lax.scan(tail_body, (x, aux), params["tail"])
    return x, aux


# ---------------------------------------------------------------------------
# KV cache: init / prefill / decode.
# ---------------------------------------------------------------------------


def lm_init_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  dtype=jnp.bfloat16):
    KV, hd = cfg.num_kv_heads, cfg.hd
    if cfg.attention == "local_global":
        gsz = cfg.group_size
        n_groups = cfg.num_layers // gsz
        tail = cfg.num_layers - n_groups * gsz
        W = min(cfg.window, cache_len)
        cache = {
            "local_k": jnp.zeros((n_groups, gsz - 1, batch, KV, W, hd), dtype),
            "local_v": jnp.zeros((n_groups, gsz - 1, batch, KV, W, hd), dtype),
            "global_k": jnp.zeros((n_groups, batch, KV, cache_len, hd), dtype),
            "global_v": jnp.zeros((n_groups, batch, KV, cache_len, hd), dtype),
        }
        if tail:
            cache["tail_k"] = jnp.zeros((tail, batch, KV, W, hd), dtype)
            cache["tail_v"] = jnp.zeros((tail, batch, KV, W, hd), dtype)
        return cache
    Lr = cfg.num_layers
    return {"k": jnp.zeros((Lr, batch, KV, cache_len, hd), dtype),
            "v": jnp.zeros((Lr, batch, KV, cache_len, hd), dtype)}


def lm_decode_step(params, cfg: ModelConfig, cache: dict, kv_len, token,
                   embeds=None):
    """token: (B, 1) int32; kv_len: existing valid cache entries.
    Returns (logits (B, vocab), new cache).

    The scan over layers reads each layer's cache where it lies and emits
    only the layer's new K and V entry; one write a leaf after the scan
    puts them at ``kv_len``.  With the cache donated, that write is in
    place and the step copies no cache."""
    B = token.shape[0]
    x = _embed(params, cfg, token)
    pos = _positions(cfg, B, 1, offset=kv_len)

    if cfg.attention == "local_global":
        x, cache = _decode_local_global(params, cfg, x, cache, kv_len, pos)
    else:
        def body(x, blk_cache):
            blk, kc, vc = blk_cache
            x, k, v = block_decode(blk, x, cfg, kc, vc, kv_len, pos)
            return x, (k, v)

        with jax.named_scope("layers"):
            x, (k, v) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"]))
        cache = {"k": L.write_kv(cache["k"], k, kv_len),
                 "v": L.write_kv(cache["v"], v, kv_len)}
    return _final(params, cfg, x)[:, 0], cache


def _decode_local_global(params, cfg, x, cache, kv_len, pos):
    """As the uniform path: the scans read the rings and the global caches
    and emit each layer's new entries, written after them."""
    def group_body(x, xs):
        grp, lk, lv, gk, gv = xs

        def local_body(x, xs2):
            blk, kc, vc = xs2
            x, k, v = block_decode(blk, x, cfg, kc, vc, kv_len, pos,
                                   theta=cfg.rope_theta)
            return x, (k, v)

        x, (lk, lv) = jax.lax.scan(local_body, x, (grp["local"], lk, lv))
        x, gk, gv = block_decode(grp["global"], x, cfg, gk, gv, kv_len, pos,
                                 theta=cfg.rope_theta_global)
        return x, (lk, lv, gk, gv)

    with jax.named_scope("layers"):
        x, (lk, lv, gk, gv) = jax.lax.scan(
            group_body, x, (params["groups"], cache["local_k"],
                            cache["local_v"], cache["global_k"],
                            cache["global_v"]))
    new = dict(cache,
               local_k=L.write_kv(cache["local_k"], lk, kv_len),
               local_v=L.write_kv(cache["local_v"], lv, kv_len),
               global_k=L.write_kv(cache["global_k"], gk, kv_len),
               global_v=L.write_kv(cache["global_v"], gv, kv_len))
    if "tail" in params:
        def tail_body(x, xs2):
            blk, kc, vc = xs2
            x, k, v = block_decode(blk, x, cfg, kc, vc, kv_len, pos)
            return x, (k, v)

        with jax.named_scope("layers"):
            x, (tk, tv) = jax.lax.scan(tail_body, x,
                                       (params["tail"], cache["tail_k"],
                                        cache["tail_v"]))
        new["tail_k"] = L.write_kv(cache["tail_k"], tk, kv_len)
        new["tail_v"] = L.write_kv(cache["tail_v"], tv, kv_len)
    return x, new


def lm_prefill(params, cfg: ModelConfig, tokens, cache_len: int | None = None,
               embeds=None):
    """Run the full prompt, returning (last-token logits, filled cache).

    The cache is filled by re-running attention projections per layer inside
    the same scan that computes the forward pass (kv returned by each block).
    """
    B, S = tokens.shape
    cache_len = cache_len or S
    x = _embed(params, cfg, tokens, embeds)
    pos = _positions(cfg, B, S)

    if cfg.attention == "local_global":
        return _prefill_local_global(params, cfg, x, pos, cache_len)

    def body(x, blk):
        x, (k, v), _ = block_fwd(blk, x, cfg, pos)
        return x, (k, v)

    with jax.named_scope("layers"):
        x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    ks, vs = L.to_cache(ks, cache_len), L.to_cache(vs, cache_len)
    logits = _final(params, cfg, x[:, -1:])[:, 0]
    return logits, {"k": ks, "v": vs}


def _prefill_local_global(params, cfg, x, pos, cache_len):
    W = min(cfg.window, cache_len)
    S_in = x.shape[1]

    @jax.named_scope("kv_write")
    def ring(a):
        """Store position p at ring index p % W (decode slot convention)."""
        if S_in <= W:  # positions 0..S_in-1 land at indices 0..S_in-1
            return L.to_cache(a, W)
        return jnp.roll(L.to_cache(a[:, -W:], W), S_in % W, axis=2)

    def group_body(x, grp):
        def local_body(x, blk):
            x, (k, v), _ = block_fwd(blk, x, cfg, pos, window=cfg.window,
                                     theta=cfg.rope_theta)
            return x, (ring(k), ring(v))

        x, (lk, lv) = jax.lax.scan(local_body, x, grp["local"])
        x, (gk, gv), _ = block_fwd(grp["global"], x, cfg, pos,
                                   theta=cfg.rope_theta_global)
        return x, (lk, lv, gk, gv)

    with jax.named_scope("layers"):
        x, (lk, lv, gk, gv) = jax.lax.scan(group_body, x, params["groups"])
    gk, gv = L.to_cache(gk, cache_len), L.to_cache(gv, cache_len)
    cache = {"local_k": lk, "local_v": lv, "global_k": gk, "global_v": gv}
    if "tail" in params:
        def tail_body(x, blk):
            x, (k, v), _ = block_fwd(blk, x, cfg, pos, window=cfg.window)
            return x, (ring(k), ring(v))

        with jax.named_scope("layers"):
            x, (tk, tv) = jax.lax.scan(tail_body, x, params["tail"])
        cache["tail_k"], cache["tail_v"] = tk, tv
    logits = _final(params, cfg, x[:, -1:])[:, 0]
    return logits, cache


# ---------------------------------------------------------------------------
# Paged decode (DDS-style block-table serving for dense/MoE/VLM archs).
# ---------------------------------------------------------------------------


def lm_init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                        page: int = 128, dtype=jnp.bfloat16):
    """Paged KV pool + block table per layer (the DDS file-mapping analogue:
    logical (sequence, position) -> physical pool page).

    Pool pages are allocated contiguously per sequence up front; a serving
    engine integrates `PagedKVEngine` to spill/fetch cold pages through the
    DDS store, remapping table entries as pages move.
    """
    if cfg.attention == "local_global":
        raise NotImplementedError("paged decode targets uniform-cache archs")
    KV, hd, Lr = cfg.num_kv_heads, cfg.hd, cfg.num_layers
    pages_per_seq = -(-max_len // page)
    npages = batch * pages_per_seq
    table = (jnp.arange(batch * pages_per_seq, dtype=jnp.int32)
             .reshape(batch, pages_per_seq))
    return {
        "k_pool": jnp.zeros((Lr, npages, page, KV, hd), dtype),
        "v_pool": jnp.zeros((Lr, npages, page, KV, hd), dtype),
        "block_table": table,            # shared across layers here
        "page": page,
    }


def lm_decode_step_paged(params, cfg: ModelConfig, cache: dict, kv_len,
                         token):
    """One-token decode over the paged pool via the paged-attention op.

    kv_len: number of existing valid positions (uniform across the batch in
    this entry point; the batch scheduler handles ragged lengths by passing
    per-sequence seq_lens to the kernel)."""
    from repro.kernels.paged_attention import paged_attention
    B = token.shape[0]
    page = cache["page"]
    table = cache["block_table"]
    x = _embed(params, cfg, token)
    pos = _positions(cfg, B, 1, offset=kv_len)
    acfg = _attn_cfg(cfg)
    slot_page = kv_len // page
    slot_off = kv_len % page
    phys = table[:, slot_page]                        # (B,) physical pages

    def body(x, xs):
        blk, k_pool, v_pool = xs
        h = _norm1(blk, cfg, x)
        with jax.named_scope("attn_qkv"):
            q, k_new, v_new = L._qkv(blk["attn"], h, acfg, pos)
        # Write the new token's K/V into its page (translate-then-write).
        with jax.named_scope("kv_write"):
            k_pool = k_pool.at[phys, slot_off].set(
                k_new[:, 0].astype(k_pool.dtype))
            v_pool = v_pool.at[phys, slot_off].set(
                v_new[:, 0].astype(v_pool.dtype))
        with jax.named_scope("attn_core"):
            seq_lens = jnp.full((B,), kv_len + 1, jnp.int32)
            o = paged_attention(q[:, 0], k_pool, v_pool, table, seq_lens,
                                scale=acfg.scale)
        with jax.named_scope("attn_out"):
            o = o.reshape(B, 1, cfg.num_heads * cfg.hd)
            x = _residual(cfg, x, o @ blk["attn"]["wo"])
        m, _ = _mix(blk, cfg, _norm2(blk, cfg, x))
        return _residual(cfg, x, m), (k_pool, v_pool)

    with jax.named_scope("layers"):
        x, (k_pool, v_pool) = jax.lax.scan(
            body, x, (params["blocks"], cache["k_pool"], cache["v_pool"]))
    new_cache = dict(cache, k_pool=k_pool, v_pool=v_pool)
    return _final(params, cfg, x)[:, 0], new_cache
