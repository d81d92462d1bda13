"""Plain float32 reference of the decoder-only models the configurations
here describe: GQA attention with RoPE, then a dense MLP or routed experts.

It imports nothing of the program under test.  It reads the weights the
benchmark made, as a tree with the program's names:

    embedding: embed (Vp, D), unembed (D, Vp)         rows past vocab_size unused
    blocks (each leaf stacked over layers):
        attn: wq (D, H*hd), wk, wv (D, KV*hd), wo (H*hd, D), [bq, bk, bv]
        norm1, norm2 (D,)                  RMSNorm: x * rsqrt(mean x^2 + eps) * (1 + w)
        or norm1_w, norm1_b, norm2_w, norm2_b   LayerNorm: weight and bias
        mlp: wi_up (D, F), wo (F, D), [wi_gate (D, F)]
        or moe: router (D, E), wi_gate, wi_up (E, D, F), wo (E, F, D)
    final_norm (D,)                        RMSNorm as above, eps 1e-6

Attention is causal and, where the configuration gives ``sliding_window``,
sees only that many of the latest positions.  Everything is computed in float32 with matmuls at ``highest`` precision,
layer by layer and in blocks of queries, so that it fits beside the
weights.  ``fp8=True`` computes the same thing with every linear layer's
operands and the cached K and V rounded to float8 (e4m3, scaled per row and
per output channel): the precision one step below the configuration's
bfloat16, which the comparison must reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
RMS_EPS = 1e-6
Q_BLOCK = 256
F8_MAX = 448.0      # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, fp8):
    """x (..., K) @ w (K, N); under fp8, x per row and w per column."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, w, eps=RMS_EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def _ln(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + LN_EPS) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _norm(blk, i, x):
    if f"norm{i}" in blk:
        return _rms(x, blk[f"norm{i}"])
    return _ln(x, blk[f"norm{i}_w"], blk[f"norm{i}_b"])


def _rope(x, theta):
    """x (n, T, heads, hd) at positions 0..T-1; the two halves of the head
    dimension rotate as pairs (i, i + hd/2) at frequency theta^(-2i/hd)."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs     # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window):
    """Causal GQA: q (n, T, KV, G, hd); k, v (n, T, KV, hd).  With a
    ``window``, a query sees only the last ``window`` positions, its own
    included.  Queries are taken Q_BLOCK at a time so the scores fit."""
    n, T, KV, G, hd = q.shape
    nb = -(-T // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, nb * Q_BLOCK - T), (0, 0), (0, 0), (0, 0)))
    qb = qp.reshape(n, nb, Q_BLOCK, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("nqkgd,nskd->nkgqs", qi, k) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nkgqs,nskd->nqkgd", p, v)

    o = jax.lax.map(block, (jnp.arange(nb), qb))       # (nb, n, Qb, KV, G, hd)
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(n, nb * Q_BLOCK, KV * G * hd)
    return o[:, :T]


def _dense_mlp(m, h, cfg, fp8):
    if cfg["mlp"] == "swiglu":
        a = jax.nn.silu(_linear(h, m["wi_gate"], fp8)) * _linear(
            h, m["wi_up"], fp8)
    else:                                              # GELU, tanh form
        a = jax.nn.gelu(_linear(h, m["wi_up"], fp8), approximate=True)
    return _linear(a, m["wo"], fp8)


def _experts(m, h, cfg, prompt_len, fp8):
    """Top-k routing with the gates renormalised over the k chosen.  Over
    the prompt, each expert takes at most C = ceil(S*k*cf/E) of a
    sequence's tokens, first come first served, as the configuration
    states; the rest of that expert's share of those tokens is dropped.
    Generated tokens are routed one at a time and never dropped.  Every
    expert is evaluated on every token and weighted by its gate (zero when
    not chosen), which is plain and needs no dispatch."""
    E, K = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_linear(h, m["router"], fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, K)
    gates = top / jnp.sum(top, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (n, T, K, E)
    chosen = onehot.sum(2)
    weight = jnp.einsum("ntk,ntke->nte", gates, onehot)
    cap = math.ceil(prompt_len * K * cfg["capacity_factor"] / E)
    rank = jnp.cumsum(chosen[:, :prompt_len], axis=1)
    keep = jnp.concatenate(
        [rank <= cap, jnp.ones_like(chosen[:, prompt_len:], bool)], axis=1)
    weight = jnp.where(keep, weight, 0.0)
    wg, wu, wo = (m[k].astype(jnp.float32) for k in ("wi_gate", "wi_up", "wo"))
    hx = h
    if fp8:
        hx = _fp8(h, -1)
        wg, wu, wo = _fp8(wg, 1), _fp8(wu, 1), _fp8(wo, 1)
    a = (jax.nn.silu(jnp.einsum("ntd,edf->ntef", hx, wg))
         * jnp.einsum("ntd,edf->ntef", hx, wu))
    if fp8:
        a = _fp8(a, -1)
    return jnp.einsum("ntef,efd,nte->ntd", a, wo, weight)


@functools.partial(jax.jit, static_argnames=("cfg_items", "prompt_len", "fp8"))
def _layer(blocks, l, x, *, cfg_items, prompt_len, fp8):
    cfg = dict(cfg_items)
    blk = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, l, keepdims=False), blocks)
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    n, T, _ = x.shape
    at = blk["attn"]
    h = _norm(blk, 1, x)
    q, k, v = (_linear(h, at[w], fp8) for w in ("wq", "wk", "wv"))
    if "bq" in at:
        q, k, v = (t + at[b].astype(jnp.float32)
                   for t, b in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(n, T, H, hd), cfg["rope_theta"])
    k = _rope(k.reshape(n, T, KV, hd), cfg["rope_theta"])
    v = v.reshape(n, T, KV, hd)
    if fp8:                                  # an fp8 KV cache, per entry
        k, v = _fp8(k, -1), _fp8(v, -1)
    o = _attention(q.reshape(n, T, KV, H // KV, hd), k, v,
                   cfg.get("sliding_window"))
    x = x + _linear(o, at["wo"], fp8)
    h = _norm(blk, 2, x)
    if "moe" in blk:
        return x + _experts(blk["moe"], h, cfg, prompt_len, fp8)
    return x + _dense_mlp(blk["mlp"], h, cfg, fp8)


@functools.partial(jax.jit, static_argnames=("vocab", "fp8"))
def _head(emb, final_norm, x, *, vocab, fp8):
    x = _rms(x, final_norm)
    w = emb["unembed"][:, :vocab] if "unembed" in emb else (
        emb["embed"][:vocab].T)
    return _linear(x, w, fp8)


def logits(params, cfg: dict, tokens, *, prompt_len: int, start: int,
           fp8: bool = False, rows_tokens: int = 8192):
    """Teacher-forced logits of ``tokens`` (n, T): (n, T - start, vocab)
    float32 for positions start..T-1.  ``prompt_len`` is where the prompt
    ends and generated tokens begin.  Sequences go through in groups of
    about ``rows_tokens`` tokens."""
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
            for l in range(cfg["num_hidden_layers"]):
                x = _layer(params["blocks"], l, x, cfg_items=cfg_items,
                           prompt_len=prompt_len, fp8=fp8)
            out.append(_head(params["embedding"], params["final_norm"],
                             x[:, start:], vocab=cfg["vocab_size"], fp8=fp8))
    return jnp.concatenate(out, axis=0)
