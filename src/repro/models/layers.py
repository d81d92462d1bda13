"""Shared model layers: params-with-logical-axes, norms, RoPE/M-RoPE,
GQA attention (train / prefill / decode, full + sliding window), MLPs.

Everything is pure-functional: ``init_*`` build parameter pytrees, ``*_fwd``
apply them.  Each init also records a parallel *axes tree* whose leaves are
tuples of logical axis names (e.g. ``("embed", "heads")``); the distribution
layer (repro.distributed.sharding) maps logical names to mesh axes, giving
per-architecture TP/FSDP/EP sharding without touching model code.

Logical axis vocabulary:
  "vocab"   embedding rows            -> model axis (TP)
  "embed"   the d_model dim           -> FSDP (data axis) on weights
  "heads"   q heads * head_dim        -> model axis (TP)
  "kv"      kv heads * head_dim       -> model if divisible, else replicated
  "ff"      MLP hidden                -> model axis (TP)
  "experts" MoE expert dim            -> model axis (EP)
  "layers"  stacked scan dim          -> never sharded
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import gather_fsdp
from repro.kernels.flash_attention import flash_attention

# The decoder's named scopes (``jax.named_scope``).  Each operation of a
# compiled prefill or decode step carries the scopes it ran under in its
# ``op_name`` metadata, so a profiler trace reads layer by layer: ``layers``
# is the scan over blocks (its own operations slice each layer's weights
# and cache out of the stacks), ``kv_write`` the cache writes and padding,
# ``attn_core`` attention over the keys.
SCOPES = ("embed", "layers", "norm", "attn_qkv", "kv_write", "attn_core",
          "attn_out", "mlp", "moe", "logits")

# ---------------------------------------------------------------------------
# Parameter factory with logical axes.
# ---------------------------------------------------------------------------


class ParamFactory:
    """Creates params and records logical axes in one pass."""

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16):
        self._key = key
        self.dtype = dtype
        self.params: dict[str, Any] = {}
        self.axes: dict[str, Any] = {}

    def _split(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def dense(self, name: str, shape: tuple[int, ...], axes: tuple,
              scale: float | None = None, dtype=None) -> None:
        assert len(shape) == len(axes)
        if scale is None:
            scale = shape[0] ** -0.5  # fan-in
        self.params[name] = (jax.random.normal(self._split(), shape,
                                               dtype or self.dtype) * scale)
        self.axes[name] = axes

    def zeros(self, name: str, shape: tuple[int, ...], axes: tuple,
              dtype=None) -> None:
        self.params[name] = jnp.zeros(shape, dtype or self.dtype)
        self.axes[name] = axes

    def ones(self, name: str, shape: tuple[int, ...], axes: tuple,
             dtype=None) -> None:
        self.params[name] = jnp.ones(shape, dtype or self.dtype)
        self.axes[name] = axes

    def const(self, name: str, shape: tuple[int, ...], axes: tuple,
              value: float, dtype=None) -> None:
        self.params[name] = jnp.full(shape, value, dtype or self.dtype)
        self.axes[name] = axes

    def sub(self, name: str) -> "ParamFactory":
        child = ParamFactory(self._split(), self.dtype)
        self.params[name] = child.params
        self.axes[name] = child.axes
        return child


def stack_layer_params(init_fn, key: jax.Array, num: int):
    """vmap an init over layer keys -> params stacked on a leading axis.

    Returns (stacked params, axes tree with "layers" prepended).
    """
    keys = jax.random.split(key, num)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, axes = init_fn(key)  # structure only
    axes = jax.tree_util.tree_map(
        lambda a: ("layers",) + tuple(a), axes,
        is_leaf=lambda x: isinstance(x, tuple))
    return params, axes


def maybe_remat(fn, policy: str):
    """Wrap a scan body in jax.checkpoint per the config's remat policy."""
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    return jax.checkpoint(fn, prevent_cse=False)  # "full"


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * w.astype(jnp.float32) + b.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and Qwen2-VL M-RoPE).
# ---------------------------------------------------------------------------


def _rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x, positions, theta: float = 1e4):
    """x: (B, S, H, D) with D even; positions: (B, S) absolute indices."""
    B, S, H, D = x.shape
    freqs = _rope_freqs(D, theta)                       # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_3d, theta: float = 1e6,
                sections: tuple[int, int, int] = (16, 24, 24)):
    """Qwen2-VL multimodal RoPE: the head dim is split into (temporal,
    height, width) sections, each rotated by its own position stream.

    x: (B, S, H, D); positions_3d: (B, S, 3).  ``sections`` are in
    half-dim units and must sum to D//2.
    """
    B, S, H, D = x.shape
    half = D // 2
    assert sum(sections) == half, "mrope sections must sum to head_dim/2"
    freqs = _rope_freqs(D, theta)                        # (half,)
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections),
                        total_repeat_length=half)        # (half,) in {0,1,2}
    pos = jnp.take_along_axis(
        positions_3d.astype(jnp.float32),                # (B,S,3)
        jnp.broadcast_to(sec_id[None, None, :], (B, S, half)).astype(jnp.int32),
        axis=2)                                          # (B,S,half)
    ang = pos * freqs[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA) with KV-cache support.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 1e4
    mrope: bool = False
    causal: bool = True
    window: int | None = None    # sliding window (None = full)
    scale: float | None = None   # score scale (None = 1/sqrt(head_dim))
    block_q: int = 512
    block_k: int = 512


def init_attention(key, cfg: AttnConfig, dtype=jnp.bfloat16):
    p = ParamFactory(key, dtype)
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p.dense("wq", (D, H * hd), ("embed", "heads"))
    p.dense("wk", (D, KV * hd), ("embed", "kv"))
    p.dense("wv", (D, KV * hd), ("embed", "kv"))
    p.dense("wo", (H * hd, D), ("heads", "embed"))
    if cfg.qkv_bias:
        p.zeros("bq", (H * hd,), ("heads",))
        p.zeros("bk", (KV * hd,), ("kv",))
        p.zeros("bv", (KV * hd,), ("kv",))
    return p.params, p.axes


def _qkv(params, x, cfg: AttnConfig, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ gather_fsdp(params["wq"], tp_dim=1)
    k = x @ gather_fsdp(params["wk"], tp_dim=1)
    v = x @ gather_fsdp(params["wv"], tp_dim=1)
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.mrope:
        pos3 = (positions[..., None].astype(jnp.int32)
                if positions.ndim == 2 else positions)
        if pos3.shape[-1] != 3:  # text-only stream: t=h=w=position
            pos3 = jnp.broadcast_to(pos3, (*pos3.shape[:-1], 3))
        q = apply_mrope(q, pos3, cfg.rope_theta, _mrope_sections(hd))
        k = apply_mrope(k, pos3, cfg.rope_theta, _mrope_sections(hd))
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mrope_sections(head_dim: int) -> tuple[int, int, int]:
    half = head_dim // 2
    t = half - 2 * (3 * half // 8)
    return (t, 3 * half // 8, 3 * half // 8)


def attention_fwd(params, x, cfg: AttnConfig, positions=None):
    """Full-sequence attention (training / prefill).  x: (B, S, D)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    with jax.named_scope("attn_qkv"):
        q, k, v = _qkv(params, x, cfg, positions)

    # Checkpoint the attention op: its chunked online-softmax carries are
    # recomputed in the backward instead of being saved per (layer x chunk)
    # — the jnp analogue of a flash-attention backward kernel.  Cuts train
    # temp memory ~10x at 4k seq (EXPERIMENTS.md §Perf iteration 6).
    attn = jax.checkpoint(
        lambda q, k, v: flash_attention(
            q, k, v, causal=cfg.causal, window=cfg.window, q_offset=0,
            scale=cfg.scale, block_q=cfg.block_q, block_k=cfg.block_k))
    with jax.named_scope("attn_core"):
        out = attn(q, k, v)
    with jax.named_scope("attn_out"):
        out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
        return out @ gather_fsdp(params["wo"], tp_dim=0), (k, v)


@jax.named_scope("kv_write")
def to_cache(kv, cache_len: int):
    """A prefill's keys or values, (..., B, S, KV, hd), as a decode cache:
    (..., B, KV, cache_len, hd), zero past S.  The cache keeps each head's
    positions together, the order in which decode's attention reads them,
    so the decode step reads its cache where it lies."""
    a = jnp.swapaxes(kv, -3, -2)
    pad = cache_len - a.shape[-2]
    if pad <= 0:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, 0)])


def attention_decode(params, x, cfg: AttnConfig, k_cache, v_cache,
                     kv_len: int, positions):
    """One-token decode against a filled cache, which it reads and does
    not write.

    x: (B, 1, D); k_cache/v_cache: (B, KV, S_cache, hd) (``to_cache``),
    where position p lies in slot p % S_cache (a ring once the cache is
    full: sliding-window layers keep a ring of size ``window``) and entries
    of positions before ``kv_len`` are valid roped keys.  The query attends
    over those entries and its own new entry, held apart.  Returns (out,
    k_new, v_new), the new entries (B, KV, 1, hd) in the cache's dtype; the
    caller writes them at ``kv_len`` with ``write_kv``, after its scan over
    layers, so that the stacked cache is written in place and never copied.
    """
    B = x.shape[0]
    with jax.named_scope("attn_qkv"):
        q, k_new, v_new = _qkv(params, x, cfg, positions)
        k_new = jnp.swapaxes(k_new, 1, 2).astype(k_cache.dtype)
        v_new = jnp.swapaxes(v_new, 1, 2).astype(v_cache.dtype)
    with jax.named_scope("attn_core"):
        out = _decode_attend(q, k_cache, v_cache, k_new, v_new, kv_len,
                             cfg.scale)
    with jax.named_scope("attn_out"):
        out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
        return out @ params["wo"], k_new, v_new


@jax.named_scope("kv_write")
def write_kv(cache, new, kv_len):
    """Write the new entries ``new`` (``cache``'s shape but for one
    position) at position ``kv_len``: slot ``kv_len % S`` of a cache of S
    positions."""
    slot = jnp.asarray(kv_len) % cache.shape[-2]
    return jax.lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype),
                                               slot, axis=cache.ndim - 2)


def _decode_attend(q, k_cache, v_cache, k_new, v_new, kv_len, scale=None):
    """Non-causal attention of one query over the cache's entries of
    positions before ``kv_len`` and the new entry: one fp32 softmax over
    both, of scores scaled by ``scale`` (1/sqrt(hd) by default).  Once the
    ring is full, the slot the new entry will overwrite (``kv_len % S``)
    holds the oldest position, which is masked."""
    from repro.distributed.sharding import constrain_kv_layout
    B, _, H, hd = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32) * (hd ** -0.5 if scale is None else scale)
    kf = constrain_kv_layout(k_cache.astype(jnp.float32))
    vf = constrain_kv_layout(v_cache.astype(jnp.float32))
    qg = qf.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kf)           # (B,KV,G,S)
    kpos = jnp.arange(S)
    old = (kpos < kv_len) & (kpos != jnp.asarray(kv_len) % S)
    s = jnp.where(old[None, None, None, :], s, -1e30)
    kn = k_new[:, :, 0].astype(jnp.float32)             # (B,KV,hd)
    vn = v_new[:, :, 0].astype(jnp.float32)
    s_new = jnp.einsum("bkgd,bkd->bkg", qg, kn)[..., None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    p, p_new = jnp.exp(s - m), jnp.exp(s_new - m)
    den = jnp.sum(p, axis=-1, keepdims=True) + p_new
    o = jnp.einsum("bkgs,bksd->bkgd", p, vf) + p_new * vn[:, :, None, :]
    return (o / den).reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs.
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, kind: str = "swiglu",
             dtype=jnp.bfloat16):
    p = ParamFactory(key, dtype)
    if kind in ("swiglu", "geglu"):
        p.dense("wi_gate", (d_model, d_ff), ("embed", "ff"))
        p.dense("wi_up", (d_model, d_ff), ("embed", "ff"))
    else:  # "gelu" / "relu": plain 2-layer MLP
        p.dense("wi_up", (d_model, d_ff), ("embed", "ff"))
    p.dense("wo", (d_ff, d_model), ("ff", "embed"))
    return p.params, p.axes


def mlp_fwd(params, x, kind: str = "swiglu"):
    if kind == "swiglu":
        h = (jax.nn.silu(x @ gather_fsdp(params["wi_gate"], tp_dim=1))
             * (x @ gather_fsdp(params["wi_up"], tp_dim=1)))
    elif kind == "geglu":
        h = (jax.nn.gelu(x @ gather_fsdp(params["wi_gate"], tp_dim=1),
                         approximate=True)
             * (x @ gather_fsdp(params["wi_up"], tp_dim=1)))
    elif kind == "gelu":
        h = jax.nn.gelu(x @ gather_fsdp(params["wi_up"], tp_dim=1),
                        approximate=True)
    elif kind == "relu":
        h = jax.nn.relu(x @ gather_fsdp(params["wi_up"], tp_dim=1))
    else:
        raise ValueError(kind)
    return h @ gather_fsdp(params["wo"], tp_dim=0)


# ---------------------------------------------------------------------------
# Embedding / unembedding.
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, tie: bool = False,
                   dtype=jnp.bfloat16):
    p = ParamFactory(key, dtype)
    p.dense("embed", (vocab, d_model), ("vocab", "embed"), scale=0.02)
    if not tie:
        p.dense("unembed", (d_model, vocab), ("embed", "vocab"))
    return p.params, p.axes


def embed_fwd(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def unembed_fwd(params, x, scaling: float = 1.0):
    """Logits of ``x``, divided by ``scaling`` while still in float32 (one
    rounding to ``x``'s dtype, after the division)."""
    w = params["unembed"] if "unembed" in params else params["embed"].T
    if scaling == 1.0:
        return x @ w
    out = jnp.dot(x, w, preferred_element_type=jnp.float32) / scaling
    return out.astype(x.dtype)
