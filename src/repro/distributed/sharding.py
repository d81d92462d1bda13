"""Sharding rules: logical parameter axes -> mesh axes.

The model zoo annotates every parameter with logical axis names
(repro.models.layers docstring).  This module turns those into
``PartitionSpec`` trees for a given mesh and workload kind:

  * **TP**   — "vocab"/"heads"/"ff"/"experts" shard over the ``model`` axis.
  * **FSDP** — "embed" (the d_model dim of weights) shards over ``data``;
    GSPMD inserts the per-layer all-gathers, which overlap with compute
    under the layer scan.  Optimizer state inherits parameter specs, so it
    is automatically ZeRO-sharded.
  * **DP**   — batch dims of inputs/activations shard over ``("pod","data")``
    (or just ``data`` single-pod).
  * **SP**   — for decode shapes whose batch is smaller than the data axis
    (long_500k: batch=1), KV-cache *sequence* dims shard over ``data``
    (sequence parallelism); attention contractions then reduce over it.

Uneven dims (e.g. 8 kv heads over a 16-way model axis, vocab 256206) rely
on GSPMD's implicit padding — correct, if sometimes wasteful; the §Perf
hillclimb addresses the wasteful cases.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig

# ---------------------------------------------------------------------------
# Activation-sharding context.
#
# Weight shardings dominate GSPMD propagation: with FSDP weights (d_model
# sharded over 'data') and only a tiny int32 token input carrying the batch
# sharding, XLA picks feature-sharded/batch-REPLICATED activations and
# all-reduces full-global-batch tensors every layer (measured 52-128 GiB
# per op on gemma3 train_4k — EXPERIMENTS.md §Perf iteration 3).  Models
# therefore pin activations to batch sharding at layer boundaries via
# ``constrain_batch``; the launcher scopes the mesh with
# ``activation_sharding_scope``.
# ---------------------------------------------------------------------------

_ACT_CTX = threading.local()


@contextlib.contextmanager
def activation_sharding_scope(mesh: Mesh, mode: str = "train",
                              skip_axes: frozenset = frozenset()):
    """mode="train": batch-pin activations; mode="decode": only cache/head
    layout pins apply (batch pinning hurts the tiny decode activations).
    ``skip_axes``: mesh axes that are MANUAL in an enclosing shard_map (a
    with_sharding_constraint may not name them)."""
    prev = (getattr(_ACT_CTX, "mesh", None),
            getattr(_ACT_CTX, "mode", "train"),
            getattr(_ACT_CTX, "skip_axes", frozenset()))
    _ACT_CTX.mesh = mesh
    _ACT_CTX.mode = mode
    _ACT_CTX.skip_axes = skip_axes
    try:
        yield
    finally:
        _ACT_CTX.mesh, _ACT_CTX.mode, _ACT_CTX.skip_axes = prev


def constrain_batch(x):
    """Pin dim 0 of an activation to the data-parallel axes (no-op outside
    an activation_sharding_scope or when the batch doesn't divide)."""
    mesh = getattr(_ACT_CTX, "mesh", None)
    if (mesh is None or x.ndim < 2
            or getattr(_ACT_CTX, "mode", "train") == "decode"):
        return x
    dp = dp_axes(mesh)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    if n <= 1 or x.shape[0] % n != 0:
        return x
    # Non-batch dims stay UNCONSTRAINED: a None would FORCE replication
    # (e.g. gathering the full d_ff of MoE hiddens — §Perf iteration 9).
    spec = P(dp, *([P.UNCONSTRAINED] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def per_shard_attention(attn):
    """Run ``attn(q, k, v)`` on (B, S, H, D) arrays shard by shard.

    GSPMD cannot partition a Mosaic (Pallas TPU) kernel, so under a
    multi-device scope the kernel runs inside a ``shard_map``: batch over
    the data-parallel axes and heads over ``model`` where they divide,
    replicated where they do not.  Attention is independent per sequence
    and per kv-head group, so the shards need no collective."""
    mesh = getattr(_ACT_CTX, "mesh", None)
    if mesh is None or mesh.size == 1:
        return attn

    def sharded(q, k, v):
        dp = dp_axes(mesh)
        bax = dp if dp and q.shape[0] % _axis_size(mesh, dp) == 0 else None
        hax = ("model" if "model" in mesh.axis_names
               and k.shape[2] % mesh.shape["model"] == 0 else None)
        spec = P(bax, None, hax, None)
        manual = set(mesh.axis_names) - getattr(_ACT_CTX, "skip_axes",
                                                frozenset())
        return jax.shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, axis_names=manual,
                             check_vma=False)(q, k, v)

    return sharded


def per_shard_experts(layer, params, x, ff_dims: dict[str, int]):
    """``layer(params, x) -> (out, aux)``, an expert layer, run shard by
    shard under a multi-device scope (``shard_map``): x's batch over the
    data-parallel axes where it divides, and each weight named in
    ``ff_dims`` sliced over ``model`` along its d_ff dim, where that
    divides.  Each data shard routes and sorts only its own tokens; each
    model shard's partial output is summed over ``model``, and ``aux``'s
    arrays are averaged over the shards.  Outside a scope, or on one
    device, ``layer`` runs as it is."""
    mesh = getattr(_ACT_CTX, "mesh", None)
    if mesh is None or mesh.size == 1:
        return layer(params, x)
    manual = frozenset(mesh.axis_names) - getattr(_ACT_CTX, "skip_axes",
                                                  frozenset())
    dp = dp_axes(mesh)
    bax = dp if dp and x.shape[0] % _axis_size(mesh, dp) == 0 else None
    tp = "model" in manual and all(
        params[k].shape[d] % mesh.shape["model"] == 0
        for k, d in ff_dims.items() if k in params)
    pspecs = {k: P(*("model" if tp and i == ff_dims.get(k) else None
                     for i in range(v.ndim))) for k, v in params.items()}
    mode = getattr(_ACT_CTX, "mode", "train")

    def body(p, xs):
        # every axis is manual in here: no sharding constraint may name one
        with activation_sharding_scope(mesh, mode,
                                       skip_axes=frozenset(mesh.axis_names)):
            out, aux = layer(p, xs)
        if tp:
            out = jax.lax.psum(out, "model")
        return out, jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, tuple(manual)), aux)

    return jax.shard_map(body, mesh=mesh, in_specs=(pspecs, P(bax)),
                         out_specs=(P(bax), P()), axis_names=set(manual),
                         check_vma=False)(params, x)


def constrain_logits(x):
    """Logits: batch over the DP axes AND vocab over the model axis.
    (Batch-only pinning replicates the vocab dim — a 64 GiB/device fp32
    tensor at 262k vocab; §Perf iteration 7.)"""
    mesh = getattr(_ACT_CTX, "mesh", None)
    if mesh is None or x.ndim < 2:
        return x
    dp = dp_axes(mesh)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    model_ax = "model" if "model" in mesh.axis_names else None
    if model_ax and x.shape[-1] % mesh.shape["model"] != 0:
        model_ax = None
    bax = dp if (n > 1 and x.shape[0] % n == 0) else None
    if bax is None and model_ax is None:
        return x
    spec = P(bax, *([P.UNCONSTRAINED] * (x.ndim - 2)), model_ax)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def gather_fsdp(w, tp_dim: int | None = None):
    """Explicit just-in-time FSDP: unshard a weight's 'data'-sharded dim
    right before use, keeping the TP dim on 'model'.

    Left to itself, GSPMD often resolves (x batch-'data') @ (w d_model-
    'data') by ALL-REDUCING the f32 activations over 'data' (~0.7 GiB/layer
    on gemma3) instead of all-gathering the ~15 MB weight slice — §Perf
    iteration 12.  Train mode only: serving keeps weights 2D-stationary.
    """
    mesh = getattr(_ACT_CTX, "mesh", None)
    if (mesh is None or getattr(_ACT_CTX, "mode", "train") != "train"
            or "data" not in mesh.axis_names):
        return w
    model_ax = "model" if "model" in mesh.axis_names else None
    if model_ax and tp_dim is not None and w.shape[tp_dim] % mesh.shape["model"]:
        model_ax = None
    entries = [None] * w.ndim
    if tp_dim is not None and model_ax:
        entries[tp_dim] = model_ax
    return jax.lax.with_sharding_constraint(
        w, NamedSharding(mesh, P(*entries)))


def constrain_kv_layout(x):
    """Pin a (..., KV, S, hd) decode cache so the model axis sits on
    whichever of KV and hd divides — stops the SPMD partitioner from
    flip-flopping cache layouts between the decode-attention einsums (its
    "involuntary full rematerialization" copies the 0.5 GiB cache per
    layer; §Perf iteration 11)."""
    mesh = getattr(_ACT_CTX, "mesh", None)
    if mesh is None or x.ndim < 3 or "model" not in mesh.axis_names:
        return x
    m = mesh.shape["model"]
    kv_ax = "model" if x.shape[-3] % m == 0 else None
    hd_ax = None if kv_ax else ("model" if x.shape[-1] % m == 0 else None)
    if kv_ax is None and hd_ax is None:
        return x
    spec = P(*([P.UNCONSTRAINED] * (x.ndim - 3)), kv_ax, P.UNCONSTRAINED,
             hd_ax)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

# Logical-axis -> mesh-axis rule tables.


def param_rules(mesh: Mesh, cfg: ModelConfig, fsdp: bool = True) -> dict:
    axes = mesh.axis_names
    model_ax = "model" if "model" in axes else None
    data_ax = "data" if ("data" in axes and fsdp) else None
    rules = {
        "vocab": model_ax,
        "embed": data_ax,     # FSDP on the d_model dim of weights
        "heads": model_ax,
        "kv": model_ax,
        "ff": model_ax,
        # Experts are REPLICATED across the model axis; their d_ff is
        # TP-sharded and d_model FSDP-sharded instead, so MoE dispatch
        # never crosses the model axis (see models/moe.py docstring).
        "experts": None,
        "layers": None,
        None: None,
    }
    return rules


def dp_axes(mesh: Mesh) -> tuple:
    skip = getattr(_ACT_CTX, "skip_axes", frozenset())
    return tuple(a for a in ("pod", "data")
                 if a in mesh.axis_names and a not in skip)


def spec_from_axes(axes_leaf: tuple, rules: dict) -> P:
    """Map logical axes to mesh axes; a mesh axis may appear only once per
    spec, so later duplicates are dropped (first occurrence wins — e.g. MoE
    (experts, embed, ff) keeps EP on 'model' and leaves 'ff' replicated).

    Embedding tables ("vocab" present) keep ONLY the vocab TP sharding:
    FSDP-sharding their d_model dim puts the partition on the un/embed
    matmuls' contraction path, which XLA SPMD resolves by all-gathering
    full-global-batch logits (measured 128 GiB/step on gemma3 train_4k —
    EXPERIMENTS.md §Perf iteration 2)."""
    used: set = set()
    out = []
    for a in axes_leaf:
        entry = rules.get(a)
        if a == "embed" and "vocab" in axes_leaf:
            entry = None
        names = (entry if isinstance(entry, (tuple, list))
                 else [entry] if entry else [])
        if any(n in used for n in names):
            entry = None
            names = []
        used.update(names)
        out.append(entry)
    return P(*out)


def param_specs(axes_tree: Any, mesh: Mesh, cfg: ModelConfig,
                fsdp: bool = True) -> Any:
    rules = param_rules(mesh, cfg, fsdp=fsdp)
    return jax.tree_util.tree_map(
        lambda a: spec_from_axes(a, rules), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def param_shardings(axes_tree: Any, mesh: Mesh, cfg: ModelConfig,
                    fsdp: bool = True) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_specs(axes_tree, mesh, cfg, fsdp),
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Input / batch specs.
# ---------------------------------------------------------------------------


def batch_specs(mesh: Mesh, shape: ShapeConfig, cfg: ModelConfig) -> dict:
    """PartitionSpec per input-spec key for a workload cell."""
    dp = dp_axes(mesh)
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    batch_shardable = shape.global_batch % ndp == 0 and shape.global_batch >= ndp
    bax = dp if batch_shardable else None
    if shape.kind in ("train", "prefill"):
        out = {"tokens": P(bax, None), "labels": P(bax, None),
               "frames": P(bax, None, None), "embeds": P(bax, None, None)}
        if not batch_shardable:
            # SP fallback: shard the sequence dim instead.
            out = {"tokens": P(None, dp), "labels": P(None, dp),
                   "frames": P(None, dp, None), "embeds": P(None, dp, None)}
        return out
    # decode
    seq_ax = None if batch_shardable else "data"
    return {"token": P(bax, None), "kv_len": P(),
            "cache": _CacheSpecRule(bax, seq_ax)}


class _CacheSpecRule:
    """Marker: cache specs are derived per-leaf (see cache_specs)."""

    def __init__(self, batch_ax, seq_ax):
        self.batch_ax = batch_ax
        self.seq_ax = seq_ax


def cache_specs(cache_tree: Any, mesh: Mesh, cfg: ModelConfig,
                shape: ShapeConfig) -> Any:
    """Per-leaf PartitionSpec for KV caches / SSM states, by key pattern.

    Leaf layouts (registry); decode caches keep each KV head's positions
    together, the order decode's attention reads (``layers.to_cache``):
      k/v                (L, B, KV, S, hd)
      global_k/v         (G, B, KV, S, hd)
      local_k/v          (G, g-1, B, KV, W, hd)
      tail_k/v           (T, B, KV, W, hd)
      attn_k/v (hybrid)  (G, B, KV, S, hd)
      cross_k/v          (L, B, S_enc, KV, hd)   read by the flash kernel
      groups_conv        (G, E, B, K-1, d_inner)
      groups_gla         (G, E, B, H, state, hd)
      tail_conv/tail_gla (T, B, ...)
      rwkv state tuple   ((L,B,1,D), (L,B,H,hd,hd), (L,B,1,D))
    """
    dp = dp_axes(mesh)
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    batch_shardable = shape.global_batch % ndp == 0 and shape.global_batch >= ndp
    bax = dp if batch_shardable else None
    seq_ax = None if batch_shardable else "data"
    model_ax = "model" if "model" in mesh.axis_names else None

    msize = mesh.shape.get("model", 1) if model_ax else 1

    def kv_hd_axes(kv_dim: int, hd_dim: int):
        """Place the model axis on whichever of (kv heads, head_dim) divides."""
        if kv_dim % msize == 0:
            return model_ax, None
        if hd_dim % msize == 0:
            return None, model_ax
        return None, None

    def leaf_spec(path, leaf) -> P:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        nd = leaf.ndim
        if "conv" in name:           # (..., B, K-1, d_inner)
            return P(*([None] * (nd - 3)), bax, None, model_ax)
        if "gla" in name:            # (..., B, H, state, hd)
            return P(*([None] * (nd - 4)), bax, model_ax, None, None)
        if nd == 6:                  # (G, g-1, B, KV, W, hd)
            kv_ax, hd_ax = kv_hd_axes(leaf.shape[3], leaf.shape[5])
            return P(None, None, bax, kv_ax, None, hd_ax)
        if nd == 5 and "cross" in name:  # (L, B, S_enc, KV, hd)
            kv_ax, hd_ax = kv_hd_axes(leaf.shape[3], leaf.shape[4])
            sax = seq_ax if leaf.shape[2] > 4096 else None
            return P(None, bax, sax, kv_ax, hd_ax)
        if nd == 5 and any(t in name for t in ("k", "v")):
            # (L/G/T, B, KV, S-or-W, hd)
            kv_ax, hd_ax = kv_hd_axes(leaf.shape[2], leaf.shape[4])
            sax = seq_ax if leaf.shape[3] > 4096 else None
            return P(None, bax, kv_ax, sax, hd_ax)
        # rwkv tuple leaves: (L,B,1,D) or (L,B,H,hd,hd)
        if nd == 4:
            return P(None, bax, None, model_ax)
        if nd == 5:
            return P(None, bax, model_ax, None, None)
        return P(*([None] * max(0, nd - 2)), bax, None) if nd >= 2 else P(None)

    specs = jax.tree_util.tree_map_with_path(leaf_spec, cache_tree)
    return sanitize_tree(specs, cache_tree, mesh)


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def sanitize_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide (pjit args must divide).

    e.g. 4 kv heads over a 16-way model axis -> replicated; the hillclimb
    replaces such cases with a better placement rather than padding.
    """
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is not None and dim % _axis_size(mesh, entry) != 0:
            entry = None
        out.append(entry)
    return P(*out)


def sanitize_tree(spec_tree: Any, shape_tree: Any, mesh: Mesh) -> Any:
    """Apply sanitize_spec leaf-wise (shape_tree: arrays/ShapeDtypeStructs)."""
    return jax.tree_util.tree_map(
        lambda s, x: sanitize_spec(s, x.shape, mesh),
        spec_tree, shape_tree, is_leaf=lambda x: isinstance(x, P))
