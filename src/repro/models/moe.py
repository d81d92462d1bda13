"""Mixture-of-Experts layer (granite-moe, dbrx): float32 routing, then the
experts as grouped matmuls over the assignments sorted by expert, or, in a
decode step of few tokens, as batched dots over every expert.

The router's logits accumulate in float32; top-k picks from them and the
gates are the softmax of the k chosen logits.  Then one of two paths,
chosen from the input's shape:

- Grouped (prefill, training, and a decode step of more than
  ``DENSE_MAX_TOKENS`` tokens).  The B*S*k token->expert assignments are
  sorted by expert (stably, so each expert's assignments keep token
  order), and ``jax.lax.ragged_dot`` runs the gate, up and down
  projections over the sorted rows, each expert's weights over its own run
  of rows: one grouped matmul each (on a TPU a Mosaic kernel), with no
  (tokens, experts, ...) dispatch tensor and no capacity buffers.  The
  results go back to token order and are summed under the gates.
- Dense (S == 1 and B <= ``DENSE_MAX_TOKENS``).  Every expert runs over
  the step's B tokens as batched dots against the (E, D, F) and (E, F, D)
  stacks, which the compiler reads where they lie in the scan's stacked
  weights, once each, with no copy of a layer's experts; an (E, B) float32
  matrix holds each token's k gates on its experts and exact zeros
  elsewhere, and the down projection's float32 outputs are summed over
  experts under it.  At B tokens the dots do B FLOPs per bf16 weight byte,
  at most 128 against the ~240 at which a v5e turns from bandwidth-bound
  to compute-bound, so the E/k times more FLOPs cost nothing and the
  step's time is one read of the weights.  The grouped kernels could not
  read the stack in place: their operand has to be a buffer, so each
  layer's slice was copied first.

``capacity_factor=None`` is dropless.  A number keeps capacity-based
routing: within each sequence, expert e takes at most
C = ceil(S * k * capacity_factor / E) of its assignments, first come first
served, and the rest are dropped (their gate zeroed).  That is a mask on
the same sorted assignments, not a second path.  A decode step (S = 1)
drops nothing, since a token's k experts are distinct and C >= 1; so the
dense path takes no mask.

Under a mesh the layer runs shard by shard (``sharding.per_shard_experts``):
each data shard routes only its own tokens, so no sort or gather crosses
data shards, and each model shard computes its slice of d_ff.

Named scopes, inside the caller's ``moe``: ``moe_route`` (router and top-k;
on the grouped path also the sort, group sizes and the gather of the
sorted rows), ``moe_experts`` (the three grouped matmuls; on the dense
path the three batched dots and the gated sum over experts, which the
compiler fuses with the down projection) and ``moe_combine`` (grouped:
back to token order and the gated sum; dense: the (E, B) gate matrix).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import per_shard_experts
from repro.models.layers import ParamFactory

# Each weight's d_ff dim, the one a model shard holds a slice of.
FF_DIMS = {"wi_gate": 2, "wi_up": 2, "wo": 1}
# The most tokens a decode step (S == 1) runs through every expert as batched
# dots: T tokens do T FLOPs per bf16 weight byte, under the ~240 (197 TFLOP/s
# over 819 GB/s) at which a v5e turns compute-bound, so the dots stay bound by
# one read of the weights.
DENSE_MAX_TOKENS = 128


def init_moe(key, d_model: int, d_ff: int, num_experts: int, top_k: int,
             kind: str = "swiglu", dtype=jnp.bfloat16):
    p = ParamFactory(key, dtype)
    E = num_experts
    p.dense("router", (d_model, E), ("embed", None), scale=0.02)
    if kind in ("swiglu", "geglu"):
        p.dense("wi_gate", (E, d_model, d_ff), ("experts", "embed", "ff"))
        p.dense("wi_up", (E, d_model, d_ff), ("experts", "embed", "ff"))
    else:
        p.dense("wi_up", (E, d_model, d_ff), ("experts", "embed", "ff"))
    p.dense("wo", (E, d_ff, d_model), ("experts", "ff", "embed"))
    return p.params, p.axes


def moe_fwd(params, x, *, num_experts: int, top_k: int,
            kind: str = "swiglu", capacity_factor: float | None = 1.25):
    """x: (B, S, D) -> (out, aux); aux holds the Switch-style load-balance
    loss (``aux_loss``) and the share of assignments dropped
    (``dropped_frac``, 0 when dropless)."""
    layer = functools.partial(_moe_local, num_experts=num_experts,
                              top_k=top_k, kind=kind,
                              capacity_factor=capacity_factor)
    out, stats = per_shard_experts(layer, params, x, FF_DIMS)
    # the loss from the whole batch's shares, which equal-sized shards'
    # means average to
    return out, {"aux_loss": num_experts * jnp.sum(stats["fe"] * stats["me"]),
                 "dropped_frac": stats["dropped_frac"]}


def route(params, x, top_k: int):
    """float32 router logits (B, S, E), and the top-k experts (B, S, k)
    with their gates, the softmax of the k chosen logits."""
    logits = jnp.dot(x, params["router"], preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(logits, top_k)
    return logits, idx, jax.nn.softmax(top, axis=-1)


def _moe_local(params, x, *, num_experts, top_k, kind, capacity_factor):
    B, S, D = x.shape
    E, K = num_experts, top_k
    T, A = B * S, B * S * K
    dense = S == 1 and T <= DENSE_MAX_TOKENS

    def hidden(dot):
        """The activation of the gate and up projections, each ``dot(w)``."""
        if kind in ("swiglu", "geglu"):
            act = jax.nn.silu if kind == "swiglu" else functools.partial(
                jax.nn.gelu, approximate=True)
            return act(dot(params["wi_gate"])) * dot(params["wi_up"])
        return jax.nn.gelu(dot(params["wi_up"]), approximate=True)

    with jax.named_scope("moe_route"):
        logits, idx, gates = route(params, x, K)
    if dense:
        with jax.named_scope("moe_combine"):
            # each token's k gates on its experts, exactly 0 elsewhere
            comb = jnp.zeros((T, E), jnp.float32).at[
                jnp.arange(T)[:, None], idx.reshape(T, K)].set(
                gates.reshape(T, K)).T                          # (E, T)
        with jax.named_scope("moe_experts"):
            xe = jnp.broadcast_to(x.reshape(T, D), (E, T, D))
            h = hidden(lambda w: jnp.einsum("etd,edf->etf", xe, w))
            y = jnp.einsum("etf,efd->etd", h, params["wo"],
                           preferred_element_type=jnp.float32)
            out = jnp.sum(y * comb[..., None], axis=0)
            out = out.astype(x.dtype).reshape(B, S, D)
        dropped = jnp.zeros((), jnp.float32)
    else:
        with jax.named_scope("moe_route"):
            flat_exp = idx.reshape(A)
            order = jnp.argsort(flat_exp, stable=True)
            sexp = flat_exp[order]
            tok = order // K                   # each sorted row's token
            sizes = jnp.diff(jnp.searchsorted(
                sexp, jnp.arange(E + 1, dtype=sexp.dtype))).astype(jnp.int32)
            keep = jnp.ones((A,), bool)
            if capacity_factor is not None:
                # rank within (sequence, expert); sorted keys stay sorted
                run = sexp * B + tok // S
                rank = jnp.arange(A) - jnp.searchsorted(run, run, side="left")
                keep = rank < max(1, math.ceil(S * K * capacity_factor / E))
            xs = x.reshape(T, D)[tok]                           # (A, D)

        with jax.named_scope("moe_experts"):
            h = hidden(lambda w: jax.lax.ragged_dot(xs, w, sizes))
            ys = jax.lax.ragged_dot(h, params["wo"], sizes)     # (A, D)

        with jax.named_scope("moe_combine"):
            back = jnp.argsort(order)         # token order from sorted order
            y = ys[back].reshape(B, S, K, D).astype(jnp.float32)
            w = gates * keep[back].reshape(B, S, K)
            out = jnp.einsum("bskd,bsk->bsd", y, w).astype(x.dtype)
        dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))

    # the load-balance loss's two shares per expert: router probability,
    # and tokens whose first choice it is
    me = jax.nn.softmax(logits, axis=-1).mean(axis=(0, 1))
    fe = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32).mean(axis=(0, 1))
    return out, {"me": me, "fe": fe, "dropped_frac": dropped}
