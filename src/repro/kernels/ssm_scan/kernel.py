"""Pallas TPU chunked gated-linear-attention scan.

The sequential recurrence is reformulated per chunk of length C so the MXU
does the work (three (C x K)@(K x V)-class matmuls per chunk) instead of S
rank-1 updates:

  within a chunk, with running log-decay  a_i = sum_{j<=i} w_j :
    q~_i = q_i * exp(a_i)            k~_j = k_j * exp(-a_j)
    intra = causal_mask(q~ k~^T) v
    cross = q~ S_chunk_start
    S_next = exp(a_{C-1}) * S + (k~ * exp(a_{C-1}))^T v

Numerical safety: exp(-a_j) explodes for strong decay, so w is clamped to
[-CLAMP, 0] and the chunk size bounds total in-chunk decay; accumulation is
fp32 throughout (VMEM scratch state).

Grid: (B*H, S/C) with the chunk axis sequential ("arbitrary") carrying the
(K, V) state in VMEM scratch.  Block shapes (C, K)/(C, V) are MXU-aligned
for C, K, V multiples of 128 (K=64 still maps acceptably via lane packing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLAMP = 30.0


def _gla_kernel(q_ref, k_ref, v_ref, w_ref, o_ref, sfin_ref, state_ref, *,
                nchunks: int, C: int, K: int, V: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)            # (C, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)            # (C, V)
    w = jnp.clip(w_ref[0].astype(jnp.float32), -CLAMP, 0.0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    causal = jj <= ii
    # Running log decay a = cumsum(w) as a matmul by a lower-triangular
    # ones matrix: Mosaic has no cumsum.  HIGHEST keeps the f32 sum exact
    # enough for exp(-a) (a bf16 pass would round w at 2^-8).
    tri = jnp.where(causal, 1.0, 0.0).astype(jnp.float32)
    a = jax.lax.dot_general(tri, w, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (C, K)
    ea = jnp.exp(a)
    q_t = q * ea                                # q~
    # fp32 exponent guard (see ops.gla_scan_xla): saturate exp(-a) at e^60.
    k_t = k * jnp.exp(jnp.minimum(-a, 60.0))    # k~
    s = jax.lax.dot_general(q_t, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, C)
    s = jnp.where(causal, s, 0.0)
    intra = jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (C, V)
    cross = jax.lax.dot_general(q_t, state_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = (intra + cross).astype(o_ref.dtype)
    # State update: S' = diag(exp(a_last)) S + (k~ * exp(a_last))^T v.
    # exp(a_last) is needed as a row (scaling k~'s columns) and as a column
    # (scaling S's rows); the column is the chunk sum of w, taken as a
    # matmul so no lane-to-sublane transpose is needed.
    ea_last_row = ea[C - 1:C, :]                 # (1, K)
    a_last_col = jax.lax.dot_general(
        w, jnp.ones((C, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # (K, 1)
    k_fin = k_t * ea_last_row
    state_ref[...] = (state_ref[...] * jnp.exp(a_last_col)
                      + jax.lax.dot_general(k_fin, v, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))

    @pl.when(c == nchunks - 1)
    def _fin():
        sfin_ref[0] = state_ref[...].astype(sfin_ref.dtype)


def gla_scan_pallas(q, k, v, w, chunk: int = 128, interpret: bool = False):
    """q/k/w: (B,H,S,K); v: (B,H,S,V) -> (o, final_state (B,H,K,V) fp32)."""
    B, H, S, K = q.shape
    V = v.shape[-1]
    C = min(chunk, S)
    assert S % C == 0, "pad sequence to chunk multiple"
    nchunks = S // C
    BH = B * H
    qr = q.reshape(BH, S, K)
    kr = k.reshape(BH, S, K)
    vr = v.reshape(BH, S, V)
    wr = w.reshape(BH, S, K)

    kernel = functools.partial(_gla_kernel, nchunks=nchunks, C=C, K=K, V=V)
    o, sfin = pl.pallas_call(
        kernel,
        grid=(BH, nchunks),
        in_specs=[
            pl.BlockSpec((1, C, K), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, C, K), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, C, V), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, C, K), lambda h, c: (h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, V), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, K, V), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, V), q.dtype),
            jax.ShapeDtypeStruct((BH, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr, wr)
    return o.reshape(B, H, S, V), sfin.reshape(B, H, K, V)
