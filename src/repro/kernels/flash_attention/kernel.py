"""Pallas TPU flash-attention kernel (forward).

Tiled online-softmax attention with GQA, causal masking, and sliding-window
support.  TPU-codesign notes:

  * Grid is ``(batch*kv_heads, q_blocks, k_blocks)`` with the k axis
    innermost and declared ``arbitrary`` so the fp32 accumulators in VMEM
    scratch carry across k iterations (output block revisiting).
  * Block shapes default to (128, head_dim) — MXU-aligned on the matmul dims
    (multiples of 128 on the contraction and lane axes).
  * All q heads of one kv head (the GQA group G) are processed together:
    the q block is (G*bq, D) so the group shares the k/v tiles in VMEM —
    this is the zero-copy principle applied to VMEM: k/v tiles are fetched
    once per group rather than once per query head.
  * Fully-masked tiles (k beyond the causal frontier or before the window)
    are skipped with ``pl.when`` so the causal kernel does ~S^2/2 work.

VMEM budget per step (defaults, D=128, bq=bk=128, G<=8):
  q (G*128*128*2B = 256K max) + k/v (64K) + acc (G*128*128*4B) ~ 1.2 MB << 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, window: int | None,
               q_offset: int, bq: int, bk: int, nk: int, k_limit: int | None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Positions of this tile.  q rows are (G, bq) flattened; all G heads of
    # the group share q positions.
    q_start = qi * bq + q_offset
    k_start = ki * bk

    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # (G*bq, D)
        k = k_ref[0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0].astype(jnp.float32)              # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G*bq, bk)
        # Tile rows are (G, bq) flattened g-major: row r -> head g = r // bq,
        # query index r % bq.  All G heads share the same query positions.
        qpos = q_start + (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % bq)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, dtype=bool)
        if k_limit is not None:  # keys padded up to a block multiple
            mask &= kpos < k_limit
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                            # (G*bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (G*bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal or window is not None:
        # Tile-level skip: entirely above the causal diagonal, or entirely
        # left of the earliest window position.
        q_last = q_start + bq - 1
        needed = k_start <= q_last
        if window is not None:
            needed = jnp.logical_and(needed, k_start + bk > q_start - (window - 1))
        pl.when(needed)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           q_offset: int | None = None,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D).

    Sequences are padded up to block multiples: padded queries are sliced
    off the output and padded keys are masked in the kernel."""
    B, Sq0, Hq, D = q.shape
    _, Sk0, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if q_offset is None:
        q_offset = Sk0 - Sq0
    bq = min(block_q, Sq0)
    bk = min(block_k, Sk0)
    pad_seq = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0), (0, 0)))
    q = pad_seq(q, (-Sq0) % bq)
    k = pad_seq(k, (-Sk0) % bk)
    v = pad_seq(v, (-Sk0) % bk)
    Sq, Sk = q.shape[1], k.shape[1]
    k_limit = Sk0 if Sk != Sk0 else None
    nq, nk = Sq // bq, Sk // bk
    # Reorder to (B*Hkv, ...) with the G q-heads of each kv head contiguous.
    qr = (q.transpose(0, 2, 1, 3)                        # (B, Hq, Sq, D)
           .reshape(B, Hkv, G, Sq, D)
           .reshape(B * Hkv, G * Sq, D))                 # rows: g-major, q-minor
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)

    grid = (B * Hkv, nq, nk)

    def q_index(h, qi, ki):
        return (h, qi, 0)

    def kv_index(h, qi, ki):
        return (h, ki, 0)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, nk=nk, k_limit=k_limit)

    # q block gathers the G head-slices for this q tile: we expose q as
    # (B*Hkv, nq, G*bq, D) by reshaping rows so that tile qi holds rows
    # [g*Sq + qi*bq : ...) for all g — do that reshape up front.
    qr = (qr.reshape(B * Hkv, G, Sq, D)
            .reshape(B * Hkv, G, nq, bq, D)
            .transpose(0, 2, 1, 3, 4)                    # (BH, nq, G, bq, D)
            .reshape(B * Hkv, nq, G * bq, D))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G * bq, D), lambda h, qi, ki: (h, qi, 0, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, G * bq, D), lambda h, qi, ki: (h, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, nq, G * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, D), jnp.float32),   # acc
            pltpu.VMEM((G * bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((G * bq, 1), jnp.float32),   # running sum l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)

    # (BH, nq, G*bq, D) -> (B, Sq, Hq, D)
    out = (out.reshape(B, Hkv, nq, G, bq, D)
              .transpose(0, 1, 3, 2, 4, 5)               # (B, Hkv, G, nq, bq, D)
              .reshape(B, Hq, Sq, D)
              .transpose(0, 2, 1, 3))
    return out[:, :Sq0]
