"""Pallas TPU flash-attention kernel (forward).

Tiled online-softmax attention with GQA, causal masking, and sliding-window
support.  TPU-codesign notes:

  * All q heads of one kv head (the GQA group G) are processed together:
    the q block is (G*bq, D) so the group shares the k/v tiles in VMEM —
    k/v tiles are fetched once per group rather than once per query head.
  * The grid is ``(batch*kv_heads, tiles)``: the second axis walks only the
    (q block, k block) tiles that the causal/window mask keeps, from
    scalar-prefetched tables (``flash_plan``), q-block-major so the fp32
    accumulators in VMEM scratch carry across one q block's k tiles and its
    output block is written once.  A masked-out tile costs neither a grid
    step nor a DMA.
  * Only tiles that cross the causal or window frontier, or hold padded
    keys, build a mask; the others run the unmasked body.
  * Both dots run on the MXU in the inputs' dtype (bf16 in serving) with
    fp32 accumulation; the softmax statistics and the accumulator stay
    fp32, and p enters the p·v dot in v's dtype.  fp32 inputs keep fp32
    operands.
  * m and l live in all 128 lanes of their rows, so they meet the scores
    and the accumulator without a broadcast across lanes; that halved the
    kernel's time on a v5e, where bf16 operands alone saved nothing.
  * Tile sizes follow the shape (``_tiles``), not a setting: 256 queries a
    block (a q block of G*256 rows) and 256 keys a block at head dimension
    128, 512 at 64, from a sweep on a v5e at the serving cells' shapes.
    VMEM per step (double-buffered q, out, k and v blocks, the fp32
    accumulator and lane-wide statistics, the fp32 scores and
    probabilities and their cast) is about 12 MB at G = 9, D = 128;
    ``vmem_limit_bytes`` is twice that estimate, 32 MB at least.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Flags of a scheduled tile (``FlashPlan.flags``).
FIRST, LAST, MASKED = 1, 2, 4


def _tiles(D: int, Sq: int, Sk: int) -> tuple[int, int]:
    """(bq, bk) for a call: 256 queries a block, so a q block holds G*256
    rows, and 256 keys a block at head dimension 128 or more, 512 below
    (the fastest of a sweep on a v5e at the serving cells' shapes; PERF.md);
    neither longer than its sequence."""
    bk = 512 if D < 128 else 256
    return min(256, Sq), min(bk, Sk)


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """The schedule of one kernel call.  ``qi``, ``ki`` and ``flags`` list
    the visited tiles of one (batch, kv head) group in grid order; the
    counts are over the whole call."""
    bq: int
    bk: int
    nq: int
    nk: int
    full_steps: int       # grid steps of the full (q block x k block) grid
    visited_steps: int    # grid steps the kernel takes
    masked_tiles: int     # visited tiles that build a mask
    qi: np.ndarray
    ki: np.ndarray
    flags: np.ndarray


def flash_plan(B: int, Sq: int, Sk: int, Hkv: int, D: int, *,
               causal: bool = True, window: int | None = None,
               q_offset: int | None = None, block_q: int | None = None,
               block_k: int | None = None) -> FlashPlan:
    """The tiles a call visits and masks.  Query row r sits at position
    ``q_offset + r``; a tile is visited when some real query row in it may
    see some real key, and masked unless every real query row of it may see
    every key position in it (padded keys included)."""
    if q_offset is None:
        q_offset = Sk - Sq
    bq, bk = _tiles(D, Sq, Sk)
    bq, bk = min(block_q or bq, Sq), min(block_k or bk, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    qi, ki, flags = [], [], []
    for i in range(nq):
        q_lo = q_offset + i * bq
        q_hi = q_offset + min((i + 1) * bq, Sq) - 1
        row = []
        for j in range(nk):
            k_lo, k_end = j * bk, (j + 1) * bk - 1
            k_hi = min(k_end, Sk - 1)
            if causal and k_lo > q_hi:
                continue
            if window is not None and k_hi <= q_lo - window:
                continue
            full = k_end < Sk
            if causal:
                full &= k_end <= q_lo
            if window is not None:
                full &= k_lo > q_hi - window
            row.append((j, 0 if full else MASKED))
        if not row:   # no key in reach: one masked tile keeps it defined
            row.append((min(nk - 1, max(0, q_hi // bk)), MASKED))
        row[0] = (row[0][0], row[0][1] | FIRST)
        row[-1] = (row[-1][0], row[-1][1] | LAST)
        for j, f in row:
            qi.append(i)
            ki.append(j)
            flags.append(f)
    groups = B * Hkv
    flags = np.asarray(flags, np.int32)
    return FlashPlan(
        bq=bq, bk=bk, nq=nq, nk=nk, full_steps=groups * nq * nk,
        visited_steps=groups * len(qi),
        masked_tiles=groups * int(np.count_nonzero(flags & MASKED)),
        qi=np.asarray(qi, np.int32), ki=np.asarray(ki, np.int32),
        flags=flags)


def _vmem_bytes(rows: int, bk: int, D: int, itemsize: int) -> int:
    """VMEM of one grid step: double-buffered q, out, k and v blocks, the
    fp32 accumulator and lane-wide statistics, and the fp32 scores,
    probabilities and their cast."""
    blocks = 2 * (2 * rows * D + 2 * bk * D) * itemsize
    scratch = rows * D * 4 + 2 * rows * 128 * 4
    temps = rows * bk * (4 + 4 + itemsize)
    return blocks + scratch + temps


def _lanes(x, n: int):
    """x, (rows, 128) with every lane equal, as (rows, n)."""
    if n <= 128:
        return x[:, :n]
    if n % 128 == 0:
        return jnp.tile(x, (1, n // 128))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fa_kernel(qtab, ktab, ftab, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
               l_ref, *, scale: float, causal: bool, window: int | None,
               q_offset: int, bq: int, bk: int, k_limit: int | None):
    t = pl.program_id(1)
    flags = ftab[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute(masked: bool):
        q = q_ref[0, 0]                                # (G*bq, D)
        k = k_ref[0]                                   # (bk, D)
        v = v_ref[0]                                   # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            # Rows are (G, bq) flattened g-major: every head of the group
            # shares row r's query position, q_start + r % bq.  rel is each
            # key's position less its query's.
            q_start = qtab[t] * bq + q_offset
            k_start = ktab[t] * bk
            row = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0) % bq
            col = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            rel = (col + (k_start - q_start)) - row
            mask = jnp.ones(s.shape, dtype=bool)
            if k_limit is not None:   # keys padded up to a block multiple
                mask &= col + k_start < k_limit
            if causal:
                mask &= rel <= 0
            if window is not None:
                mask &= rel > -window
            s = jnp.where(mask, s, NEG_INF)
        # m and l are kept in all 128 lanes of their rows, so that they
        # meet the (rows, bk) scores and the (rows, D) accumulator without
        # a broadcast across lanes.
        m_prev = m_ref[...]                            # (G*bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))             # (G*bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, acc_ref.shape[1]) + (
            jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    pl.when((flags & MASKED) != 0)(lambda: compute(True))
    pl.when((flags & MASKED) == 0)(lambda: compute(False))

    @pl.when((flags & LAST) != 0)
    def _finish():
        l = _lanes(l_ref[...], acc_ref.shape[1])
        o_ref[0, 0] = (acc_ref[...] / (l + 1e-30)).astype(o_ref.dtype)


def _fa_call(qr, kr, vr, plan: FlashPlan, *, scale: float, causal: bool,
             window: int | None, q_offset: int, k_limit: int | None,
             interpret: bool = False):
    """The kernel over relaid inputs: qr (B*Hkv, nq, G*bq, D), kr/vr
    (B*Hkv, Sk, D) -> (B*Hkv, nq, G*bq, D)."""
    BH, nq, rows, D = qr.shape
    bq, bk = plan.bq, plan.bk
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, k_limit=k_limit)
    q_index = lambda h, t, qt, kt, ft: (h, qt[t], 0, 0)
    kv_index = lambda h, t, qt, kt, ft: (h, kt[t], 0)
    vmem = _vmem_bytes(rows, bk, D, qr.dtype.itemsize)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BH, len(plan.qi)),
            in_specs=[
                pl.BlockSpec((1, 1, rows, D), q_index),
                pl.BlockSpec((1, bk, D), kv_index),
                pl.BlockSpec((1, bk, D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((rows, D), jnp.float32),   # acc
                pltpu.VMEM((rows, 128), jnp.float32),   # running max m
                pltpu.VMEM((rows, 128), jnp.float32),   # running sum l
            ]),
        out_shape=jax.ShapeDtypeStruct(qr.shape, qr.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        interpret=interpret,
    )(jnp.asarray(plan.qi), jnp.asarray(plan.ki), jnp.asarray(plan.flags),
      qr, kr, vr)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           q_offset: int | None = None,
                           scale: float | None = None,
                           block_q: int | None = None,
                           block_k: int | None = None,
                           interpret: bool = False):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D).

    Tile sizes follow the shape (``flash_plan``); ``block_q``/``block_k``
    override them for tests.  Sequences are padded up to block multiples:
    padded queries are sliced off the output and padded keys are masked in
    the kernel."""
    B, Sq0, Hq, D = q.shape
    _, Sk0, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if q_offset is None:
        q_offset = Sk0 - Sq0
    plan = flash_plan(B, Sq0, Sk0, Hkv, D, causal=causal, window=window,
                      q_offset=q_offset, block_q=block_q, block_k=block_k)
    bq, bk, nq = plan.bq, plan.bk, plan.nq
    pad_seq = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0), (0, 0)))
    q = pad_seq(q, (-Sq0) % bq)
    k = pad_seq(k, (-Sk0) % bk)
    v = pad_seq(v, (-Sk0) % bk)
    Sq, Sk = q.shape[1], k.shape[1]
    k_limit = Sk0 if Sk != Sk0 else None
    # Reorder to (B*Hkv, ...) with the G q-heads of each kv head contiguous.
    qr = (q.transpose(0, 2, 1, 3)                        # (B, Hq, Sq, D)
           .reshape(B, Hkv, G, Sq, D)
           .reshape(B * Hkv, G * Sq, D))                 # rows: g-major, q-minor
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)

    # q block gathers the G head-slices for this q tile: we expose q as
    # (B*Hkv, nq, G*bq, D) by reshaping rows so that tile qi holds rows
    # [g*Sq + qi*bq : ...) for all g — do that reshape up front.
    qr = (qr.reshape(B * Hkv, G, Sq, D)
            .reshape(B * Hkv, G, nq, bq, D)
            .transpose(0, 2, 1, 3, 4)                    # (BH, nq, G, bq, D)
            .reshape(B * Hkv, nq, G * bq, D))
    out = _fa_call(qr, kr, vr, plan, scale=scale, causal=causal,
                   window=window, q_offset=q_offset, k_limit=k_limit,
                   interpret=interpret)

    # (BH, nq, G*bq, D) -> (B, Sq, Hq, D)
    out = (out.reshape(B, Hkv, nq, G, bq, D)
              .transpose(0, 1, 3, 2, 4, 5)               # (B, Hkv, G, nq, bq, D)
              .reshape(B, Hq, Sq, D)
              .transpose(0, 2, 1, 3))
    return out[:, :Sq0]
