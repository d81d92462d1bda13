"""Pallas TPU paged decode-attention kernel.

The TPU-native analogue of DDS zero-copy reads (DESIGN.md §2): instead of
gathering KV pages into a contiguous buffer and then attending (two passes
over HBM — the straw-man of paper §6.2), the kernel walks the block table
and streams each physical page HBM->VMEM exactly once, accumulating the
online softmax in VMEM scratch.  The block table is the file mapping; the
page pool is the segment store.

Design:
  * ``PrefetchScalarGridSpec``: the block table and sequence lengths are
    scalar-prefetch operands, so each grid step's page index map reads
    ``block_table[b, p]`` BEFORE the DMA — the hardware analogue of DDS
    translating (file, offset) -> physical block before issuing the SSD op.
  * Grid = (B, MaxPages), pages innermost (``arbitrary``) so the per-batch
    accumulators live across page steps.
  * Pages past ``ceil(seq_len/page)`` are skipped with ``pl.when`` — like
    unallocated segments, they are never touched.
  * q is laid out (B, Hkv*G, D); scores are computed per kv-head group so
    each page tile is read once for all G query heads of its group.

VMEM per step: page tile (page*Hkv*D*2B, e.g. 64*8*128*2 = 128 KB) + q/acc
((Hq*D)*(2+4)B < 200 KB) — comfortably inside 16 MB for page<=512.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(block_table, seq_lens,              # scalar prefetch refs
               q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *,
               scale: float, page: int, npages: int, Hkv: int, G: int, D: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens[b]
    used = jax.lax.div(seq_len + page - 1, page)

    @pl.when(p < used)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (Hkv*G, D)
        k = k_ref[0].astype(jnp.float32)                  # (page, Hkv, D)
        v = v_ref[0].astype(jnp.float32)
        qg = q.reshape(Hkv, G, D)
        s = jnp.einsum("hgd,thd->hgt", qg, k,
                       preferred_element_type=jnp.float32)  # (Hkv, G, page)
        kpos = p * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < seq_len, s, NEG_INF)
        s = s.reshape(Hkv * G, page)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + pr.sum(axis=1, keepdims=True)
        prg = pr.reshape(Hkv, G, page)
        ctx = jnp.einsum("hgt,thd->hgd", prg, v,
                         preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + ctx.reshape(Hkv * G, D)
        m_ref[...] = m_new

    @pl.when(p == npages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, block_table, seq_lens,
                           scale: float | None = None,
                           interpret: bool = False):
    """q: (B, Hq, D); pools: (P, page, Hkv, D) -> (B, Hq, D)."""
    B, Hq, D = q.shape
    P, page, Hkv, _ = k_pages.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    npages = block_table.shape[1]
    if scale is None:
        scale = D ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, p, bt, sl: (b, 0, 0)),
            # The block table translates (sequence, logical page) ->
            # physical pool page BEFORE the DMA is issued.
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, p, bt, sl: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, p, bt, sl: (bt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, p, bt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_pa_kernel, scale=scale, page=page,
                               npages=npages, Hkv=Hkv, G=G, D=D)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, seq_lens, q, k_pages, v_pages)
