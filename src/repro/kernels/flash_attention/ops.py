"""Dispatching wrapper for flash attention.

``flash_attention`` picks the implementation:
  * ``pallas``      — the Mosaic TPU kernel (kernel.py), on TPU backends.
    It has no backward kernel: its VJP is that of ``xla_chunked`` on the
    same inputs (an XLA blockwise backward that recomputes the forward);
  * ``xla_chunked`` — a pure-jnp blockwise online-softmax implementation
    (lax.scan over KV blocks) with the same memory behaviour: activations
    are O(S * block) instead of O(S^2).  Used on CPU (incl. the multi-pod
    dry-run) and as a portable fallback;
  * ``naive``       — the ref oracle (tests only; materializes S^2).

All implementations share semantics with ``ref.attention_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed.sharding import per_shard_attention
from repro.kernels.flash_attention.ref import attention_ref

NEG_INF = -1e30


def _chunk_body(q, kc, vc, carry, q_start, k_start, *, causal, window, bq, bk,
                k_limit):
    """One KV chunk of online softmax.  q: (B,H,bq,D); kc/vc: (B,H,bk,D)."""
    acc, m, l = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                   preferred_element_type=jnp.float32)
    qpos = q_start + jnp.arange(bq)[:, None]
    kpos = k_start + jnp.arange(bk)[None, :]
    mask = kpos < k_limit  # padded key positions never attend
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p.sum(-1, keepdims=True)
    acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vc,
                                   preferred_element_type=jnp.float32)
    return acc, m_new, l


def flash_attention_xla(q, k, v, *, causal=True, window=None, q_offset=None,
                        scale=None, block_q: int = 512, block_k: int = 512):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if q_offset is None:
        q_offset = Sk - Sq
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    # Pad sequences up to block multiples (masked out).
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    qf = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kf = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vf = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    Sqp, Skp = Sq + pq, Sk + pk
    nq, nk = Sqp // bq, Skp // bk
    # (B, H, S, D) layouts; kv heads repeated lazily per group.
    qf = qf.transpose(0, 2, 1, 3).astype(jnp.float32) * scale   # (B,Hq,Sq,D)
    kf = kf.transpose(0, 2, 1, 3)
    vf = vf.transpose(0, 2, 1, 3)
    kf = jnp.repeat(kf, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(vf, G, axis=1).astype(jnp.float32)
    kb = kf.reshape(B, Hq, nk, bk, D).transpose(2, 0, 1, 3, 4)  # (nk,B,H,bk,D)
    vb = vf.reshape(B, Hq, nk, bk, D).transpose(2, 0, 1, 3, 4)

    def per_q_block(qi, qblk):
        q_start = qi * bq + q_offset
        init = (jnp.zeros((B, Hq, bq, D), jnp.float32),
                jnp.full((B, Hq, bq, 1), NEG_INF, jnp.float32),
                jnp.zeros((B, Hq, bq, 1), jnp.float32))

        def body(carry, xs):
            ki, kc, vc = xs
            carry = _chunk_body(qblk, kc, vc, carry, q_start, ki * bk,
                                causal=causal, window=window, bq=bq, bk=bk,
                                k_limit=Sk)
            return carry, None

        (acc, m, l), _ = jax.lax.scan(body, init,
                                      (jnp.arange(nk), kb, vb))
        return acc / (l + 1e-30)

    qb = qf.reshape(B, Hq, nq, bq, D).transpose(2, 0, 1, 3, 4)  # (nq,B,H,bq,D)
    out = jax.lax.map(lambda xs: per_q_block(xs[0], xs[1]),
                      (jnp.arange(nq), qb))                     # (nq,B,H,bq,D)
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Sqp, D)
    out = out[:, :, :Sq].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _pallas_xla_vjp(q, k, v, causal, window, q_offset, scale, interpret):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, scale=scale,
                                  interpret=interpret)


def _pallas_xla_vjp_fwd(q, k, v, causal, window, q_offset, scale, interpret):
    out = _pallas_xla_vjp(q, k, v, causal, window, q_offset, scale, interpret)
    return out, (q, k, v)


def _pallas_xla_vjp_bwd(causal, window, q_offset, scale, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(
        flash_attention_xla, causal=causal, window=window, q_offset=q_offset,
        scale=scale), *res)
    return vjp(g)


_pallas_xla_vjp.defvjp(_pallas_xla_vjp_fwd, _pallas_xla_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    q_offset: int | None = None, scale: float | None = None,
                    impl: str | None = None, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    """GQA flash attention.  See ref.attention_ref for semantics.
    ``block_q``/``block_k`` tile the ``xla_chunked`` path; the Pallas
    kernel takes its tiles from the shape (``kernel.flash_plan``)."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla_chunked"
    if impl == "pallas":
        kernel = functools.partial(_pallas_xla_vjp, causal=causal,
                                   window=window, q_offset=q_offset,
                                   scale=scale, interpret=interpret)
        return per_shard_attention(kernel)(q, k, v)
    if impl == "xla_chunked":
        return flash_attention_xla(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, scale=scale,
                                   block_q=block_q, block_k=block_k)
    if impl == "naive":
        return attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale)
    raise ValueError(f"unknown impl {impl}")
