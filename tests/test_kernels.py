"""Per-kernel shape/dtype sweeps against the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention, flash_attention_xla
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssm_scan.kernel import gla_scan_pallas
from repro.kernels.ssm_scan.ops import gla_scan_xla
from repro.kernels.ssm_scan.ref import gla_decode_step, gla_scan_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Flash attention.
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window
    (2, 128, 128, 4, 2, 64, True, None),
    (1, 256, 256, 8, 8, 64, True, 64),
    (2, 64, 192, 4, 1, 32, False, None),
    (1, 128, 128, 6, 2, 128, True, None),
    (1, 64, 64, 2, 2, 64, True, 16),
    # The cells' groups, scaled down in S: G = 9 at D = 128, G = 3 at D = 64.
    (1, 160, 160, 9, 1, 128, True, None),
    (1, 200, 200, 6, 2, 64, True, None),
    # Queries after the keys' start (q_offset = Sk - Sq > 0), with a window.
    (1, 96, 224, 4, 2, 64, True, None),
    (1, 80, 208, 4, 2, 64, True, 48),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_pallas_interpret(case, dtype):
    B, Sq, Sk, Hq, Hkv, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, Sq, Hq, D), dtype)
    k = _rand(ks[1], (B, Sk, Hkv, D), dtype)
    v = _rand(ks[2], (B, Sk, Hkv, D), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# Lengths that are not a multiple of the kernel's 128-row blocks: the
# wrapper pads them and the kernel masks the padded keys.
FA_RAGGED_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal
    (1, 200, 200, 4, 2, 64, True),
    (1, 200, 200, 4, 2, 64, False),
    (2, 72, 330, 4, 1, 64, False),
    (1, 130, 259, 4, 4, 128, True),
]


@pytest.mark.parametrize("case", FA_RAGGED_CASES)
def test_flash_attention_pallas_pads_ragged_lengths(case):
    B, Sq, Sk, Hq, Hkv, D, causal = case
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = _rand(ks[0], (B, Sq, Hq, D), jnp.float32)
    k = _rand(ks[1], (B, Sk, Hkv, D), jnp.float32)
    v = _rand(ks[2], (B, Sk, Hkv, D), jnp.float32)
    ref = attention_ref(q, k, v, causal=causal)
    out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, atol=TOL[jnp.float32],
                               rtol=TOL[jnp.float32])


# The tiles the wrapper picks from the shape, at the cells' groups scaled
# down in S: several k tiles, ragged lengths, q_offset > 0, windows and
# non-causal calls, in both dtypes.
FA_PLANNED_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window, q_offset
    (1, 600, 600, 9, 1, 128, True, None, 0),
    (1, 700, 700, 6, 2, 64, True, None, 0),
    (1, 300, 1100, 3, 1, 64, True, None, None),
    (1, 260, 520, 9, 1, 128, True, 200, 130),
    (1, 333, 333, 6, 2, 64, True, 100, 0),
    (1, 800, 800, 9, 1, 128, True, 100, 0),
    (1, 250, 610, 9, 1, 128, False, None, 0),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FA_PLANNED_CASES)
def test_flash_attention_pallas_planned_tiles(case, dtype):
    B, Sq, Sk, Hq, Hkv, D, causal, window, q_offset = case
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = _rand(ks[0], (B, Sq, Hq, D), dtype)
    k = _rand(ks[1], (B, Sk, Hkv, D), dtype)
    v = _rand(ks[2], (B, Sk, Hkv, D), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        q_offset=q_offset)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, interpret=True)
    assert out.shape == ref.shape
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def _band(Sq, Sk, causal, window, q_offset):
    """ref.attention_ref's mask, (Sq, Sk)."""
    qpos = np.arange(Sq)[:, None] + q_offset
    kpos = np.arange(Sk)[None, :]
    keep = np.ones((Sq, Sk), bool)
    if causal:
        keep &= kpos <= qpos
    if window is not None:
        keep &= kpos > qpos - window
    return keep


@pytest.mark.parametrize("case", FA_PLANNED_CASES + [
    # the cells' prefill shapes: (B, S, S, Hq, Hkv, D), causal
    (16, 1500, 1500, 36, 4, 128, True, None, 0),
    (16, 1020, 1020, 36, 4, 128, True, None, 0),
    (16, 1020, 1020, 24, 8, 64, True, None, 0),
])
def test_flash_plan_visits_the_band_and_masks_its_edges(case):
    """The plan visits exactly the tiles that hold a kept (query, key) pair,
    masks exactly those that also hold a dropped pair or a padded key, and
    lists each q block's tiles together, first and last flagged."""
    B, Sq, Sk, Hq, Hkv, D, causal, window, q_offset = case
    plan = K.flash_plan(B, Sq, Sk, Hkv, D, causal=causal, window=window,
                        q_offset=q_offset)
    assert (plan.bq, plan.bk) == K._tiles(D, Sq, Sk)
    bq, bk = plan.bq, plan.bk
    off = Sk - Sq if q_offset is None else q_offset
    band = np.zeros((plan.nq * bq, plan.nk * bk), bool)
    band[:Sq, :Sk] = _band(Sq, Sk, causal, window, off)
    tiles = band.reshape(plan.nq, bq, plan.nk, bk)
    # A tile is masked unless every real query row keeps every column.
    rows_real = np.zeros((plan.nq, bq), bool)
    rows_real.reshape(-1)[:Sq] = True
    visited, masked = set(), set()
    for i in range(plan.nq):
        for j in range(plan.nk):
            t = tiles[i, :, j, :]
            if t.any():
                visited.add((i, j))
                if not t[rows_real[i]].all():
                    masked.add((i, j))
    got = list(zip(plan.qi.tolist(), plan.ki.tolist()))
    assert set(got) == visited and len(got) == len(visited)
    assert {g for g, f in zip(got, plan.flags) if f & K.MASKED} == masked
    groups = B * Hkv
    assert plan.visited_steps == groups * len(visited)
    assert plan.masked_tiles == groups * len(masked)
    assert plan.full_steps == groups * plan.nq * plan.nk
    assert got == sorted(got)
    for i in range(plan.nq):
        fl = [int(f) for (a, _), f in zip(got, plan.flags) if a == i]
        assert fl[0] & K.FIRST and fl[-1] & K.LAST
        assert not any(f & K.FIRST for f in fl[1:])
        assert not any(f & K.LAST for f in fl[:-1])


def test_flash_tiles_follow_the_shape():
    """The rule at the three cells' prefill shapes, (D, Sq, Sk) -> (bq, bk),
    and at sequences shorter than a block."""
    assert K._tiles(128, 1500, 1500) == (256, 256)
    assert K._tiles(128, 1020, 1020) == (256, 256)
    assert K._tiles(64, 1020, 1020) == (256, 512)
    assert K._tiles(64, 100, 300) == (100, 300)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FA_CASES + [(1, 100, 100, 2, 2, 64, True, None)])
def test_flash_attention_xla_chunked(case, dtype):
    B, Sq, Sk, Hq, Hkv, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (B, Sq, Hq, D), dtype)
    k = _rand(ks[1], (B, Sk, Hkv, D), dtype)
    v = _rand(ks[2], (B, Sk, Hkv, D), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention_xla(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# Paged attention.
# ---------------------------------------------------------------------------

PA_CASES = [
    # B, Hq, Hkv, D, pool_pages, page, max_pages
    (2, 8, 2, 64, 16, 16, 4),
    (1, 4, 4, 32, 8, 8, 8),
    (3, 16, 8, 128, 32, 32, 3),
    (2, 4, 1, 64, 8, 64, 2),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", PA_CASES)
def test_paged_attention_pallas_interpret(case, dtype):
    B, Hq, Hkv, D, P, page, maxp = case
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(ks[0], (B, Hq, D), dtype)
    kp = _rand(ks[1], (P, page, Hkv, D), dtype)
    vp = _rand(ks[2], (P, page, Hkv, D), dtype)
    bt = jax.random.randint(ks[3], (B, maxp), 0, P, jnp.int32)
    sl = jnp.asarray([(maxp * page) - 3] + [(maxp - 1) * page - 1] * (B - 1),
                     jnp.int32)[:B]
    ref = paged_attention_ref(q, kp, vp, bt, sl)
    out = paged_attention_pallas(q, kp, vp, bt, sl, interpret=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_attention_respects_block_table():
    """Permuting physical pages + table together must not change results."""
    B, Hq, Hkv, D, P, page, maxp = 1, 4, 2, 32, 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = _rand(ks[0], (B, Hq, D), jnp.float32)
    kp = _rand(ks[1], (P, page, Hkv, D), jnp.float32)
    vp = _rand(ks[2], (P, page, Hkv, D), jnp.float32)
    bt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    sl = jnp.asarray([maxp * page], jnp.int32)
    base = paged_attention_ref(q, kp, vp, bt, sl)
    perm = jnp.asarray([3, 0, 1, 2, 4, 5, 6, 7])
    inv = jnp.argsort(perm)
    out = paged_attention_ref(q, kp[perm], vp[perm], inv[bt], sl)
    np.testing.assert_allclose(base, out, atol=1e-6)


# ---------------------------------------------------------------------------
# GLA / SSM scan.
# ---------------------------------------------------------------------------

GLA_CASES = [
    # B, H, S, K, V, chunk
    (2, 4, 128, 64, 64, 32),
    (1, 2, 256, 32, 64, 64),
    (2, 1, 96, 16, 16, 32),
    (1, 3, 64, 128, 32, 16),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", GLA_CASES)
def test_gla_xla_chunked(case, dtype):
    B, H, S, K, V, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = _rand(ks[0], (B, H, S, K), dtype) * 0.5
    k = _rand(ks[1], (B, H, S, K), dtype) * 0.5
    v = _rand(ks[2], (B, H, S, V), dtype)
    w = -jnp.exp(_rand(ks[3], (B, H, S, K), jnp.float32)) * 0.05
    ref_o, ref_s = gla_scan_ref(q, k, v, w)
    out_o, out_s = gla_scan_xla(q, k, v, w, chunk=chunk)
    np.testing.assert_allclose(out_o.astype(jnp.float32),
                               ref_o.astype(jnp.float32),
                               atol=TOL[dtype] * 4, rtol=TOL[dtype] * 4)
    np.testing.assert_allclose(out_s, ref_s, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("case", GLA_CASES[:3])
def test_gla_pallas_interpret(case):
    B, H, S, K, V, chunk = case
    if S % chunk:
        pytest.skip("pallas path needs chunk-aligned S")
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = _rand(ks[0], (B, H, S, K), jnp.float32) * 0.5
    k = _rand(ks[1], (B, H, S, K), jnp.float32) * 0.5
    v = _rand(ks[2], (B, H, S, V), jnp.float32)
    w = -jnp.exp(_rand(ks[3], (B, H, S, K), jnp.float32)) * 0.05
    ref_o, ref_s = gla_scan_ref(q, k, v, w)
    out_o, out_s = gla_scan_pallas(q, k, v, w, chunk=chunk, interpret=True)
    np.testing.assert_allclose(out_o, ref_o, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(out_s, ref_s, atol=1e-3, rtol=1e-3)


def test_gla_decode_continuation():
    """prefill(S-1) + decode_step == full scan at position S-1."""
    B, H, S, K, V = 2, 2, 64, 32, 32
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = _rand(ks[0], (B, H, S, K), jnp.float32) * 0.5
    k = _rand(ks[1], (B, H, S, K), jnp.float32) * 0.5
    v = _rand(ks[2], (B, H, S, V), jnp.float32)
    w = -jnp.exp(_rand(ks[3], (B, H, S, K), jnp.float32)) * 0.05
    o_all, s_all = gla_scan_ref(q, k, v, w)
    _, s_pre = gla_scan_xla(q[:, :, :-1], k[:, :, :-1], v[:, :, :-1],
                            w[:, :, :-1], chunk=16)
    o_dec, s_dec = gla_decode_step(q[:, :, -1], k[:, :, -1], v[:, :, -1],
                                   w[:, :, -1], s_pre)
    np.testing.assert_allclose(o_dec, o_all[:, :, -1], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s_dec, s_all, atol=1e-4, rtol=1e-4)


def test_gla_strong_decay_stays_finite():
    """The exponent guard keeps extreme decays finite (regression)."""
    B, H, S, K, V = 1, 1, 256, 32, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (B, H, S, K), jnp.float32)
    k = _rand(ks[1], (B, H, S, K), jnp.float32)
    v = _rand(ks[2], (B, H, S, V), jnp.float32)
    w = jnp.full((B, H, S, K), -2.5)          # very strong decay
    o, s = gla_scan_xla(q, k, v, w, chunk=128)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert bool(jnp.all(jnp.isfinite(s)))


# ---------------------------------------------------------------------------
# Backward passes (training differentiates through the portable paths).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", FA_CASES[:3])
def test_flash_attention_xla_gradients_match_naive(case):
    B, Sq, Sk, Hq, Hkv, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand(ks[0], (B, Sq, Hq, D), jnp.float32)
    k = _rand(ks[1], (B, Sk, Hkv, D), jnp.float32)
    v = _rand(ks[2], (B, Sk, Hkv, D), jnp.float32)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(attention_ref(
            q, k, v, causal=causal, window=window)))

    def loss_xla(q, k, v):
        return jnp.sum(jnp.square(flash_attention_xla(
            q, k, v, causal=causal, window=window, block_q=64, block_k=64)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_xla):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("case", [FA_CASES[0], FA_CASES[2],
                                  (1, 200, 200, 4, 2, 64, True, None)])
def test_flash_attention_pallas_vjp_matches_xla(case):
    """The Pallas forward's custom VJP gives the XLA path's gradients."""
    B, Sq, Sk, Hq, Hkv, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (B, Sq, Hq, D), jnp.float32)
    k = _rand(ks[1], (B, Sk, Hkv, D), jnp.float32)
    v = _rand(ks[2], (B, Sk, Hkv, D), jnp.float32)

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(jnp.square(flash_attention(
                q, k, v, causal=causal, window=window, impl=impl,
                interpret=True)))
        return f

    g_pallas = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss("xla_chunked"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pallas, g_xla):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("case", GLA_CASES[:2])
def test_gla_xla_gradients_match_naive(case):
    B, H, S, K, V, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = _rand(ks[0], (B, H, S, K), jnp.float32) * 0.5
    k = _rand(ks[1], (B, H, S, K), jnp.float32) * 0.5
    v = _rand(ks[2], (B, H, S, V), jnp.float32)
    w = -jnp.exp(_rand(ks[3], (B, H, S, K), jnp.float32)) * 0.05

    def loss_ref(q, k, v, w):
        return jnp.sum(jnp.square(gla_scan_ref(q, k, v, w)[0]))

    def loss_xla(q, k, v, w):
        return jnp.sum(jnp.square(gla_scan_xla(q, k, v, w, chunk=chunk)[0]))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, w)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2, 3))(q, k, v, w)
    for a, b in zip(g_ref, g_xla):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)
