"""Serving engine: continuous batching, DDS KV paging, sharding specs."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, reduced_config
from repro.models.registry import build_model
from repro.serve.engine import BatchScheduler, PagedKVEngine, Request
from repro.storage.pagestore import PageStore


@pytest.fixture(scope="module")
def small_lm():
    cfg = dataclasses.replace(reduced_config(get_config("tinyllama_1p1b")),
                              num_layers=2, vocab_size=512)
    api = build_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    return api, params


def test_continuous_batching_completes(small_lm):
    api, params = small_lm
    sched = BatchScheduler(api, params, slots=4, cache_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 512, size=4), max_new=5)
            for i in range(10)]
    for r in reqs:
        sched.submit(r)
    done = steps = 0
    while done < 10 and steps < 500:
        done += sched.step()
        steps += 1
    assert done == 10
    assert all(len(r.generated) == 5 for r in reqs)
    # 10 requests over 4 slots need at least ceil(10/4)*5 steps
    assert steps >= 15


def test_greedy_decode_is_deterministic(small_lm):
    api, params = small_lm
    outs = []
    for _ in range(2):
        sched = BatchScheduler(api, params, slots=2, cache_len=32)
        req = Request(0, np.asarray([5, 7, 9]), max_new=4)
        sched.submit(req)
        while not req.done:
            sched.step()
        outs.append(tuple(req.generated))
    assert outs[0] == outs[1]


def test_paged_kv_spill_and_fetch():
    store = PageStore(page_size=4096, num_pages=256)
    eng = PagedKVEngine(store, block_bytes=1024, hbm_blocks=4)
    blobs = {}
    for blk in range(12):
        data = bytes([blk]) * 1024
        blobs[blk] = data
        eng.put_block(0, 0, blk, data)
    assert eng.spills == 8                       # 12 blocks, 4 slots
    # cold fetch goes through the DPU offload path and returns page bytes
    before = store.server.offload.stats.completed
    got = eng.get_block(0, 0, 0)
    assert got[:1024] == blobs[0]
    assert store.server.offload.stats.completed == before + 1
    # hot block: HBM hit, no store traffic
    assert eng.get_block(0, 0, 11) is None
    assert eng.hits == 1


def test_paged_kv_fetch_offloads_pages_larger_than_one_request():
    """A real KV page (K and V of 128 tokens x 4 heads x 128 dims, bf16) is
    larger than the host library's largest request, so each page is written
    in pieces; the fetch must still be served by the offload path."""
    block = 2 * 128 * 4 * 128 * 2
    store = PageStore(page_size=block + 4096, num_pages=8)
    eng = PagedKVEngine(store, block_bytes=block, hbm_blocks=1)
    blobs = [np.random.default_rng(i).bytes(block) for i in range(3)]
    for i, data in enumerate(blobs):
        eng.put_block(0, 0, i, data)
    before = store.server.offload.stats.completed
    for i in range(2):                           # both spilled
        assert eng.get_block(0, 0, i)[:block] == blobs[i]
    assert store.server.offload.stats.completed == before + 2
    assert store.host_served == 0


def test_kv_block_versions_respected():
    store = PageStore(page_size=4096, num_pages=256)
    eng = PagedKVEngine(store, block_bytes=1024, hbm_blocks=2)
    eng.put_block(1, 0, 0, b"v1" * 512)
    eng.put_block(1, 0, 0, b"v2" * 512)          # rewrite bumps version
    eng.put_block(1, 0, 1, b"xx" * 512)
    eng.put_block(1, 0, 2, b"yy" * 512)          # evicts block 0
    got = eng.get_block(1, 0, 0)
    assert got[:1024] == b"v2" * 512             # freshest version came back


@pytest.mark.slow
def test_paged_decode_matches_dense():
    """lm_decode_step_paged == lm_decode_step over the same prefix."""
    import dataclasses
    import jax.numpy as jnp
    from repro.models import transformer as TF

    cfg = dataclasses.replace(reduced_config(get_config("tinyllama_1p1b")),
                              num_layers=2, vocab_size=256)
    api = build_small = build_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, 256, (2, 12)), jnp.int32)

    # dense path: prefill 8, decode 9..11
    _, dense_cache = api.prefill(params, {"tokens": tokens[:, :8]},
                                 cache_len=16)
    # paged path: replay the same prefix token-by-token into the pool
    paged = TF.lm_init_paged_cache(cfg, batch=2, max_len=16, page=4)
    for t in range(8):
        logits_p, paged = TF.lm_decode_step_paged(
            params, cfg, paged, jnp.asarray(t, jnp.int32),
            tokens[:, t : t + 1])
    for t in range(8, 12):
        d_logits, dense_cache = api.decode_step(
            params, dense_cache, jnp.asarray(t, jnp.int32),
            tokens[:, t : t + 1])
        p_logits, paged = TF.lm_decode_step_paged(
            params, cfg, paged, jnp.asarray(t, jnp.int32),
            tokens[:, t : t + 1])
        np.testing.assert_allclose(np.asarray(p_logits, np.float32),
                                   np.asarray(d_logits, np.float32),
                                   atol=3e-2, rtol=3e-2)
