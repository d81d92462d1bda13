"""Serving engine: prefill/decode steps + DDS-backed KV-block offloading.

``make_serve_fns`` builds the pjit-able serve entry points the dry-run
lowers for the decode/prefill cells.

``PagedKVEngine`` is the DDS integration (DESIGN.md §2.2): KV blocks of a
long context are pages in a store.  Hot/recent blocks live "on the host"
(HBM pool, accessed via the paged-attention kernel's block table); cold
blocks spill to the DDS page store (storage server) and are fetched back
through the OFFLOAD path — cold, simple, read-only reads, exactly what the
paper offloads — while writes (new KV blocks) take the host path.

``BatchScheduler`` is a minimal continuous-batching front: requests join or
leave decode slots between steps.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import sharding as sh
from repro.models.registry import ModelAPI


PREFILL_2D_BYTES = 4 << 30   # 1D-TP weights above this per chip -> go 2D


def make_serve_fns(api: ModelAPI, mesh: Mesh, axes_tree,
                   shape: ShapeConfig, pshapes=None):
    """Returns (prefill_jit, decode_jit) with explicit shardings; their
    programs are named ``jit_prefill`` and ``jit_decode``.  Decode donates
    its cache (argument 1): the step writes its new entries into it in
    place, and the caller passes each step the cache the last one returned.

    DECODE always uses 2D weight sharding (model TP x data): weights stay
    stationary on both axes and the tiny decode activations move instead —
    16x less per-chip parameter traffic for the 132B MoE (§Perf it. 10).
    PREFILL has train-sized activations, so the per-layer weight gathers 2D
    costs only pay off when 1D-TP weights don't fit comfortably
    (> PREFILL_2D_BYTES/chip); small models keep 1D TP (the baseline-sweep
    regression on small-arch prefill cells motivated this split).
    """
    if pshapes is None:
        from repro.train.loop import abstract_init
        pshapes, _ = abstract_init(api)
    model_size = mesh.shape.get("model", 1)
    params_1d = sum(
        int(np.prod(p.shape)) * 2
        for p in jax.tree_util.tree_leaves(pshapes)) // max(1, model_size)
    prefill_fsdp = params_1d > PREFILL_2D_BYTES
    pspecs_prefill = sh.sanitize_tree(
        sh.param_specs(axes_tree, mesh, api.cfg, fsdp=prefill_fsdp),
        pshapes, mesh)
    pspecs = sh.sanitize_tree(
        sh.param_specs(axes_tree, mesh, api.cfg, fsdp=True), pshapes, mesh)
    dp = sh.dp_axes(mesh)
    ns = lambda s: NamedSharding(mesh, s)
    row_sh = ns(P(dp if shape.global_batch >= _ndp(mesh) else None, None))

    def cache_sh(cache_like):
        return sh.to_shardings(
            sh.cache_specs(cache_like, mesh, api.cfg, shape), mesh)

    def decode_jit(cache_like):
        csh = cache_sh(cache_like)

        def decode(params, cache, kv_len, token):
            return api.decode_step(params, cache, kv_len, token)

        return jax.jit(decode,
                       in_shardings=(sh.to_shardings(pspecs, mesh), csh,
                                     ns(P()), row_sh),
                       out_shardings=(row_sh, csh), donate_argnums=(1,))

    def prefill_jit(batch_like, cache_len: int | None = None):
        """The prefill's cache leaves with decode's cache shardings, so
        decode takes it as it is; ``cache_len`` leaves room to decode."""
        bspecs = sh.batch_specs(mesh, shape, api.cfg)
        in_b = {k: ns(bspecs.get(k, P(dp, None))) for k in batch_like}
        in_sh = (sh.to_shardings(pspecs_prefill, mesh), in_b)

        def prefill(params, batch):
            return api.prefill(params, batch, cache_len=cache_len)

        _, cache_like = jax.eval_shape(prefill, pshapes, batch_like)
        return jax.jit(prefill, in_shardings=in_sh,
                       out_shardings=(row_sh, cache_sh(cache_like)))

    return prefill_jit, decode_jit


def _ndp(mesh: Mesh) -> int:
    n = 1
    for a in sh.dp_axes(mesh):
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# DDS-backed paged KV offloading.
# ---------------------------------------------------------------------------


@dataclass
class KVBlockMeta:
    seq_id: int
    layer: int
    block: int
    version: int


class PagedKVEngine:
    """HBM block pool + DDS page store spillover for long-context decode.

    The HBM pool holds ``hbm_blocks`` KV pages; a block table maps
    (sequence, logical block) -> pool slot.  When the pool overflows, the
    coldest blocks are written to the DDS page store (HOST path — writes
    belong on the host, §3) and their slots recycled.  A query that needs a
    cold block triggers a fetch via the OFFLOAD path (DPU-served read).
    """

    def __init__(self, page_store, block_bytes: int, hbm_blocks: int):
        from repro.storage.pagestore import PageStore
        self.store = page_store
        self.block_bytes = block_bytes
        self.hbm_blocks = hbm_blocks
        self.pool: dict[int, tuple[int, int, int]] = {}  # slot -> (seq,layer,blk)
        self.where: dict[tuple[int, int, int], int] = {}  # key -> slot
        self.lru: deque = deque()
        self.versions: dict[tuple[int, int, int], int] = {}
        self.spills = 0
        self.fetches = 0
        self.hits = 0
        self._client = None
        self._page_ids: dict[tuple[int, int, int], int] = {}

    def _page_id(self, key: tuple[int, int, int]) -> int:
        """Dense page ids (the page store's file is offset = id * page_size)."""
        pid = self._page_ids.get(key)
        if pid is None:
            pid = len(self._page_ids)
            self._page_ids[key] = pid
        return pid

    def put_block(self, seq: int, layer: int, blk: int, data: bytes) -> int:
        """New KV block (decode write).  Returns the HBM slot."""
        key = (seq, layer, blk)
        ver = self.versions.get(key, 0) + 1
        self.versions[key] = ver
        if len(self.pool) >= self.hbm_blocks:
            self._evict_one()
        slot = self._free_slot()
        self.pool[slot] = key
        self.where[key] = slot
        self.lru.append(key)
        # Write-through to the store on the HOST path (durable + cacheable).
        self.store.replay(self._page_id(key), ver, data[: self.store.payload_size])
        return slot

    def _free_slot(self) -> int:
        used = set(self.pool)
        for s in range(self.hbm_blocks):
            if s not in used:
                return s
        raise RuntimeError("pool full after eviction")

    def _evict_one(self) -> None:
        while self.lru:
            key = self.lru.popleft()
            slot = self.where.get(key)
            if slot is not None and self.pool.get(slot) == key:
                del self.pool[slot]
                del self.where[key]
                self.spills += 1
                return

    def get_block(self, seq: int, layer: int, blk: int) -> bytes | None:
        """Fetch a block; cold blocks come back via the DPU offload path."""
        key = (seq, layer, blk)
        if key in self.where:
            self.hits += 1
            self.lru.append(key)  # refresh
            return None  # already in HBM; caller uses the block table
        from repro.core.client import DDSClient
        from repro.storage.pagestore import PageStore
        if self._client is None:
            self._client = DDSClient(self.store.server)
        page, version = self._page_id(key), self.versions.get(key, 0)
        rid = self._client.send_raw(
            0, lambda rid: PageStore.encode_get(rid, page, version))
        status, body = self._client.harvest([rid])[rid]
        self.fetches += 1
        if status != 0:
            return None
        _, payload = PageStore.decode_page(body)
        return payload


# ---------------------------------------------------------------------------
# Continuous batching (minimal).
# ---------------------------------------------------------------------------


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    generated: list[int] = field(default_factory=list)
    done: bool = False


class BatchScheduler:
    """Slot-based continuous batching over a fixed decode batch."""

    def __init__(self, api: ModelAPI, params, slots: int, cache_len: int):
        self.api = api
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.queue: deque[Request] = deque()
        self.active: list[Request | None] = [None] * slots
        self.kv_len = 0
        self.cache = api.init_cache(slots, cache_len)
        self.tokens = np.zeros((slots, 1), np.int32)
        self._decode = jax.jit(api.decode_step)

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                req = self.queue.popleft()
                self.active[i] = req
                self.tokens[i, 0] = int(req.prompt[-1])

    def step(self) -> int:
        """One decode step for all active slots; returns #completed."""
        self._admit()
        if not any(self.active):
            return 0
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(self.kv_len, jnp.int32),
            jnp.asarray(self.tokens))
        self.kv_len = min(self.kv_len + 1, self.cache_len - 1)
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        done = 0
        for i, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(nxt[i])
            req.generated.append(tok)
            self.tokens[i, 0] = tok
            if len(req.generated) >= req.max_new:
                req.done = True
                self.active[i] = None
                done += 1
        return done
