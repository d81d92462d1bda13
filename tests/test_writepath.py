"""Host-path batch overhaul: coalesced writes, burst rings, backpressure.

Covers the PR-3 write pipeline end to end:

  * ring burst APIs (``consume_batch`` single doorbell, ``insert_burst``
    single reservation, ``publish_batch`` gathered delivery);
  * ``SegmentFS.submit_writev`` scatter-gather coalescing (segment-aligned
    runs, cross-segment integrity, read-your-writes barriers);
  * the file service's E_NOSPC backpressure and TailA wrap-pad slots;
  * the zero-copy write invariant (``request_copies == 0`` under a burst);
  * write bursts through ``submit`` on both clients;
  * cache-table ``items()`` stability under cuckoo kicks and the stats
    surfaced through the KV app.
"""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import wire
from repro.core.cache_table import CacheTable
from repro.core.client import ClusterClient, DDSClient
from repro.core.dds_server import (DDSStorageServer, ServerConfig,
                                   encode_app_write)
from repro.core.file_service import FileServiceRunner, SegmentFS
from repro.core.host_lib import DDSFrontEnd
from repro.core.ring import (FRAME_HDR, DMAEngine, ProgressiveRing,
                             ResponseRing, frame, unframe_batch)
from repro.distributed.cluster import DDSCluster
from repro.storage.blockdev import BlockDevice


def make_stack(zero_copy=True, segment_size=1 << 16, capacity=1 << 22,
               resp_buf_size=1 << 22):
    dev = BlockDevice(capacity, block_size=512)
    fs = SegmentFS(dev, segment_size)
    svc = FileServiceRunner(fs, DMAEngine(), zero_copy=zero_copy,
                            resp_buf_size=resp_buf_size)
    fe = DDSFrontEnd(svc, ring_capacity=1 << 14)
    return dev, fs, svc, fe


# ---------------------------------------------------------------------------
# Ring burst APIs
# ---------------------------------------------------------------------------


def test_consume_batch_one_doorbell_per_burst():
    ring = ProgressiveRing(1 << 12)
    dma = DMAEngine()
    msgs = [frame(bytes([i]) * 16) for i in range(8)]
    for m in msgs:
        ring.insert(m)
    before = dma.stats.snapshot()
    batches = ring.consume_batch(dma)
    delta = dma.stats.delta(before)
    assert unframe_batch(b"".join(batches)) == [m[4:] for m in msgs]
    # ONE IncHead doorbell for the whole burst (the only DMA write).
    assert delta.writes == 1
    assert ring.head == ring.tail


def test_consume_batch_empty_ring_no_doorbell():
    ring = ProgressiveRing(1 << 12)
    dma = DMAEngine()
    before = dma.stats.snapshot()
    assert ring.consume_batch(dma) == []
    assert dma.stats.delta(before).writes == 0


def test_insert_burst_single_reservation_fifo():
    ring = ProgressiveRing(1 << 12)
    dma = DMAEngine()
    payloads = [bytes([i]) * (8 + i) for i in range(10)]
    msgs = [(FRAME_HDR.pack(len(p)), p) for p in payloads]
    atomic_before = ring._atom.ops
    ring.insert_burst(msgs)
    # one CAS + one fetch-add for the WHOLE burst
    assert ring._atom.ops - atomic_before == 2
    got = unframe_batch(ring.consume(dma))
    assert got == payloads


def test_insert_burst_chunks_when_exceeding_max_progress():
    ring = ProgressiveRing(1 << 10, max_progress=128)
    dma = DMAEngine()
    payloads = [bytes([i]) * 40 for i in range(12)]  # 44B framed; 2/chunk
    collected = []

    msgs = [(FRAME_HDR.pack(len(p)), p) for p in payloads]
    # Interleave consumption so chunked reservations find space.
    import threading
    t = threading.Thread(target=lambda: ring.insert_burst(msgs))
    t.start()
    while True:
        batch = ring.consume(dma)
        if batch:
            collected += unframe_batch(batch)
        if not t.is_alive() and len(collected) == len(payloads):
            break
    t.join()
    assert collected == payloads


def test_publish_batch_gathers_views_and_wraps():
    ring = ResponseRing(1 << 8)
    dma = DMAEngine()
    # Fill past the wrap point in two bursts, claiming in between.
    first = [frame(b"a" * 100), frame(b"b" * 80)]
    assert ring.publish_batch(dma, [p for m in first
                                    for p in (m[:4], memoryview(m)[4:])])
    _, data = ring.try_claim()
    assert unframe_batch(data) == [b"a" * 100, b"b" * 80]
    second = [frame(b"c" * 120)]  # crosses the ring wrap boundary now
    assert ring.publish_batch(dma, second)
    _, data = ring.try_claim()
    assert unframe_batch(data) == [b"c" * 120]


def test_publish_batch_all_or_nothing_on_overflow():
    ring = ResponseRing(1 << 8)
    dma = DMAEngine()
    tail_before = ring.tail
    assert not ring.publish_batch(dma, [b"x" * 300])  # > capacity
    assert ring.tail == tail_before
    assert ring.try_claim() is None


# ---------------------------------------------------------------------------
# SegmentFS scatter-gather writes
# ---------------------------------------------------------------------------


def test_submit_writev_cross_segment_runs():
    dev = BlockDevice(1 << 20, block_size=512)
    fs = SegmentFS(dev, segment_size=1 << 12)
    fid = fs.create_file("v")
    # 3 buffers, 6000 bytes total -> crosses one segment boundary.
    bufs = [b"A" * 2500, b"B" * 2500, b"C" * 1000]
    writes_before = dev.stats.writes
    assert fs.submit_writev(fid, 0, bufs, cookie=7) == wire.E_OK
    dev.drain()
    assert dev.reap() == [(7, 0)]
    # one gathered device op per physical segment run, not per buffer
    assert dev.stats.writes - writes_before == len(fs.translate(fid, 0, 6000))
    out = bytearray(6000)
    done = []
    fs.submit_read(fid, 0, 6000, memoryview(out), done.append)
    dev.drain()
    assert done == [wire.E_OK]
    assert bytes(out) == b"".join(bufs)


def test_submit_writev_rejects_unknown_file_synchronously():
    dev = BlockDevice(1 << 20, block_size=512)
    fs = SegmentFS(dev, segment_size=1 << 12)
    assert fs.submit_writev(999, 0, [b"x"], cookie=1) == wire.E_NOENT
    dev.drain()
    assert dev.reap() == []  # no completion follows a synchronous reject


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_property_coalesced_writes_cross_segments_intact(data):
    """Bursts of adjacent writes spanning segment boundaries read back
    intact (oracle: a shadow buffer)."""
    _, fs, svc, fe = make_stack(segment_size=1 << 12)
    fid = fe.create_file("prop")
    size = 1 << 14
    shadow = bytearray(size)
    fe.write_sync(fid, 0, bytes(size))
    for _ in range(data.draw(st.integers(1, 4))):
        start = data.draw(st.integers(0, size - 4096))
        ops = []
        off = start
        for _ in range(data.draw(st.integers(1, 6))):
            n = data.draw(st.integers(1, 900))
            if off + n > size:
                break
            payload = bytes([data.draw(st.integers(0, 255))]) * n
            ops.append(("w", fid, off, payload))
            shadow[off : off + n] = payload
            off += n
        if not ops:
            continue
        rids = fe.submit_many(ops)
        comps = {}
        for _ in range(200_000):
            svc.step()
            for c in fe.poll_wait(fe._control_group):
                comps[c.request_id] = c
            if len(comps) == len(rids):
                break
        assert sorted(comps) == rids
        assert all(c.error == wire.E_OK for c in comps.values())
    assert fe.read_sync(fid, 0, size) == bytes(shadow)


def test_write_burst_coalesces_and_acks_per_request():
    dev, _, svc, fe = make_stack(segment_size=1 << 16)
    fid = fe.create_file("log")
    chunk = b"r" * 100
    ops = [("w", fid, i * 100, chunk) for i in range(32)]
    writes_before = dev.stats.writes
    rids = fe.submit_many(ops)
    svc.run_until_idle()
    comps = {c.request_id: c for c in fe.poll_wait(fe._control_group)}
    assert sorted(comps) == rids               # every request acked...
    assert all(c.error == wire.E_OK for c in comps.values())
    assert svc.stats.writes == 32
    assert svc.stats.write_submits < 32        # ...but not one submit each
    assert svc.stats.coalesced_writes > 0
    assert dev.stats.writes - writes_before < 32
    assert fe.read_sync(fid, 0, 3200) == chunk * 32


def test_coalescing_flushes_before_interleaved_read():
    """A read between adjacent writes sees the writes (device-order barrier)."""
    _, _, svc, fe = make_stack()
    fid = fe.create_file("rw")
    fe.write_sync(fid, 0, b"\x00" * 256)
    ops = [("w", fid, 0, b"x" * 64), ("w", fid, 64, b"y" * 64),
           ("r", fid, 0, 128), ("w", fid, 128, b"z" * 64)]
    rids = fe.submit_many(ops)
    svc.run_until_idle()
    comps = {c.request_id: c for c in fe.poll_wait(fe._control_group)}
    assert [comps[r].error for r in rids] == [wire.E_OK] * 4
    assert comps[rids[2]].data == b"x" * 64 + b"y" * 64  # read-your-writes


# ---------------------------------------------------------------------------
# Backpressure (E_NOSPC) and TailA wrap padding
# ---------------------------------------------------------------------------


def test_nospc_response_larger_than_buffer():
    _, _, svc, fe = make_stack(resp_buf_size=1 << 10)
    fid = fe.create_file("big")
    fe.write_sync(fid, 0, bytes(8192))
    rid = fe.read_file(fid, 0, 4096)   # response can never fit: 4096 > 1024
    c = None
    for _ in range(100_000):
        svc.step()
        got = fe.poll_wait(fe._control_group)
        if got:
            c = got[0]
            break
    assert c is not None and c.request_id == rid
    assert c.error == wire.E_NOSPC


def test_nospc_backpressure_sheds_then_recovers():
    """Overflowing the response buffer E_NOSPCs the overflow inline, keeps
    earlier slots intact, and the service recovers once drained."""
    _, _, svc, fe = make_stack(resp_buf_size=1 << 10)
    fid = fe.create_file("bp")
    fe.write_sync(fid, 0, bytes(4096))
    # Each response slot is 16 + 200 bytes; ~4 fit in the 1 KiB buffer.
    rids = [fe.read_file(fid, i * 200, 200) for i in range(12)]
    results = {}
    for _ in range(200_000):
        svc.step()
        for c in fe.poll_wait(fe._control_group):
            results[c.request_id] = c
        if len(results) == len(rids):
            break
    assert len(results) == len(rids)
    errs = [results[r].error for r in rids]
    assert all(e in (wire.E_OK, wire.E_NOSPC) for e in errs)
    assert wire.E_OK in errs                 # forward progress
    # service fully drained: later requests still work
    ok = fe.read_sync(fid, 0, 100)
    assert ok == bytes(100)
    assert not svc._any_pending()


def test_taila_wrap_pad_keeps_responses_contiguous():
    """Responses stream correctly across many response-buffer wraps; pad
    slots occupy space but are never delivered."""
    _, _, svc, fe = make_stack(resp_buf_size=1 << 10)
    fid = fe.create_file("wrap")
    fe.write_sync(fid, 0, bytes(4096))
    # 316-byte slots against a 1024-byte buffer: every third-ish allocation
    # pads to the wrap boundary.
    for i in range(24):
        rid = fe.read_file(fid, (i * 300) % 3700, 300)
        c = fe._wait_one(fid, rid)
        assert c.error == wire.E_OK
        assert c.data == bytes(300)
    assert svc.stats.responses_delivered >= 24
    g = svc.groups[fe._control_group]
    assert not g.pending and not g.ready     # pads consumed, nothing stuck


# ---------------------------------------------------------------------------
# Zero-copy write invariant
# ---------------------------------------------------------------------------


def test_request_copies_zero_under_zero_copy_write_burst():
    _, _, svc, fe = make_stack(zero_copy=True)
    fid = fe.create_file("zc")
    blob = bytes(range(256)) * 4
    ops = [("w", fid, i * 128, memoryview(blob)[:128]) for i in range(64)]
    fe.submit_many(ops)
    svc.run_until_idle()
    fe.poll_wait(fe._control_group)
    assert svc.stats.writes == 64
    assert svc.stats.request_copies == 0     # end-to-end zero-copy writes
    assert svc.stats.response_copies == 0


def test_request_copies_counted_in_straw_man_mode():
    _, _, svc, fe = make_stack(zero_copy=False)
    fid = fe.create_file("cp")
    fe.submit_many([("w", fid, i * 64, b"d" * 64) for i in range(8)])
    svc.run_until_idle()
    assert svc.stats.request_copies == 8


def test_encode_app_write_accepts_memoryview_without_materializing():
    data = bytes(range(64))
    assert (encode_app_write(7, 3, 128, memoryview(data))
            == encode_app_write(7, 3, 128, data))


# ---------------------------------------------------------------------------
# write bursts through submit
# ---------------------------------------------------------------------------


def test_dds_client_submit_writes_single_batch():
    srv = DDSStorageServer(ServerConfig())
    fid = srv.frontend.create_file("wm")
    srv.run_until_idle()
    cli = DDSClient(srv)
    rids = cli.submit([("w", fid, i * 32, bytes([i]) * 32)
                       for i in range(16)])
    assert cli.stats.batches_sent == 1
    for rid in rids:
        status, _ = cli.wait(rid)
        assert status == wire.E_OK
    status, body = cli.wait(cli.read(fid, 0, 16 * 32))
    assert status == wire.E_OK
    assert body == b"".join(bytes([i]) * 32 for i in range(16))


def test_cluster_client_submit_writes_routes_and_coalesces():
    cluster = DDSCluster(num_shards=2,
                         config=ServerConfig(device_capacity=1 << 26))
    files = [cluster.create_file(f"f{i}") for i in range(4)]
    cli = ClusterClient(cluster)
    writes = [(files[i % 4], (i // 4) * 64, bytes([i & 0xFF]) * 64)
              for i in range(32)]
    rids = cli.submit([("w",) + w for w in writes])
    got = cli.harvest(rids)
    assert all(status == wire.E_OK for status, _ in got.values())
    coalesced = sum(s.file_service.stats.coalesced_writes
                    for s in cluster.servers)
    assert coalesced > 0                     # adjacent same-file runs merged
    for i, (gfid, off, data) in enumerate(writes):
        rid = cli.read(gfid, off, 64)
        status, body = cli.wait(rid)
        assert status == wire.E_OK and body == data


# ---------------------------------------------------------------------------
# Cache table: kick-stable items() + surfaced stats
# ---------------------------------------------------------------------------


def test_items_snapshot_stable_under_kicks():
    t = CacheTable(max_items=512, slots_per_bucket=1, load_factor=1.0)
    expect = {}
    for i in range(400):
        key = f"k{i}"
        assert t.insert(key, i)
        expect[key] = i
    assert t.stats.kicks > 0                 # the layout really was kicked
    assert dict(t.items()) == expect
    # items() is a snapshot: mutating mid-iteration neither deadlocks nor
    # perturbs what the snapshot yields.
    it = t.items()
    first = next(it)
    t.insert("fresh", 999)
    t.delete(first[0])
    rest = dict(it)
    assert first[0] not in rest
    assert set(rest) | {first[0]} == set(expect)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 120), st.integers(0, 1_000_000)),
                min_size=1, max_size=300))
def test_property_items_match_dict_after_kick_heavy_churn(ops):
    """items() agrees with a shadow dict through kick-heavy insert/delete
    churn (1-slot buckets at load factor 1.0 maximize relocations)."""
    t = CacheTable(max_items=256, slots_per_bucket=1, load_factor=1.0)
    shadow = {}
    for key_i, val in ops:
        key = f"key-{key_i}"
        if val % 5 == 0 and key in shadow:
            assert t.delete(key)
            del shadow[key]
        elif t.insert(key, val):
            shadow[key] = val
    assert dict(t.items()) == shadow
    assert len(t) == len(shadow)


def test_kv_shard_stats_surface_cache_counters():
    from repro.apps.kv_store import KVClient, ShardedKVStore
    store = ShardedKVStore(num_shards=2,
                           config=ServerConfig(device_capacity=1 << 26))
    cli = KVClient(store)
    loc = cli.wait_put(cli.put(b"alpha", b"1" * 64))
    assert loc.size > 0
    assert cli.wait_value(cli.get(b"alpha")) == b"1" * 64
    stats = store.shard_stats()
    assert len(stats) == 2
    cache = stats[store.shard_for_key(b"alpha")]["cache"]
    for field in ("lookups", "hits", "inserts", "deletes", "kicks",
                  "chain_inserts", "full_rejections"):
        assert field in cache
    assert cache["inserts"] >= 1             # cache-on-write fired
    assert cache["hits"] >= 1                # the GET's predicate hit
    assert stats[store.shard_for_key(b"alpha")]["cache_items"] >= 1
