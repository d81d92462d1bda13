"""Hot-path overhaul: slab pool, flow demux, batch decode, drain sharing.

Covers the PR-2 invariants:

  * the size-classed slab allocator (O(1) allocate/release) never corrupts
    neighboring allocations, reuses freed blocks, bounces to the host on
    exhaustion, and keeps 64-byte alignment;
  * ``reassemble_responses`` consumes many small responses in one pass
    (regression for the old O(n^2) ``del rx[:total]`` loop);
  * the demuxed ``to_client`` wire isolates flows and preserves per-flow
    FIFO order; ``pop_flow``/``drain_flow`` never see foreign packets;
  * ``decode_batch``/``unframe_batch`` return zero-copy views that decode
    identically to the old bytes-slicing implementations;
  * deferred pool release: an undrained response is never overwritten by
    later reads (TX-completion ownership), and draining returns every block.
"""

import struct

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import wire
from repro.core.client import DDSClient
from repro.core.dds_server import (APP_RESP_HDR, DDSStorageServer,
                                   ServerConfig, decode_batch, encode_batch,
                                   reassemble_responses)
from repro.core.offload import PKT_HEADROOM, SlabPool
from repro.core.ring import frame, unframe_batch
from repro.core.traffic import FiveTuple, FlowDemuxWire, Packet


# -- slab allocator ---------------------------------------------------------------------

def test_slab_allocate_release_reuse():
    pool = SlabPool(1 << 16)
    a = pool.allocate(100)          # -> 128 B class
    assert a is not None
    off_a, view_a = a
    assert off_a % 64 == 0 and len(view_a) == 100
    pool.release(off_a, 100)
    b = pool.allocate(120)          # same class: freed block comes right back
    assert b is not None and b[0] == off_a
    assert pool.in_use() == 128
    assert pool.allocs == 2 and pool.failed == 0


def test_slab_alignment_and_distinct_blocks():
    pool = SlabPool(1 << 16)
    seen = set()
    for n in (1, 63, 64, 65, 200, 1000, 4096):
        off, view = pool.allocate(n)
        assert off % 64 == 0
        assert len(view) == n
        for o, ln in seen:
            assert off + len(view) <= o or off >= o + ln, "overlap!"
        seen.add((off, n))


def test_slab_exhaustion_and_borrowed_class_release():
    pool = SlabPool(1 << 10)        # 1 KiB: 8 blocks of the 128 B class
    offs = []
    while True:
        a = pool.allocate(128)
        if a is None:
            break
        offs.append(a[0])
    assert len(offs) == 8 and pool.failed == 1
    # free one big-class... release one and allocate a SMALLER request: the
    # bump region is gone, so the 64 B request borrows the freed 128 B block
    pool.release(offs[0], 128)
    b = pool.allocate(32)
    assert b is not None and b[0] == offs[0]
    # releasing the borrowed block returns it to its TRUE (128 B) class
    pool.release(b[0], 32)
    c = pool.allocate(128)
    assert c is not None and c[0] == offs[0]


def test_slab_double_release_raises():
    pool = SlabPool(1 << 12)
    off, _ = pool.allocate(64)
    pool.release(off, 64)
    with pytest.raises(ValueError):
        pool.release(off, 64)


def test_slab_occupancy_accounting():
    pool = SlabPool(1 << 16)
    pool.allocate(100)              # 128 class
    pool.allocate(200)              # 256 class
    occ = pool.occupancy()
    assert occ["live_bytes"] == 300
    assert occ["committed_bytes"] == 128 + 256
    assert occ["internal_frag_bytes"] == (128 - 100) + (256 - 200)
    assert occ["classes"][128]["live"] == 1
    assert occ["classes"][256]["live"] == 1


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 2048), st.booleans()),
                min_size=1, max_size=64))
def test_slab_sequences_never_corrupt_neighbors(ops):
    """Random allocate/release sequences: every live block keeps its bytes.

    Each allocation is filled with its own tag; at every step every live
    allocation must still hold its tag — a slab handing out overlapping
    blocks (or resurrecting a released offset) would scribble on a neighbor.
    """
    pool = SlabPool(1 << 14)
    live: dict[int, tuple[memoryview, int, int]] = {}  # off -> (view, n, tag)
    tag = 0
    for n, do_release in ops:
        if do_release and live:
            off = next(iter(live))
            view, sz, t = live.pop(off)
            assert bytes(view) == bytes([t]) * sz, "corrupted before release"
            pool.release(off, sz)
        else:
            a = pool.allocate(n)
            if a is None:
                continue            # exhausted: allocator said so honestly
            off, view = a
            tag = (tag + 1) % 251
            view[:] = bytes([tag]) * n
            assert off not in live
            live[off] = (view, n, tag)
        for off, (view, sz, t) in live.items():
            assert bytes(view) == bytes([t]) * sz, f"block {off} corrupted"


def test_slab_reset_when_fully_free_serves_larger_class():
    """A pool carved into small classes, once fully drained, must still be
    able to serve a larger class (no permanent starvation)."""
    pool = SlabPool(1 << 12)        # 4 KiB
    offs = []
    while (a := pool.allocate(64)) is not None:
        offs.append(a[0])
    assert len(offs) == 64          # bump fully carved into the 64 B class
    for off in offs:
        pool.release(off, 64)
    big = pool.allocate(2048)       # larger than any carved class
    assert big is not None and len(big[1]) == 2048
    assert pool.in_use() == 2048


def test_failed_offloaded_read_reports_real_request_id():
    """An offloaded read that fails at the device still answers ITS rid."""
    srv = DDSStorageServer(ServerConfig())
    fid = srv.frontend.create_file("short")
    srv.frontend.write_sync(fid, 0, bytes(512))
    srv.run_until_idle()
    cli = DDSClient(srv)
    rid = cli.read(fid, 0, 4096)    # beyond EOF: submit fails (E_INVAL)
    status, body = cli.wait(rid)    # must NOT time out on req_id 0
    assert status != wire.E_OK and body == b""
    assert srv.offload.stats.failed == 1


def test_pool_exhaustion_bounces_to_host():
    """A pool too small for the read forces the host path — no data loss."""
    srv = DDSStorageServer(ServerConfig(offload_pool=1 << 12))
    fid = srv.frontend.create_file("big")
    srv.frontend.write_sync(fid, 0, bytes(range(256)) * 64)
    srv.run_until_idle()
    cli = DDSClient(srv)
    status, body = cli.wait(cli.read(fid, 0, 8192))  # > pool
    assert status == wire.E_OK and len(body) == 8192
    assert srv.offload.stats.bounced_to_host == 1
    assert srv.offload.pool.failed >= 1


# -- reassembly (O(n) regression test) -------------------------------------------------

def test_reassemble_many_small_responses_single_pass():
    rx = bytearray()
    for rid in range(1, 501):
        body = bytes([rid & 0xFF]) * 3
        rx += APP_RESP_HDR.pack(rid, 0, len(body)) + body
    rx += APP_RESP_HDR.pack(999, 0, 100)[:8]     # trailing partial header
    responses: dict = {}
    order: list = []
    n = reassemble_responses(rx, responses, order)
    assert n == 500 and len(responses) == 500
    assert order == list(range(1, 501))
    assert responses[7] == (0, b"\x07\x07\x07")
    assert bytes(rx) == APP_RESP_HDR.pack(999, 0, 100)[:8]  # partial kept


def test_reassemble_partial_body_left_for_next_call():
    rx = bytearray(APP_RESP_HDR.pack(1, 0, 10) + b"12345")
    responses: dict = {}
    assert reassemble_responses(rx, responses) == 0
    rx += b"67890"
    assert reassemble_responses(rx, responses) == 1
    assert responses[1] == (0, b"1234567890") and len(rx) == 0


# -- flow demux -------------------------------------------------------------------------

def _flow(port):
    return FiveTuple("10.0.0.2", port, "10.0.0.1", 5000)


def test_flow_demux_isolates_flows_and_keeps_fifo():
    w = FlowDemuxWire("t")
    a, b = _flow(1), _flow(2)
    for i in range(3):
        w.push(Packet(a, i, b"a%d" % i))
        w.push(Packet(b, i, b"b%d" % i))
    assert len(w) == 6
    assert [bytes(p.payload) for p in w.drain_flow(a)] == [b"a0", b"a1", b"a2"]
    assert w.pop_flow(a) is None                 # a is empty; b untouched
    assert bytes(w.pop_flow(b).payload) == b"b0"
    assert [bytes(p.payload) for p in w.drain_flow(b)] == [b"b1", b"b2"]
    assert len(w) == 0 and w.pop() is None


def test_flow_demux_push_many_and_generic_pop():
    w = FlowDemuxWire("t")
    a = _flow(7)
    w.push_many(a, [Packet(a, 0, b"x"), Packet(a, 1, b"y")])
    assert len(w) == 2
    assert bytes(w.pop().payload) == b"x"        # per-flow FIFO via pop()
    assert bytes(w.pop_flow(a).payload) == b"y"


def test_packet_consumed_releases_pool_block_once():
    """Single-packet consumers (pop_flow) release ownership via consumed()."""
    pool = SlabPool(1 << 12)
    off, view = pool.allocate(100)
    pkt = Packet(_flow(1), 0, view, pool_ref=(pool, off, 100))
    pkt.consumed()
    assert pool.in_use() == 0
    pkt.consumed()                  # idempotent: ref cleared on first call
    assert pool.allocate(100)[0] == off


# -- zero-copy batch decode -------------------------------------------------------------

def test_decode_batch_views_match_bytes_and_are_zero_copy():
    msgs = [b"alpha", b"", b"x" * 2000, struct.pack("<I", 7)]
    payload = encode_batch(msgs)
    out = decode_batch(payload)
    assert [bytes(m) for m in out] == msgs
    assert all(isinstance(m, memoryview) for m in out)
    assert out[2].obj is payload                 # a view INTO the buffer


def test_unframe_batch_views_match_bytes():
    msgs = [b"r1", b"longer-message" * 10, b""]
    batch = b"".join(frame(m) for m in msgs)
    out = unframe_batch(batch)
    assert [bytes(m) for m in out] == msgs
    assert all(isinstance(m, memoryview) for m in out)


# -- harvest: no head-of-line blocking --------------------------------------------------

def test_harvest_collects_out_of_order_completions():
    """rids are collected as they arrive, regardless of the order given."""
    from repro.core.client import ClusterClient
    from repro.distributed.cluster import DDSCluster

    cl = DDSCluster(num_shards=2)
    fids = [cl.create_file(f"w{i}") for i in range(4)]
    for i, f in enumerate(fids):
        cl.write_sync(f, 0, bytes([i + 1]) * 4096)
    cc = ClusterClient(cl)
    rids = [cc.read(f, 0, 64) for f in fids for _ in range(3)]
    cc.flush()
    # ask for the rids in REVERSE order: a serial per-rid wait would block
    # on the last-issued rid while all the others sit ready
    res = cc.harvest(list(reversed(rids)))
    assert set(res) == set(rids)
    for k, rid in enumerate(rids):
        status, body = res[rid]
        assert status == 0 and body == bytes([k // 3 + 1]) * 64
    assert cc.outstanding() == 0


# -- deferred pool release (TX-completion ownership) -----------------------------------

def test_undrained_responses_survive_later_reads():
    """Responses left on the wire keep their bytes while new reads execute."""
    srv = DDSStorageServer(ServerConfig())
    fid = srv.frontend.create_file("f")
    srv.frontend.write_sync(fid, 0, bytes([i & 0xFF for i in range(16384)]))
    srv.run_until_idle()
    cli = DDSClient(srv)
    # issue many reads but do NOT collect between pumps: every response
    # sits on the demuxed wire referencing pool memory
    rids = cli.submit([("r", fid, i * 64, 64) for i in range(64)])
    for _ in range(200):
        if len(srv.director.to_client) >= 64:
            break
        srv.pump()
        srv.device.drain()
    assert srv.offload.pool.in_use() > 0         # blocks still owned by wire
    for _ in range(2000):
        cli.poll()
        if len(cli.responses) == len(rids):
            break
        srv.pump()
    expect = bytes([i & 0xFF for i in range(16384)])
    for k, rid in enumerate(rids):
        status, body = cli.responses[rid]
        assert status == wire.E_OK
        assert body == expect[k * 64 : k * 64 + 64], f"read {k} corrupted"
    assert srv.offload.pool.in_use() == 0        # every block came back
    assert srv.offload.stats.data_copies == 0    # still zero-copy
