"""End-to-end DDS storage server behaviour (§8.1 app + §9 integrations)."""

import pytest

from repro.core import wire
from repro.core.client import DDSClient
from repro.core.dds_server import DDSStorageServer, ServerConfig
from repro.storage.pagestore import KVStoreServer, PageStore


@pytest.fixture()
def server():
    srv = DDSStorageServer(ServerConfig())
    fid = srv.frontend.create_file("bench.dat")
    srv.frontend.write_sync(fid, 0, bytes(range(256)) * 64)  # 16 KiB
    srv.run_until_idle()
    return srv, fid


def test_offloaded_read(server):
    srv, fid = server
    cli = DDSClient(srv)
    rid = cli.read(fid, 512, 256)
    status, body = cli.wait(rid)
    assert status == wire.E_OK
    assert body == (bytes(range(256)) * 64)[512:768]
    assert srv.offload.stats.completed == 1
    assert srv.director.stats.to_dpu == 1
    assert srv.host_cpu_busy_s == 0.0       # zero host CPU on the read path


def test_write_takes_host_path(server):
    srv, fid = server
    cli = DDSClient(srv)
    rid = cli.write(fid, 0, b"W" * 128)
    status, _ = cli.wait(rid)
    assert status == wire.E_OK
    assert srv.director.stats.to_host == 1
    assert srv.host_cpu_busy_s > 0.0        # writes burn host CPU (Fig 14b)
    rid = cli.read(fid, 0, 128)
    status, body = cli.wait(rid)
    assert body == b"W" * 128               # read-your-writes through the DPU


def test_mixed_batch_splits(server):
    """One network message with reads+writes splits between DPU and host."""
    srv, fid = server
    cli = DDSClient(srv)
    rids = cli.submit([("r", fid, 0, 64), ("w", fid, 4096, b"x" * 64),
                           ("r", fid, 64, 64)])
    results = {r: cli.wait(r) for r in rids}
    assert all(status == wire.E_OK for status, _ in results.values())
    assert srv.director.stats.to_dpu == 2
    assert srv.director.stats.to_host == 1


def test_large_read_segmented_and_reassembled(server):
    srv, fid = server
    cli = DDSClient(srv)
    rid = cli.read(fid, 0, 8192)            # > MTU: multiple packets
    status, body = cli.wait(rid)
    assert status == wire.E_OK and len(body) == 8192
    assert srv.offload.stats.packets > 5


def test_zero_copy_accounting(server):
    srv, fid = server
    cli = DDSClient(srv)
    status, _ = cli.wait(cli.read(fid, 0, 2048))
    assert status == wire.E_OK
    assert srv.offload.stats.data_copies == 0


def test_context_ring_full_bounces_to_host():
    cfg = ServerConfig(offload_ring=2)
    srv = DDSStorageServer(cfg)
    fid = srv.frontend.create_file("f")
    srv.frontend.write_sync(fid, 0, bytes(4096))
    srv.run_until_idle()
    cli = DDSClient(srv)
    rids = [cli.read(fid, i * 64, 64) for i in range(8)]
    for r in rids:
        status, body = cli.wait(r)
        assert status == wire.E_OK and len(body) == 64
    # with a 2-slot ring under 8 outstanding reads, some must have bounced
    assert srv.offload.stats.bounced_to_host + srv.offload.stats.completed == 8


def test_page_store_lsn_semantics():
    ps = PageStore()
    ps.replay(3, lsn=50, payload=b"v50")
    cli = DDSClient(ps.server)
    status, body = cli.wait(cli.send_raw(
        0, lambda rid: PageStore.encode_get(rid, 3, 50)))
    lsn, payload = PageStore.decode_page(body)
    assert (status, lsn) == (wire.E_OK, 50) and payload[:3] == b"v50"
    assert ps.server.offload.stats.completed == 1   # served by the DPU
    # requested LSN newer than cached -> host serves (partial offload)
    status, body = cli.wait(cli.send_raw(
        0, lambda rid: PageStore.encode_get(rid, 3, 99)))
    assert status == wire.E_OK and ps.host_served == 1
    # invalidate-on-read: host pulls the page back -> next GET -> host
    ps.host_read_for_update(3)
    cli.wait(cli.send_raw(0, lambda rid: PageStore.encode_get(rid, 3, 10)))
    assert ps.host_served == 2


def test_kv_store_tail_vs_disk():
    kv = KVStoreServer()
    kv.upsert(b"cold", b"on-disk-value")
    kv.flush()                                # -> cache-on-write fires
    kv.upsert(b"hot", b"tail-value")          # stays in the mutable tail
    cli = DDSClient(kv.server)
    status, body = cli.wait(cli.send_raw(
        0, lambda rid: KVStoreServer.encode_get(rid, b"cold")))
    k, v = KVStoreServer.decode_record(body)
    assert (k, v) == (b"cold", b"on-disk-value")
    assert kv.server.offload.stats.completed == 1   # DPU-served
    status, body = cli.wait(cli.send_raw(
        0, lambda rid: KVStoreServer.encode_get(rid, b"hot")))
    k, v = KVStoreServer.decode_record(body)
    assert (k, v) == (b"hot", b"tail-value")        # host-served (RMW data)
    status, body = cli.wait(cli.send_raw(
        0, lambda rid: KVStoreServer.encode_get(rid, b"missing")))
    assert status == wire.E_NOENT


def test_kv_rmw_on_host():
    kv = KVStoreServer()
    kv.upsert(b"ctr", (0).to_bytes(8, "little"))
    kv.flush()
    for _ in range(5):
        kv.rmw(b"ctr", lambda cur: (
            int.from_bytes(cur or bytes(8), "little") + 1).to_bytes(8, "little"))
    assert int.from_bytes(kv.get_local(b"ctr"), "little") == 5


def test_host_only_baseline_mode():
    """offload_enabled=False: everything is hardware-forwarded to the host."""
    srv = DDSStorageServer(ServerConfig(offload_enabled=False))
    fid = srv.frontend.create_file("base")
    srv.frontend.write_sync(fid, 0, bytes(1024))
    srv.run_until_idle()
    cli = DDSClient(srv)
    status, body = cli.wait(cli.read(fid, 0, 128))
    assert status == wire.E_OK and len(body) == 128
    assert srv.offload.stats.completed == 0
    assert srv.director.stats.hw_forwarded >= 1
