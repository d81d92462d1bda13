"""Tail-latency overhaul: deterministic tick clock, priority demux, sheds.

Covers the PR-5 latency machinery end to end:

  * the BlockDevice priority queue: offloaded reads served first, per-queue
    FIFO, completion-latency histograms in ``stats``, and the PROPERTY that
    the write-interleave budget bounds starvation (every write completes
    within a computable number of polls under sustained priority-read load);
  * tick-clock determinism: two identical cluster runs produce byte-
    identical latency histograms (server lifecycle, client end-to-end, and
    device histograms);
  * per-flow FIFO is preserved under priority demux;
  * latency-adaptive write coalescing: adjacent writes from SEPARATE ring
    batches merge into one scatter-gather submission, bounded by the tick
    budget / ring-idle flush;
  * cache-on-write fires at device COMPLETION, never at submission;
  * the read/write fence (``ServerConfig.read_write_fence``) bounces reads
    of files whose writes are still in the file-service pipeline to the
    host FIFO (read-your-writes for anything the file service accepted);
  * terminal SHED status: a request the file service shed under overload is
    surfaced by ``DDSClient.wait`` / ``ClusterClient.harvest`` as
    ``wire.E_SHED`` instead of spinning into a timeout.
"""

import json
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import wire
from repro.core.client import ClusterClient, DDSClient
from repro.core.dds_server import DDSStorageServer, ServerConfig
from repro.core.file_service import FileServiceRunner, SegmentFS
from repro.core.host_lib import DDSFrontEnd
from repro.core.lifecycle import TickClock, TickHistogram
from repro.core.qos import QoSProfile
from repro.distributed.cluster import DDSCluster
from repro.storage.blockdev import BlockDevice


# ---------------------------------------------------------------------------
# TickHistogram
# ---------------------------------------------------------------------------


def test_tick_histogram_exact_percentiles():
    h = TickHistogram()
    for d, k in [(1, 90), (5, 9), (40, 1)]:
        for _ in range(k):
            h.add(d)
    assert h.n == 100
    assert h.percentile(50) == 1
    assert h.percentile(95) == 5
    assert h.percentile(99) == 5
    assert h.percentile(100) == 40
    assert h.summary()["p99"] == 5
    merged = TickHistogram()
    merged.merge(h)
    merged.merge(h)
    assert merged.n == 200 and merged.as_dict() == {"1": 180, "5": 18,
                                                    "40": 2}


# ---------------------------------------------------------------------------
# BlockDevice priority queue + completion histograms
# ---------------------------------------------------------------------------


def _dev(**kw):
    return BlockDevice(1 << 20, **kw)


def test_blockdev_completion_histogram_in_stats():
    dev = _dev(queue_depth=4)
    buf = bytearray(64)
    dev.submit_write(0, b"x" * 64)
    dev.clock.tick()
    dev.clock.tick()
    dev.poll()                       # completes 2 ticks after submission
    dev.submit_read(0, 64, memoryview(buf))
    dev.poll()                       # completes the tick it was submitted
    h = dev.stats.completion_ticks
    assert h.n == 2
    assert h.as_dict() == {"0": 1, "2": 1}
    assert h.summary()["max"] == 2
    assert dev.stats.prio_completion_ticks.n == 0


def test_priority_reads_served_before_write_backlog():
    dev = _dev(queue_depth=8, prio_interleave=4)
    done: list[str] = []
    for i in range(12):
        dev.submit_write(i * 4096, b"w" * 64,
                         on_complete=lambda s, i=i: done.append(f"w{i}"))
    bufs = [bytearray(64) for _ in range(3)]
    for i, b in enumerate(bufs):
        dev.submit_read(0, 64, memoryview(b), priority=True,
                        on_complete=lambda s, i=i: done.append(f"r{i}"))
    dev.poll()
    # One poll, budget 8: the 3 priority reads first (in order), then the
    # reserved-normal share fills the rest of the budget (in order).
    assert done[:3] == ["r0", "r1", "r2"]
    assert done[3:] == ["w0", "w1", "w2", "w3", "w4"]
    assert dev.stats.prio_completion_ticks.n == 3
    dev.drain()
    assert [d for d in done if d[0] == "w"] == [f"w{i}" for i in range(12)]


def test_normal_share_reserved_under_priority_pressure():
    dev = _dev(queue_depth=8, prio_interleave=4)
    done: list[str] = []
    for i in range(4):
        dev.submit_write(i * 4096, b"w" * 64,
                         on_complete=lambda s, i=i: done.append(f"w{i}"))
    for i in range(20):
        dev.submit_read(0, 64, memoryview(bytearray(64)), priority=True,
                        on_complete=lambda s, i=i: done.append(f"r{i}"))
    dev.poll()
    # budget 8, interleave 4 => >= 2 normal completions despite 20 reads.
    assert done.count("w0") + done.count("w1") == 2
    assert sum(1 for d in done if d[0] == "r") == 6


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 48), st.integers(4, 32), st.integers(2, 8))
def test_write_interleave_budget_prevents_starvation(n_writes, budget,
                                                     interleave):
    """Every write completes within the computable starvation bound even
    under SUSTAINED priority-read load that saturates the poll budget."""
    dev = _dev(queue_depth=budget, prio_interleave=interleave)
    for i in range(n_writes):
        dev.submit_write(i * 4096, b"w" * 64)
    share = max(1, budget // interleave)
    bound = math.ceil(n_writes / share)
    for _ in range(bound + 2):
        # saturate the priority queue every tick
        for _ in range(budget):
            dev.submit_read(0, 64, memoryview(bytearray(64)), priority=True)
        dev.clock.tick()
        dev.poll()
    h = dev.stats.completion_ticks      # normal-queue (write) completions
    assert h.n == n_writes
    assert max(h.counts) <= bound + 1, (
        f"write starved: completed {max(h.counts)} ticks after submit, "
        f"bound {bound} (W={n_writes} budget={budget} share={share})")


# ---------------------------------------------------------------------------
# Tick-clock determinism
# ---------------------------------------------------------------------------


def _mixed_run(seed: int) -> str:
    import random
    cluster = DDSCluster(num_shards=2,
                         config=ServerConfig(device_capacity=1 << 24,
                                             cache_items=1 << 10))
    for srv in cluster.servers:
        srv.device.queue_depth = 8
    fids = [cluster.create_file(f"det{i}") for i in range(6)]
    for f in fids:
        cluster.write_sync(f, 0, b"\x01" * 8192)
    cli = ClusterClient(cluster, port=45500)
    rng = random.Random(seed)
    for _ in range(30):
        cli.submit([("r", fids[rng.randrange(6)], rng.randrange(0, 7936), 128)
                    for _ in range(8)])
        cli.submit([("w", fids[rng.randrange(6)], rng.randrange(0, 15) * 512,
                     b"z" * 128) for _ in range(4)])
        cli.flush()
        cluster.pump()
        cli.poll()
    cli.run_until_idle()
    while cli.poll():
        pass
    doc = {
        "server": cluster.latency_histograms(),
        "client": cli.latency.histograms(),
        "device": [srv.device.stats.completion_ticks.as_dict()
                   for srv in cluster.servers],
        "device_prio": [srv.device.stats.prio_completion_ticks.as_dict()
                        for srv in cluster.servers],
    }
    return json.dumps(doc, sort_keys=True)


def test_two_identical_runs_identical_histograms():
    a = _mixed_run(123)
    b = _mixed_run(123)
    assert a == b
    # and the histograms are non-trivial (something was measured)
    doc = json.loads(a)
    assert doc["server"].get("dpu_read")
    assert doc["server"].get("write")
    assert doc["client"].get("read") and doc["client"].get("write")


def test_cluster_latency_stats_classes():
    import random
    cluster = DDSCluster(num_shards=2,
                         config=ServerConfig(device_capacity=1 << 24))
    fid = cluster.create_file("stats")
    cluster.write_sync(fid, 0, b"\x05" * 4096)
    cli = ClusterClient(cluster, port=45600)
    rng = random.Random(1)
    rids = cli.submit([("r", fid, rng.randrange(0, 3968), 64)
                       for _ in range(16)])
    rids += cli.submit([("w", fid, 4096, b"y" * 64)])
    cli.flush()
    cli.harvest(rids)
    stats = cluster.latency_stats()
    assert stats["classes"]["dpu_read"]["count"] == 16
    assert stats["classes"]["write"]["count"] == 1
    assert stats["device_prio"]["count"] == 16
    # client-side end-to-end view
    lat = cli.latency.summary()
    assert lat["read"]["count"] == 16 and lat["write"]["count"] == 1
    # per-server view includes ring residency once host traffic flowed
    srv_stats = cluster.servers[0].latency_stats()
    assert "classes" in srv_stats


# ---------------------------------------------------------------------------
# Per-flow FIFO under priority demux
# ---------------------------------------------------------------------------


def test_per_flow_fifo_preserved_under_priority_demux():
    cluster = DDSCluster(num_shards=1,
                         config=ServerConfig(device_capacity=1 << 24))
    fid = cluster.create_file("fifo")
    cluster.write_sync(fid, 0, b"\x02" * 65536)
    reader = ClusterClient(cluster, port=45700)
    writer = ClusterClient(cluster, port=45800)
    read_rids, write_rids = [], []
    for r in range(6):
        read_rids += reader.submit([("r", fid, 128 * i, 64)
                                    for i in range(10)])
        write_rids += writer.submit([("w", fid, 65536 + 1024 * i, b"q" * 64)
                                     for i in range(5)])
        reader.flush()
        writer.flush()
        cluster.pump()
        reader.poll()
        writer.poll()
    reader.run_until_idle()
    writer.run_until_idle()
    while reader.poll() or writer.poll():
        pass
    # Responses on each flow arrive EXACTLY in issue order: priority demux
    # reorders across queues/flows, never within a flow.
    assert reader.conns[0].arrival_order == read_rids
    assert writer.conns[0].arrival_order == write_rids


# ---------------------------------------------------------------------------
# Latency-adaptive write coalescing (cross-batch holds, bounded age)
# ---------------------------------------------------------------------------


def _stack(**kw):
    dev = BlockDevice(1 << 22)
    fs = SegmentFS(dev, 1 << 16)
    svc = FileServiceRunner(fs, **kw)
    fe = DDSFrontEnd(svc, ring_capacity=1 << 14)
    return dev, fs, svc, fe


def test_adjacent_writes_across_batches_coalesce_once():
    dev, _, svc, fe = _stack(coalesce_ticks=4)
    fid = fe.create_file("xbatch")
    submits_before = svc.stats.write_submits
    # Two separate ring publishes => two consume batches; adjacent offsets.
    fe.write_file(fid, 0, b"a" * 100)
    svc.step()                 # batch 1 fetched; run HELD (age 0 < 4)
    fe.write_file(fid, 100, b"b" * 100)
    svc.step()                 # batch 2 extends the held run
    svc.run_until_idle()       # ring idle => flush; completes
    assert svc.stats.writes == 2
    assert svc.stats.write_submits - submits_before == 1   # ONE writev
    assert svc.stats.coalesced_writes >= 1
    assert fe.read_sync(fid, 0, 200) == b"a" * 100 + b"b" * 100


def test_held_run_flushes_at_tick_budget_under_continuous_load():
    dev, _, svc, fe = _stack(coalesce_ticks=2)
    fid = fe.create_file("aged")
    off = 0
    first_submit_step = None
    for step in range(6):      # continuous adjacent write traffic
        fe.write_file(fid, off, b"c" * 64)
        off += 64
        svc.step()
        if first_submit_step is None and svc.stats.write_submits:
            first_submit_step = step
    # The run must NOT wait for the traffic to stop: the age budget flushed
    # it within coalesce_ticks steps of the run opening.
    assert first_submit_step is not None and first_submit_step <= 2
    svc.run_until_idle()
    assert fe.read_sync(fid, 0, off) == b"c" * off


def test_read_flushes_held_run_first():
    dev, _, svc, fe = _stack(coalesce_ticks=50)   # age alone would hold long
    fid = fe.create_file("barrier")
    fe.write_sync(fid, 0, b"\x00" * 256)
    fe.write_file(fid, 0, b"x" * 64)
    svc.step()                                     # held
    rid = fe.read_file(fid, 0, 64)
    for _ in range(50):
        svc.step()
        comps = {c.request_id: c for c in fe.poll_wait(fe._control_group)}
        if rid in comps:
            assert comps[rid].data == b"x" * 64    # read-your-writes
            return
    raise AssertionError("read did not complete")


def test_cache_hook_fires_at_completion_not_submission():
    calls = []
    dev = BlockDevice(1 << 22)
    fs = SegmentFS(dev, 1 << 16)
    svc = FileServiceRunner(
        fs, coalesce_ticks=0,
        cache_hook=lambda fid, off, data: calls.append(
            (fid, off, bytes(data), dev.stats.writes)))
    fe = DDSFrontEnd(svc, ring_capacity=1 << 14)
    fid = fe.create_file("cachet")
    writes_before = dev.stats.writes
    fe.write_file(fid, 0, b"h" * 64)
    svc.run_until_idle()
    assert len(calls) == 1
    cfid, coff, cdata, writes_at_call = calls[0]
    assert (cfid, coff, cdata) == (fid, 0, b"h" * 64)
    # the device had ALREADY executed the write when the hook fired
    assert writes_at_call > writes_before


# ---------------------------------------------------------------------------
# Read/write fence: pipelined read-your-writes with priority demux
# ---------------------------------------------------------------------------


def test_read_write_fence_bounces_fenced_reads_to_host():
    """A read of a file whose writes are still in the file-service pipeline
    (held / ring-queued / at the device) is bounced to the host, where the
    submission FIFO orders it after them — fresh bytes despite the device
    priority queue."""
    srv = DDSStorageServer(ServerConfig(
        device_capacity=1 << 24, qos=QoSProfile(read_write_fence=True)))
    srv.device.queue_depth = 1           # keep the write backlog alive
    cli = DDSClient(srv)
    fid = srv.frontend.create_file("fence")
    srv.frontend.write_sync(fid, 0, b"\x00" * 65536)
    srv.run_until_idle()
    # Strided (non-coalescing) writes: a real multi-op device backlog.
    wrids = cli.submit([("w", fid, 1024 * i, bytes([i]) * 128)
                        for i in range(24)])
    srv.pump()                           # writes reach the file service
    assert srv.file_service.write_inflight.get(fid, 0) > 0
    rrid = cli.read(fid, 1024 * 23, 128)   # read bytes of the LAST write
    got = cli.wait(rrid)
    assert got == (wire.E_OK, bytes([23]) * 128)   # fresh, not stale
    assert srv.offload.stats.bounced_to_host >= 1  # the fence rerouted it
    # lifecycle classified the bounced read as host-served
    assert srv.lifecycle.hist["host_read"].n >= 1
    for rid in wrids:
        assert cli.wait(rid)[0] == wire.E_OK


# ---------------------------------------------------------------------------
# Bounded host-wire drain slices
# ---------------------------------------------------------------------------


def test_drain_host_wire_bounded_slice_keeps_server_busy():
    srv = DDSStorageServer(ServerConfig(device_capacity=1 << 24))
    cli = DDSClient(srv)
    fid = srv.frontend.create_file("slice")
    srv.run_until_idle()
    # 12 single-write messages => 12 packets on the host wire.
    for i in range(12):
        cli.write(fid, 64 * i, b"s" * 64)
    srv.director.step_n(64)
    n = srv.host_app.step(max_pkts=5)      # one bounded drain slice
    assert n == 5
    assert bool(srv.director.to_host)      # remainder still queued
    assert srv.director.busy()             # server stays runnable
    srv.run_until_idle()
    for rid in range(1, 13):
        assert cli.wait(rid)[0] == wire.E_OK


# ---------------------------------------------------------------------------
# Terminal SHED status
# ---------------------------------------------------------------------------


def test_file_service_shed_hook_fires_with_request_id():
    sheds: list[int] = []
    dev = BlockDevice(1 << 22)
    fs = SegmentFS(dev, 1 << 16)
    svc = FileServiceRunner(fs, resp_buf_size=1 << 10,
                            shed_hook=sheds.append)
    fe = DDSFrontEnd(svc, ring_capacity=1 << 8)   # tiny response ring
    fid = fe.create_file("shed")
    # Flood reads; NEVER drain the response ring: slots exhaust the small
    # response buffer, inline E_NOSPC completions fill the tiny ring, and
    # the bounded emergency path gives up — SHED.
    rids = []
    for i in range(16):
        rids.append(fe.read_file(fid, 0, 200))
        svc.step()
    assert svc.stats.shed_requests > 0
    assert sheds and set(sheds) <= set(rids)


def test_client_wait_surfaces_shed_as_terminal_status():
    srv = DDSStorageServer(ServerConfig(device_capacity=1 << 24))
    cli = DDSClient(srv)
    fid = srv.frontend.create_file("shedcli")
    srv.run_until_idle()
    rid = cli.write(fid, 0, b"gone" * 16)
    # Deliver the request to the host app but stop before the file service
    # runs, then simulate the file service shedding it.
    srv.director.step_n(64)
    srv.host_app.step()
    frontend_rids = list(srv.host_app._inflight)
    assert len(frontend_rids) == 1
    srv.file_service.shed_hook(frontend_rids[0])   # the wired _on_shed
    status, body = cli.wait(rid, max_iters=2_000)  # no timeout spin
    assert status == wire.E_SHED
    # Overload sheds carry a retry-after hint (tenant 0, retry next tick).
    assert wire.decode_shed_hint(body) == (0, 1)
    assert not srv.host_app.busy()                 # in-flight entry dropped
    assert not srv.frontend.any_outstanding()      # booking cancelled
    assert srv.lifecycle.sheds == 1
    srv.run_until_idle()                           # server fully quiesces


def test_shed_during_submit_many_reentry_is_not_lost():
    """A shed that fires INSIDE frontend.submit_many (the ring-full
    on_retry re-entrantly steps the file service) lands before the host
    app records its in-flight meta; the orphan-shed reconcile must still
    mark it terminally instead of leaking a forever-pending request."""
    srv = DDSStorageServer(ServerConfig(device_capacity=1 << 24))
    cli = DDSClient(srv)
    fid = srv.frontend.create_file("reentry")
    srv.run_until_idle()
    rid = cli.write(fid, 0, b"lost?" * 8)
    srv.director.step_n(64)
    # Simulate the re-entrant window: the file service sheds the frontend
    # rid BEFORE _execute_burst has booked it (submit_many not yet run).
    g = srv.frontend._groups[srv.frontend._control_group]
    next_rid = g._next_rid              # the rid submit_many will assign
    srv.file_service.shed_hook(next_rid)
    assert next_rid in srv.host_app._orphan_sheds   # parked, not dropped
    srv.host_app.step()                 # books the meta + reconciles
    assert not srv.host_app._orphan_sheds
    assert next_rid not in srv.host_app._inflight   # meta did not leak
    status, body = cli.wait(rid, max_iters=2_000)
    assert status == wire.E_SHED and wire.decode_shed_hint(body) == (0, 1)
    srv.run_until_idle()                # server quiesces; nothing pinned


def test_cluster_harvest_surfaces_shed():
    cluster = DDSCluster(num_shards=1,
                         config=ServerConfig(device_capacity=1 << 24))
    fid = cluster.create_file("shedmany")
    cluster.write_sync(fid, 0, b"\x00" * 4096)
    cli = ClusterClient(cluster, port=45900)
    ok_rid = cli.read(fid, 0, 64)
    shed_rid = cli.write(fid, 1024, b"x" * 64)
    cli.flush()
    srv = cluster.servers[0]
    srv.director.step_n(64)
    srv.host_app.step()
    # Shed the write while it is in flight on the host path.
    frontend_rids = list(srv.host_app._inflight)
    assert frontend_rids
    srv.file_service.shed_hook(frontend_rids[0])
    got = cli.harvest([ok_rid, shed_rid], max_iters=20_000)
    assert got[ok_rid][0] == wire.E_OK
    assert got[shed_rid][0] == wire.E_SHED
    assert wire.decode_shed_hint(got[shed_rid][1]) == (0, 1)
    assert cli.outstanding() == 0
