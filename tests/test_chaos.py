"""Lossy-network survival (the chaos PR).

Covers the fault-injection wire, the exactly-once request layer, and the
graceful-degradation paths end to end:

  * ``FaultWire`` with no schedule armed is byte-identical to the bare
    wire (property, both ``Wire`` and ``FlowDemuxWire`` shapes);
  * the frame checksum rejects ANY single-byte corruption at any offset
    (property), and a corrupted ingress frame is discarded as a loss the
    client's timeout/resend recovers;
  * the server-side dedup/reply cache never double-applies a resent
    mutation under arbitrary seeded drop/dup/reorder/corrupt schedules —
    the KV record log is the ledger oracle (appends are NOT idempotent,
    so a double-apply would leave a second record);
  * a lost ack is answered from the reply cache on resend, not re-run;
  * a heartbeat blip shorter than the supervisor's grace windows does
    not promote; a real partition promotes, and the healed primary
    rejoins as a REPLICA of the shard that took over (no split-brain),
    for both cluster files and the KV store's record logs;
  * a failed DPU degrades transparently: offloaded GETs bounce to the
    host path and the bypass is visible in the stats;
  * shed-retry backoff is jittered per request id — deterministic across
    runs, de-synchronized across clients.
"""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import vector, wire
from repro.core.client import ClusterClient, DDSClient
from repro.core.dds_server import DDSStorageServer, ServerConfig
from repro.core.faultnet import FaultSchedule, FaultWire, wrap_director
from repro.core.traffic import FiveTuple, FlowDemuxWire, Packet, Wire
from repro.distributed.cluster import DDSCluster
from repro.apps.kv_store import REC_HDR, KVClient, ShardedKVStore


class _Clock:
    now = 0


_FLOW = FiveTuple("10.0.0.2", 7777, "10.0.0.1", 31337)


def _snap(pkt):
    return (pkt.seq, bytes(pkt.payload), pkt.flags, pkt.ack, pkt.csum)


# ---------------------------------------------------------------------------
# Passthrough: an unarmed FaultWire is invisible
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=32), max_size=12),
       st.sampled_from([None, "unarmed", "inactive"]))
def test_faultwire_passthrough_byte_identical(payloads, shape):
    """No armed+active schedule, no partitions => byte-identical traffic."""
    sched = {None: None,
             "unarmed": FaultSchedule(seed=3),                  # all rates 0
             "inactive": FaultSchedule(seed=3, drop=1.0,
                                       start_tick=10_000)}[shape]
    bare = Wire("bare")
    wrapped = FaultWire(Wire("inner"), _Clock(), sched)
    for i, p in enumerate(payloads):
        bare.push(Packet(_FLOW, i, p))
        wrapped.push(Packet(_FLOW, i, p))
    assert len(bare) == len(wrapped)
    while True:
        a, b = bare.pop(), wrapped.pop()
        assert (a is None) == (b is None)
        if a is None:
            break
        assert _snap(a) == _snap(b)
    assert all(v == 0 for v in wrapped.totals.values())


@settings(max_examples=10, deadline=None)
@given(st.lists(st.binary(min_size=1, max_size=16), max_size=10))
def test_faultwire_passthrough_demux_shape(payloads):
    bare = FlowDemuxWire("bare")
    wrapped = FaultWire(FlowDemuxWire("inner"), _Clock(), FaultSchedule())
    pkts_a = [Packet(_FLOW, i, p) for i, p in enumerate(payloads)]
    pkts_b = [Packet(_FLOW, i, p) for i, p in enumerate(payloads)]
    bare.push_many(_FLOW, pkts_a)
    wrapped.push_many(_FLOW, pkts_b)
    assert ([_snap(p) for p in bare.drain_flow(_FLOW)]
            == [_snap(p) for p in wrapped.drain_flow(_FLOW)])


def test_faultwire_taxonomy_counters_and_partition():
    clk = _Clock()
    fw = FaultWire(Wire("w"), clk, FaultSchedule(seed=7, drop=1.0))
    for i in range(5):
        fw.push(Packet(_FLOW, i, b"x"))
    assert fw.pop() is None and fw.totals["dropped"] == 5
    stats = fw.injection_stats()
    assert stats["totals"]["dropped"] == 5
    (fc,) = stats["flows"].values()
    assert fc["dropped"] == 5
    # timed partition: drops both directions until the clock passes
    fw2 = FaultWire(Wire("w2"), clk)
    fw2.partition("10.0.0.2", "10.0.0.1", until_tick=5)
    fw2.push(Packet(_FLOW, 0, b"a"))
    fw2.push(Packet(_FLOW.reversed(), 0, b"b"))
    assert fw2.pop() is None and fw2.totals["partition_dropped"] == 2
    clk.now = 5
    fw2.push(Packet(_FLOW, 1, b"c"))
    assert fw2.pop().payload == b"c"


def test_faultwire_delay_held_frames_keep_wire_busy():
    clk = _Clock()
    fw = FaultWire(Wire("w"), clk,
                   FaultSchedule(seed=1, delay=1.0, delay_ticks=(2, 2)))
    fw.push(Packet(_FLOW, 0, b"late"))
    assert fw.pop() is None
    assert bool(fw) and len(fw) == 1   # held frame keeps the server runnable
    clk.now = 2
    assert fw.pop().payload == b"late"
    assert fw.totals["delayed"] == 1 and not fw


# ---------------------------------------------------------------------------
# Frame checksums
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(st.binary(min_size=1, max_size=256), st.data())
def test_checksum_rejects_any_single_byte_corruption(payload, data):
    """Position-salted checksum64: every single-byte change is visible."""
    c = vector.checksum64(payload)
    i = data.draw(st.integers(0, len(payload) - 1))
    flip = data.draw(st.integers(1, 255))
    buf = bytearray(payload)
    buf[i] ^= flip
    assert vector.checksum64(bytes(buf)) != c


def test_corrupt_ingress_discarded_and_recovered_by_resend():
    srv = DDSStorageServer(ServerConfig(wire_checksums=True,
                                        device_capacity=1 << 24))
    fid = srv.frontend.create_file("c.dat")
    srv.frontend.write_sync(fid, 0, b"\x0c" * 256)
    srv.run_until_idle()
    cli = DDSClient(srv, timeout_ticks=4)
    t0 = srv.clock.now
    wrap_director(srv.director, srv.clock,
                  ingress=FaultSchedule(seed=11, corrupt=1.0,
                                        stop_tick=t0 + 6))
    status, body = cli.wait(cli.read(fid, 0, 64))
    assert status == wire.E_OK and body == b"\x0c" * 64
    assert srv.director.stats.corrupt_dropped >= 1
    assert cli.timeouts >= 1 and cli.resends >= 1
    assert srv.latency_stats()["wire"]["corrupt_dropped"] >= 1


def test_lost_ack_resend_replays_cached_ack():
    """The ack is dropped; the resent write must NOT re-run — the reply
    cache answers it."""
    srv = DDSStorageServer(ServerConfig(wire_checksums=True, dedup_cache=64,
                                        device_capacity=1 << 24))
    fid = srv.frontend.create_file("a.dat")
    srv.frontend.write_sync(fid, 0, bytes(256))
    srv.run_until_idle()
    cli = DDSClient(srv, timeout_ticks=4)
    _fin, fout = wrap_director(srv.director, srv.clock)
    fout.partition("10.0.0.1", "10.0.0.2", until_tick=srv.clock.now + 10)
    status, _ = cli.wait(cli.write(fid, 0, b"W" * 64))
    assert status == wire.E_OK
    assert srv.host_app.replayed_acks >= 1
    assert cli.wait(cli.read(fid, 0, 64)) == (wire.E_OK, b"W" * 64)
    assert srv.latency_stats()["exactly_once"]["replayed_acks"] >= 1


# ---------------------------------------------------------------------------
# Exactly-once under arbitrary schedules: the KV log is the ledger oracle
# ---------------------------------------------------------------------------


@settings(max_examples=5, deadline=None)
@given(st.integers(0, (1 << 32) - 1))
def test_kv_puts_apply_exactly_once_under_faults(seed):
    """PUT N distinct keys through a seeded drop/dup/reorder/corrupt storm.

    KV appends are not idempotent: if a resent PUT ever re-ran, its key
    would appear twice in the record log.  After the storm quiesces the
    log must hold each key EXACTLY once and every acked key must be
    present (zero lost acked writes, zero duplicate applies)."""
    store = ShardedKVStore(1, ServerConfig(wire_checksums=True,
                                           device_capacity=1 << 24))
    cl = store.cluster
    srv = cl.servers[0]
    fin, fout = wrap_director(
        srv.director, cl.clock,
        ingress=FaultSchedule(seed=seed, drop=0.12, dup=0.12,
                              reorder=0.08, corrupt=0.08),
        responses=FaultSchedule(seed=seed ^ 0x5BD1E995, drop=0.12,
                                dup=0.12, reorder=0.08))
    c = KVClient(store, timeout_ticks=8)
    keys = [b"chaos-%03d" % i for i in range(24)]
    rids = c.submit([("put", k, b"v:" + k) for k in keys])
    res = c.harvest(rids)
    assert all(s == wire.E_OK for s, _ in res.values())
    fin.schedule = None
    fout.schedule = None
    cl.run_until_idle()
    # ledger scan: each key exactly once in the shard's own log
    st0 = store._states[0]
    data = srv.frontend.read_sync(st0.log_fid, 0, st0.log_off) \
        if st0.log_off else b""
    counts: dict[bytes, int] = {}
    pos = 0
    while pos + REC_HDR.size <= len(data):
        klen, vlen = REC_HDR.unpack_from(data, pos)
        key = bytes(data[pos + REC_HDR.size:pos + REC_HDR.size + klen])
        counts[key] = counts.get(key, 0) + 1
        pos += REC_HDR.size + klen + vlen
    assert counts == {k: 1 for k in keys}
    # the storm actually did something on most seeds; don't flake on the
    # quiet ones — just require the bookkeeping to be consistent
    assert fin.injection_stats()["held"] == 0
    # typed round-trip after the storm
    got = c.harvest(c.submit([("get", keys[0])]))
    ((_, (status, body)),) = got.items()
    assert status == wire.E_OK


# ---------------------------------------------------------------------------
# Supervisor grace windows + partition/heal rejoin
# ---------------------------------------------------------------------------


def test_partition_blip_within_grace_does_not_promote():
    cl = DDSCluster(3, ServerConfig(replication=1, heartbeat_timeout_ticks=6))
    g = cl.create_file("blip")
    cl.write_sync(g, 0, b"\x01" * 128)
    victim = cl.locate(g).shard
    # 8 ticks of silence < miss_windows * (timeout + 1) = 14: a blip
    cl.partition(victim, until_tick=cl.clock.now + 8)
    for _ in range(30):
        cl.pump()
    assert not cl.failover_events and not cl.rejoin_events
    assert victim not in cl._dead and cl.epoch == 0
    c = ClusterClient(cl)
    assert c.harvest([c.read(g, 0, 128)]).popitem()[1] \
        == (wire.E_OK, b"\x01" * 128)


def test_partitioned_primary_heals_as_replica_no_split_brain():
    cl = DDSCluster(3, ServerConfig(replication=1, heartbeat_timeout_ticks=4))
    g = cl.create_file("p")
    cl.write_sync(g, 0, b"A" * 128)
    victim = cl.locate(g).shard
    cl.partition(victim, until_tick=cl.clock.now + 40)
    for _ in range(60):
        cl.pump()
        if cl.rejoin_events:
            break
    assert len(cl.failover_events) == 1 and cl.epoch == 1
    assert len(cl.rejoin_events) == 1
    ev = cl.rejoin_events[0]
    assert ev["healed"] == victim
    assert victim not in cl._dead
    # routes stay moved: the healed shard serves no client traffic...
    loc = cl.locate(g)
    assert loc.shard == ev["primary"] != victim
    # ...but it is a full replica again: re-silvered bytes + new mirrors
    assert victim in loc.replicas
    rlfid = loc.replicas[victim]
    assert cl.servers[victim].frontend.read_sync(rlfid, 0, 128) == b"A" * 128
    cl.write_sync(g, 0, b"B" * 128)
    cl.run_until_idle()
    assert cl.servers[victim].frontend.read_sync(rlfid, 0, 128) == b"B" * 128
    assert cl.latency_stats()["rejoins"][0]["healed"] == victim


def test_kv_rejoin_resilvers_record_log():
    store = ShardedKVStore(2, ServerConfig(replication=1,
                                           heartbeat_timeout_ticks=4,
                                           device_capacity=1 << 24))
    cl = store.cluster
    c = KVClient(store, retry_attempts=2)
    keys = [b"k%02d" % i for i in range(8)]
    res = c.harvest(c.submit([("put", k, b"v" + k) for k in keys]))
    assert all(s == wire.E_OK for s, _ in res.values())
    cl.run_until_idle()
    victim = store.shard_for_key(keys[0])
    cl.partition(victim, until_tick=cl.clock.now + 60)
    for _ in range(90):
        cl.pump()
        if cl.rejoin_events:
            break
    assert cl.rejoin_events and cl.rejoin_events[0]["healed"] == victim
    primary = cl.rejoin_events[0]["primary"]
    pst = store._states[primary]
    assert victim in pst.replica_fids
    rlfid = pst.replica_fids[victim]
    # healed copy mirrors the promoted primary's whole log...
    psrv, hsrv = cl.servers[primary], cl.servers[victim]
    assert hsrv.fs.file_size(rlfid) == psrv.fs.file_size(pst.log_fid)
    # ...and a post-heal PUT for an adopted key mirrors before the ack
    rid = c.put(keys[0], b"fresh-after-heal")
    assert c.harvest([rid])[rid][0] == wire.E_OK
    cl.run_until_idle()
    data = hsrv.frontend.read_sync(rlfid, 0, hsrv.fs.file_size(rlfid))
    assert b"fresh-after-heal" in data


# ---------------------------------------------------------------------------
# DPU failure: graceful degradation to the host path
# ---------------------------------------------------------------------------


def test_dpu_failure_bounces_offloaded_gets_to_host():
    srv = DDSStorageServer(ServerConfig(device_capacity=1 << 24))
    fid = srv.frontend.create_file("d.dat")
    srv.frontend.write_sync(fid, 0, bytes(range(256)) * 4)
    srv.run_until_idle()
    cli = DDSClient(srv)
    assert cli.wait(cli.read(fid, 0, 128))[0] == wire.E_OK
    completed_before = srv.offload.stats.completed
    assert completed_before >= 1        # the warm read was DPU-served
    srv.offload.fail()
    status, body = cli.wait(cli.read(fid, 0, 128))
    assert status == wire.E_OK and body == bytes(range(128))
    assert srv.offload.stats.completed == completed_before
    assert srv.director.stats.dpu_bypassed >= 1
    assert srv.latency_stats()["wire"]["dpu_bypassed"] >= 1
    # writes keep working on the host path too
    assert cli.wait(cli.write(fid, 0, b"Z" * 16))[0] == wire.E_OK
    assert cli.wait(cli.read(fid, 0, 16)) == (wire.E_OK, b"Z" * 16)


# ---------------------------------------------------------------------------
# Shed-retry jitter: deterministic, de-synchronized
# ---------------------------------------------------------------------------


def _retry_deadlines(client, rids, retry_after=4):
    hint = wire.encode_shed_hint(0, retry_after)
    got = {rid: (wire.E_SHED, hint) for rid in rids}
    for rid in rids:
        client._replay[rid] = b"stub"   # presence is all the guard checks
    pending: set = set()
    client._backoff.clear()
    client._maybe_retry_shed(got, pending)
    assert pending == set(rids)
    return {rid: due for due, rid in client._backoff}


def test_shed_retry_backoff_jittered_and_deterministic():
    cl = DDSCluster(1, ServerConfig(device_capacity=1 << 24))
    c1 = ClusterClient(cl, retry_attempts=3)
    c2 = ClusterClient(cl, retry_attempts=3)
    rids = list(range(1, 33))
    d1 = _retry_deadlines(c1, rids)
    d2 = _retry_deadlines(c2, rids)
    # deterministic: a pure function of (rid, attempt), identical across
    # clients and runs
    assert d1 == d2
    # jittered: the storm spreads over multiple ticks instead of
    # re-colliding in one
    assert len(set(d1.values())) > 1
