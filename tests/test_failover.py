"""Replication, crash-consistency and failover (the robustness PR).

Covers the replicated-shard overhaul end to end:

  * ring successors: K distinct live replicas per primary, deterministic;
  * primary-backup over the host wire: a write ack releases only after
    every replica holds the bytes (ack-hold), so an acked write survives
    the primary's crash;
  * crash-consistent apply: the redo journal turns a coalesced run into
    journal-writev -> single-slot commit flip -> in-place writev, so a
    power-fail at ANY device op leaves each file fully pre- or fully
    post-run — never torn (torn-writev injection + recovery mount);
  * the supervisor: tick-clock heartbeats, deterministic detection,
    replica promotion, ring repair and epoch bump;
  * client transparency: the epoch fence refuses stale-epoch packets with
    retryable redirects; ClusterClient and KVClient replay against the
    repaired ring with the same request ids (the redirect replay itself is
    pinned on both clients in ``test_request_book.py``);
  * a property-style crash sweep: kill each shard at a range of ticks
    across a deterministic run — zero lost acknowledged writes;
  * KV promotion: the adopted log copy rebuilds the index, stale DPU
    cache-table entries are replaced, adopted invalidation views work;
  * shed retry with bounded exponential backoff honoring ``retry_after``
    (retry disabled is the shed case of ``test_request_book.py``).
"""

import pytest

from repro.core import wire
from repro.core.client import ClusterClient
from repro.core.dds_server import ServerConfig
from repro.core.file_service import FileServiceRunner, SegmentFS
from repro.core.host_lib import DDSFrontEnd
from repro.core.qos import QoSProfile
from repro.core.ring import DMAEngine
from repro.distributed.cluster import DDSCluster
from repro.distributed.fault_tolerance import HeartbeatMonitor
from repro.apps.kv_store import (KVClient, KVLocation, ShardedKVStore,
                                 decode_record)
from repro.storage.blockdev import BlockDevice

RCFG = dict(replication=1, heartbeat_timeout_ticks=6)


def make_cluster(num_shards=3, **over):
    kw = dict(RCFG)
    kw.update(over)
    return DDSCluster(num_shards, ServerConfig(**kw))


# ---------------------------------------------------------------------------
# Ring successors + replica placement
# ---------------------------------------------------------------------------


def test_ring_successors_distinct_and_deterministic():
    cl = make_cluster(4, replication=2)
    for i in range(4):
        succ = cl.ring.successors(i, 2)
        assert len(succ) == 2
        assert i not in succ
        assert len(set(succ)) == 2
        assert succ == cl.ring.successors(i, 2)  # stable
    # K is clamped to num_shards - 1
    assert len(cl.ring.successors(0, 99)) == 3


def test_replication_clamped_and_disabled_paths():
    # A single shard cannot replicate; an unreplicated cluster arms nothing.
    solo = DDSCluster(1, ServerConfig(replication=2))
    assert solo.replication == 0 and solo.supervisor is None
    plain = DDSCluster(2, ServerConfig())
    assert plain.supervisor is None
    assert all(s.replicator is None for s in plain.servers)


def test_create_file_places_replicas_on_successors():
    cl = make_cluster(3)
    g = cl.create_file("data")
    loc = cl.locate(g)
    assert set(loc.replicas) == {cl.ring.successors(loc.shard, 1)[0]}
    # control-plane bulk load mirrors onto the replica directly
    cl.write_sync(g, 0, b"seed" * 64)
    (t, rlfid), = loc.replicas.items()
    assert cl.servers[t].frontend.read_sync(rlfid, 0, 256) == b"seed" * 64


# ---------------------------------------------------------------------------
# Primary-backup forwarding + ack-hold
# ---------------------------------------------------------------------------


def test_wire_write_forwarded_before_ack_releases():
    cl = make_cluster(3)
    g = cl.create_file("x")
    c = ClusterClient(cl)
    rid = c.write(g, 0, b"A" * 512)
    assert c.harvest([rid])[rid] == (wire.E_OK, b"")
    loc = cl.locate(g)
    (t, rlfid), = loc.replicas.items()
    cl.run_until_idle()
    # the ack implies the replica holds the bytes
    assert cl.servers[t].frontend.read_sync(rlfid, 0, 512) == b"A" * 512
    repl = cl.servers[loc.shard].replicator
    assert repl.forwarded == 1 and repl.forwarded_bytes == 512
    assert repl.lag.n == 1
    stats = cl.latency_stats()
    assert stats["replication"]["forwarded"] == 1


def test_reads_are_not_forwarded():
    cl = make_cluster(3)
    g = cl.create_file("x")
    cl.write_sync(g, 0, b"r" * 128)
    c = ClusterClient(cl)
    rid = c.read(g, 0, 128)
    assert c.harvest([rid])[rid] == (wire.E_OK, b"r" * 128)
    assert cl.locate(g) is not None
    repl = cl.servers[cl.locate(g).shard].replicator
    assert repl.forwarded == 0  # the bulk-load mirror bypassed the wire


# ---------------------------------------------------------------------------
# Crash-consistent apply: redo journal + torn-writev injection
# ---------------------------------------------------------------------------


def _journal_stack(segment_size=1 << 16):
    dev = BlockDevice(1 << 22, block_size=512)
    fs = SegmentFS(dev, segment_size, journal_segments=2)
    svc = FileServiceRunner(fs, DMAEngine())
    fe = DDSFrontEnd(svc, ring_capacity=1 << 14)
    return dev, fs, svc, fe


def _drive_until_crash(svc, dev, budget=500):
    for _ in range(budget):
        if dev.crashed:
            return
        svc.step()
        if not dev.crashed:
            dev.poll(64)
    assert dev.crashed, "injected tear never fired"


@pytest.mark.parametrize("tear_op,expect_new", [
    (1, False),   # journal record itself torn: commit never lands -> OLD
    (2, True),    # in-place writev torn after commit: replay -> NEW
])
def test_torn_writev_leaves_file_pre_or_post_never_torn(tear_op, expect_new):
    dev, fs, svc, fe = _journal_stack()
    fid = fe.create_file("t")
    old_a, old_b = b"\xAA" * 2048, b"\xAB" * 2048
    fe.write_sync(fid, 0, old_a + old_b)
    new_a, new_b = b"\xBA" * 2048, b"\xBB" * 2048
    # Two adjacent writes coalesce into ONE run = one journal record = one
    # in-place writev with two gathered chunks (the satellite-3 hazard:
    # a coalesced run must flip atomically, not per source buffer).
    dev.inject_torn_writev(nth=tear_op, chunks=1)
    fe.submit_many([("w", fid, 0, new_a), ("w", fid, 2048, new_b)])
    _drive_until_crash(svc, dev)

    # Recovery mount on the survived media.
    fs2 = SegmentFS.mount(dev, 1 << 16, journal_segments=2)
    rec = fs2.recover_journal()
    phys = fs2.files[fid].segments[0] * (1 << 16)
    got = dev.raw_read(phys, 4096)
    want = (new_a + new_b) if expect_new else (old_a + old_b)
    assert got == want
    assert got in (old_a + old_b, new_a + new_b)   # never torn
    # The initial write_sync journaled one committed record; the torn run
    # adds a second only when its commit flip landed before the tear.
    assert rec["records"] == (2 if expect_new else 1)
    assert fs2.journal_replayed_records == rec["records"]


def test_torn_inplace_write_is_visibly_torn_without_recovery():
    """Sanity of the fault model itself: the tear DOES corrupt media (half
    the coalesced run landed) — recovery is what un-tears it."""
    dev, fs, svc, fe = _journal_stack()
    fid = fe.create_file("t")
    fe.write_sync(fid, 0, b"\x00" * 4096)
    dev.inject_torn_writev(nth=2, chunks=1)
    fe.submit_many([("w", fid, 0, b"\x11" * 2048),
                    ("w", fid, 2048, b"\x22" * 2048)])
    _drive_until_crash(svc, dev)
    phys = fs.files[fid].segments[0] * (1 << 16)
    raw = dev.raw_read(phys, 4096)
    assert raw[:2048] == b"\x11" * 2048      # first chunk landed
    assert raw[2048:] == b"\x00" * 2048      # second did not: torn
    fs2 = SegmentFS.mount(dev, 1 << 16, journal_segments=2)
    assert fs2.recover_journal()["records"] == 2   # seed write + torn run
    assert dev.raw_read(phys, 4096) == b"\x11" * 2048 + b"\x22" * 2048


# ---------------------------------------------------------------------------
# Detection + promotion
# ---------------------------------------------------------------------------


def test_heartbeat_on_ticks_is_deterministic():
    class Clock:
        now = 0
    clock = Clock()
    mon = HeartbeatMonitor.on_ticks(["a", "b"], clock, timeout_ticks=5)
    mon.beat("a", 0)
    mon.beat("b", 0)
    clock.now = 5
    assert mon.dead_hosts() == []       # exactly at timeout: still alive
    clock.now = 6
    assert mon.dead_hosts() == ["a", "b"]


def test_supervisor_detects_crash_and_promotes_deterministically():
    cl = make_cluster(3)
    g = cl.create_file("x")
    cl.write_sync(g, 0, b"D" * 128)
    victim = cl.locate(g).shard
    cl.crash(victim)
    crash_tick = cl.clock.now
    for _ in range(20):
        cl.pump()
    assert len(cl.failover_events) == 1
    ev = cl.failover_events[0]
    assert ev["dead"] == victim and ev["epoch"] == 1
    # detection latency == miss_windows * (heartbeat_timeout_ticks + 1)
    # pumps, exactly — the first silent window only notes a miss, the
    # second consecutive one promotes.
    assert ev["tick"] == crash_tick + 2 * (RCFG["heartbeat_timeout_ticks"] + 1)
    loc = cl.locate(g)
    assert loc.shard == ev["promoted"] and loc.shard != victim
    assert cl.servers[loc.shard].frontend.read_sync(
        loc.local_fid, 0, 128) == b"D" * 128
    assert cl.route_of(victim) == ev["promoted"]
    stats = cl.latency_stats()
    assert stats["failover"]["epoch"] == 1
    assert stats["failover"]["events"] == cl.failover_events


def test_crash_at_schedules_deterministic_kill():
    cl = make_cluster(3)
    cl.crash_at(1, 10)
    while cl.clock.now < 9:
        cl.pump()
    assert 1 not in cl._dead
    cl.pump()
    assert 1 in cl._dead


# ---------------------------------------------------------------------------
# Client transparency: epoch fence + redirect replay (all three clients)
# ---------------------------------------------------------------------------


def test_cluster_client_replays_through_failover():
    cl = make_cluster(3)
    files = [cl.create_file(f"f{i}") for i in range(12)]
    c = ClusterClient(cl)
    rids = c.submit([("w", g, 0, bytes([i + 1]) * 128)
                     for i, g in enumerate(files)])
    res = c.harvest(rids)
    assert all(v == (wire.E_OK, b"") for v in res.values())
    victim = cl.locate(files[0]).shard
    reads = c.submit([("r", g, 0, 128) for g in files])
    cl.crash(victim)                 # mid-flight
    res = c.harvest(reads)
    for i, rid in enumerate(reads):
        assert res[rid] == (wire.E_OK, bytes([i + 1]) * 128)
    assert cl.epoch == 1 and c._epoch_seen == 1
    assert all(conn.epoch == 1 for conn in c.conns)


def test_kv_client_failover_with_cache_invalidation_and_adoption():
    store = ShardedKVStore(3, ServerConfig(**RCFG))
    c = KVClient(store)
    keys = [f"k{i:03d}".encode() for i in range(30)]
    res = c.harvest(c.submit([("put", k, b"v-" + k) for k in keys]))
    assert all(s == wire.E_OK for s, _ in res.values())
    res = c.harvest(c.submit([("get", k) for k in keys]))  # warm DPU cache
    assert all(s == wire.E_OK for s, _ in res.values())

    victims = {store.shard_for_key(k) for k in keys}
    victim = sorted(victims)[0]
    vkeys = [k for k in keys if store.shard_for_key(k) == victim]
    assert vkeys
    promoted = store.cluster.ring.successors(victim, 1)[0]
    # Plant a STALE DPU cache entry for an adopted key on the promotion
    # target: promotion must replace it, or the DPU would serve garbage.
    table = store.cluster.servers[promoted].cache_table
    table.insert(vkeys[0], KVLocation(999, 0, 8))

    reads = c.submit([("get", k) for k in keys])
    store.cluster.crash(victim)
    res = c.harvest(reads)
    for k, rid in zip(keys, reads):
        status, body = res[rid]
        assert status == wire.E_OK
        assert decode_record(body)[1] == b"v-" + k
    assert store.cluster.failover_events[0]["promoted"] == promoted
    # the stale entry was replaced with the adopted-log location
    loc = table.lookup(vkeys[0])
    assert loc is not None and loc.file_id != 999
    st = store._states[promoted]
    assert st.adopted_records == len(vkeys)
    assert loc.file_id in st.adopted
    # key->shard cache re-routes to the promoted shard post-epoch-bump
    assert c._shard(vkeys[0]) == promoted

    # overwrite an adopted key (appends to the promoted shard's OWN log),
    # then delete it: both exercise the cross-fid invalidation view.
    r = c.put(vkeys[0], b"NEW")
    assert c.harvest([r])[r][0] == wire.E_OK
    r = c.get(vkeys[0])
    assert decode_record(c.harvest([r])[r][1])[1] == b"NEW"
    r = c.delete(vkeys[0])
    assert c.harvest([r])[r][0] == wire.E_OK
    r = c.get(vkeys[0])
    assert c.harvest([r])[r][0] == wire.E_NOENT


# ---------------------------------------------------------------------------
# Property-style crash sweep: zero lost acknowledged writes
# ---------------------------------------------------------------------------


def _crash_run(victim: int, crash_delay: int):
    """One deterministic run: write, kill ``victim`` ``crash_delay`` ticks
    into the read+write wave, verify every acked write is readable."""
    cl = make_cluster(3)
    files = [cl.create_file(f"f{i}") for i in range(9)]
    c = ClusterClient(cl)
    res = c.harvest(c.submit([("w", g, 0, bytes([i + 1]) * 64)
                              for i, g in enumerate(files)]))
    assert all(v[0] == wire.E_OK for v in res.values())
    crash_tick = cl.clock.now + crash_delay
    cl.crash_at(victim, crash_tick)
    wave = c.submit([("w", g, 64, bytes([i + 33]) * 64)
                     for i, g in enumerate(files)]
                    + [("r", g, 0, 64) for g in files])
    res = c.harvest(wave)
    # K=1, one crash: the repaired ring serves everything — no lost acks,
    # no spurious errors, reads see phase-1 bytes.
    for i, rid in enumerate(wave[:9]):
        assert res[rid] == (wire.E_OK, b""), (victim, crash_delay, i)
    for i, rid in enumerate(wave[9:]):
        assert res[rid] == (wire.E_OK, bytes([i + 1]) * 64), \
            (victim, crash_delay, i)
    # Let the kill + detection complete even when the wave outran the
    # scheduled crash tick (a victim without in-flight traffic blocks no
    # harvest, so the wave can finish pre-crash).
    deadline = crash_tick + 2 * (RCFG["heartbeat_timeout_ticks"] + 1) + 5
    while cl.clock.now < deadline:
        cl.pump()
    # every phase-2 ack readable post-failover
    res = c.harvest(c.submit([("r", g, 64, 64) for g in files]))
    for i, rid in enumerate(sorted(res)):
        assert res[rid] == (wire.E_OK, bytes([i + 33]) * 64)
    return cl.failover_events


@pytest.mark.parametrize("victim", [0, 1, 2])
@pytest.mark.parametrize("crash_delay", [0, 3, 8, 17])
def test_crash_sweep_zero_lost_acked_writes(victim, crash_delay):
    events = _crash_run(victim, crash_delay)
    assert len(events) == 1 and events[0]["dead"] == victim


def test_crash_run_is_deterministic():
    assert _crash_run(1, 3) == _crash_run(1, 3)


# ---------------------------------------------------------------------------
# Satellite 2: shed retry with bounded exponential backoff
# ---------------------------------------------------------------------------


def test_shed_retry_backoff_recovers_within_cap():
    cl = DDSCluster(1, ServerConfig(
        device_capacity=1 << 24,
        qos=QoSProfile(tenant_rates={7: 1.0}, tenant_bursts={7: 2.0})))
    g = cl.create_file("s")
    cl.write_sync(g, 0, b"\x01" * 4096)
    c = ClusterClient(cl, tenant=7, retry_attempts=5)
    rids = c.submit([("r", g, 0, 64)] * 6)    # burst 2.0: 4 shed initially
    res = c.harvest(rids)
    # ... but the bucket refills at 1/tick and the bounded-backoff retry
    # resubmits with the server's retry_after honored: all succeed.
    assert all(v == (wire.E_OK, b"\x01" * 64) for v in res.values())
    assert cl.servers[0].admission.summary()["shed"] >= 4   # retries happened


def test_shed_retry_cap_surfaces_terminal_error():
    cl = DDSCluster(1, ServerConfig(
        device_capacity=1 << 24,
        qos=QoSProfile(tenant_rates={7: 0.05}, tenant_bursts={7: 1.0})))
    g = cl.create_file("s")
    cl.write_sync(g, 0, b"\x01" * 4096)
    c = ClusterClient(cl, tenant=7, retry_attempts=1)
    rids = c.submit([("r", g, 0, 64)] * 4)
    res = c.harvest(rids)
    statuses = sorted(s for s, _ in res.values())
    assert wire.E_SHED in statuses            # cap exhausted: terminal shed
    assert wire.E_OK in statuses              # the granted ones served
    for s, body in res.values():
        if s == wire.E_SHED:
            tenant, ra = wire.decode_shed_hint(body)
            assert tenant == 7 and ra >= 1
    assert c.outstanding() == 0               # nothing leaked


# ---------------------------------------------------------------------------
# Batch-checksum integrity gate: corrupted writev bytes are DETECTED —
# neither served to a reader nor replayed out of the journal.
# ---------------------------------------------------------------------------


def test_corrupted_writev_media_fails_reads_with_eio():
    from repro.storage.blockdev import STATUS_EIO, STATUS_OK
    dev, fs, svc, fe = _journal_stack()
    dev.enable_checksums()
    fid = fe.create_file("t")
    fe.write_sync(fid, 0, b"\xC3" * 4096)
    phys = fs.files[fid].segments[0] * (1 << 16)
    assert dev.verify_blocks() == 0        # journaled run committed its CRCs

    dev._mem[phys + 123] ^= 0x01           # single-bit rot inside the run
    assert dev.verify_blocks() == 1        # exactly one block flagged

    sts, dst = [], memoryview(bytearray(4096))
    dev.submit_read(phys, 4096, dst, on_complete=sts.append)
    dev.poll()
    assert sts == [STATUS_EIO]
    assert bytes(dst) == bytes(4096)       # corrupt bytes never delivered
    assert dev.stats.crc_read_failures == 1

    # Rewriting the span re-commits: the same read succeeds again.
    fe.write_sync(fid, 0, b"\xC4" * 4096)
    sts2 = []
    dev.submit_read(phys, 4096, dst, on_complete=sts2.append)
    dev.poll()
    assert sts2 == [STATUS_OK] and bytes(dst) == b"\xC4" * 4096


def test_corrupted_journal_record_is_refused_at_recovery():
    from repro.core.file_service import _JREC
    dev, fs, svc, fe = _journal_stack()
    fid = fe.create_file("t")
    old = b"\xAA" * 2048
    fe.write_sync(fid, 0, old)
    # Commit flip lands, in-place writev applies ZERO chunks: media stays
    # fully old, and recovery alone decides whether the record applies.
    dev.inject_torn_writev(nth=2, chunks=0)
    fe.submit_many([("w", fid, 0, b"\xBB" * 2048)])
    _drive_until_crash(svc, dev)

    # Rot one payload byte of the committed-but-unapplied record on the
    # survived media (its region is the only one still journal-pending).
    pos, _end = next(iter(fs._journal_pending.values()))
    dev._mem[fs._journal_start + pos + _JREC.size + 4 + 10] ^= 0x80

    fs2 = SegmentFS.mount(dev, 1 << 16, journal_segments=2)
    rec = fs2.recover_journal()
    assert rec["records"] == 1             # the seed write_sync only
    assert fs2.journal_crc_failures == 1   # the rotted record was refused
    phys = fs2.files[fid].segments[0] * (1 << 16)
    assert dev.raw_read(phys, 2048) == old  # corrupt bytes never applied
