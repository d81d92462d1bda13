"""Sharded cluster layer: routing, pipelining, and the §9.2 KV offload path."""

import pytest

from repro.apps.kv_store import KVClient, KVLocation, ShardedKVStore
from repro.core import wire
from repro.core.client import ClusterClient
from repro.core.dds_server import ServerConfig
from repro.distributed.cluster import DDSCluster, HashRing


# -- consistent-hash routing -----------------------------------------------------------

def test_ring_routing_is_deterministic_across_instances():
    a, b = HashRing(4), HashRing(4)
    keys = [f"key-{i}".encode() for i in range(500)] + list(range(500))
    assert [a.shard_for(k) for k in keys] == [b.shard_for(k) for k in keys]


def test_ring_spreads_load_and_rescales_minimally():
    ring4, ring5 = HashRing(4), HashRing(5)
    keys = list(range(2000))
    dist = ring4.distribution(keys)
    assert all(dist[s] > 0 for s in range(4))          # no empty shard
    moved = sum(ring4.shard_for(k) != ring5.shard_for(k) for k in keys)
    # consistent hashing: adding shard 5 of 5 should move ~1/5, far from all
    assert moved / len(keys) < 0.45


def test_cluster_file_placement_follows_ring():
    cl = DDSCluster(num_shards=4)
    fids = [cl.create_file(f"f{i}") for i in range(16)]
    for f in fids:
        assert cl.locate(f).shard == cl.ring.shard_for(f)
    cl2 = DDSCluster(num_shards=4)
    fids2 = [cl2.create_file(f"g{i}") for i in range(16)]
    # placement is a pure function of the (global) file id sequence
    assert [cl.locate(f).shard for f in fids] == \
           [cl2.locate(f).shard for f in fids2]


# -- pipelined batched client ----------------------------------------------------------

@pytest.fixture()
def loaded_cluster():
    cl = DDSCluster(num_shards=2)
    fids = [cl.create_file(f"d{i}") for i in range(6)]
    for i, f in enumerate(fids):
        cl.write_sync(f, 0, bytes([i + 1]) * 8192)
    return cl, fids


def test_client_batches_per_shard_messages(loaded_cluster):
    cl, fids = loaded_cluster
    cc = ClusterClient(cl)
    rids = [cc.read(f, 0, 64) for f in fids for _ in range(4)]
    cc.flush()
    # one network message per shard holding every request for that shard
    shards_used = {cl.locate(f).shard for f in fids}
    assert cc.stats.batches_sent == len(shards_used)
    assert cc.stats.messages_sent == len(rids)
    res = cc.harvest(rids)
    assert all(s == wire.E_OK for s, _ in res.values())


def test_pipelined_responses_preserve_per_shard_issue_order(loaded_cluster):
    cl, fids = loaded_cluster
    cc = ClusterClient(cl)
    # several pipelined batches in flight before any collection
    rids = []
    for round_ in range(5):
        rids += [cc.read(f, 256 * round_, 128) for f in fids]
        cc.flush()                      # new batch; do NOT wait
    res = cc.harvest(rids)
    assert all(s == wire.E_OK for s, _ in res.values())
    for conn in cc.conns:
        issued = [r for r in rids if r in set(conn.arrival_order)]
        assert conn.arrival_order == sorted(conn.arrival_order), \
            "offloaded responses must stream back in issue order per shard"
        assert issued == conn.arrival_order


def test_reads_offload_and_writes_take_host_path(loaded_cluster):
    cl, fids = loaded_cluster
    cc = ClusterClient(cl)
    st, _ = cc.wait(cc.write(fids[0], 0, b"Z" * 512))
    assert st == wire.E_OK
    st, body = cc.wait(cc.read(fids[0], 0, 512))
    assert st == wire.E_OK and body == b"Z" * 512
    stats = cl.stats()
    assert stats.offloaded_completed >= 1       # the read ran on a DPU
    assert stats.host_cpu_busy_s > 0            # the write burned host CPU


def test_cluster_data_is_actually_sharded(loaded_cluster):
    cl, fids = loaded_cluster
    per_shard_files = {}
    for f in fids:
        per_shard_files.setdefault(cl.locate(f).shard, []).append(f)
    assert len(per_shard_files) == 2            # both shards own files
    for shard, owned in per_shard_files.items():
        srv = cl.servers[shard]
        # every owned file is present locally, none of the others are
        local = {cl.locate(f).local_fid for f in owned}
        assert local <= set(srv.fs.files)


def test_two_clients_share_a_cluster_without_cross_talk(loaded_cluster):
    cl, fids = loaded_cluster
    a, b = ClusterClient(cl), ClusterClient(cl)
    ra = a.read(fids[0], 0, 64)      # rid 1 in BOTH clients' namespaces
    rb = b.read(fids[1], 0, 64)
    sa, body_a = a.wait(ra)
    sb, body_b = b.wait(rb)
    assert (sa, body_a) == (wire.E_OK, bytes([1]) * 64)
    assert (sb, body_b) == (wire.E_OK, bytes([2]) * 64)


# -- the §9.2 KV workload --------------------------------------------------------------

@pytest.fixture()
def kv():
    store = ShardedKVStore(num_shards=2)
    return store, KVClient(store)


def test_kv_get_after_put_is_dpu_served(kv):
    store, c = kv
    loc = c.wait_put(c.put(b"alpha", b"value-1"))
    assert isinstance(loc, KVLocation) and loc.size > 0
    assert c.wait_value(c.get(b"alpha")) == b"value-1"
    assert store.dpu_served_gets() == 1         # offload hit, zero host CPU
    assert store.host_served_gets() == 0


def test_kv_put_ack_location_points_at_the_record(kv):
    store, c = kv
    loc = c.wait_put(c.put(b"where", b"am-i"))
    shard = store.shard_for_key(b"where")
    raw = store.cluster.servers[shard].frontend.read_sync(
        loc.file_id, loc.offset, loc.size)
    from repro.apps.kv_store import decode_record
    assert decode_record(raw) == (b"where", b"am-i")


def test_kv_overwrite_updates_mapping_not_stale(kv):
    store, c = kv
    c.wait_put(c.put(b"k", b"v1"))
    assert c.wait_value(c.get(b"k")) == b"v1"
    c.wait_put(c.put(b"k", b"v2"))              # append; Cache upserts
    assert c.wait_value(c.get(b"k")) == b"v2"
    # still served from the DPU at the NEW location
    assert store.dpu_served_gets() == 2


def test_kv_delete_invalidates_dpu_mapping(kv):
    store, c = kv
    c.wait_put(c.put(b"doomed", b"payload"))
    assert c.wait_value(c.get(b"doomed")) == b"payload"
    shard = store.shard_for_key(b"doomed")
    table = store.cluster.servers[shard].cache_table
    assert table.lookup(b"doomed") is not None
    st, _ = c.net.wait(c.delete(b"doomed"))
    assert st == wire.E_OK
    assert table.lookup(b"doomed") is None      # Invalidate fired on read
    assert c.wait_value(c.get(b"doomed")) is None


def test_kv_invalidate_correct_under_interleaved_writes(kv):
    store, c = kv
    # interleave: PUT a, PUT b, overwrite a, DEL b — all pipelined
    rids = [c.put(b"a", b"a1"), c.put(b"b", b"b1")]
    c.flush()
    rids += [c.put(b"a", b"a2")]
    c.flush()
    for r in rids:
        c.wait_put(r)
    st, _ = c.net.wait(c.delete(b"b"))
    assert st == wire.E_OK
    # deleting b (old log region) must not clobber a's fresh mapping
    assert c.wait_value(c.get(b"a")) == b"a2"
    assert c.wait_value(c.get(b"b")) is None
    shard_a = store.shard_for_key(b"a")
    assert store.cluster.servers[shard_a].cache_table.lookup(b"a") is not None


def test_kv_scales_across_shards_with_nonzero_offload():
    store = ShardedKVStore(num_shards=4)
    c = KVClient(store)
    keys = [f"user:{i}".encode() for i in range(64)]
    for k in keys:
        c.put(k, b"profile-" + k)
    c.flush()
    c.run_until_idle()
    grids = {k: c.get(k) for k in keys}
    for k in keys:
        assert c.wait_value(grids[k]) == b"profile-" + k
    per_shard = store.shard_stats()
    assert sum(1 for s in per_shard if s["puts"] > 0) >= 3   # data spread out
    assert store.dpu_served_gets() == len(keys)              # all offloaded


# -- PR 4 satellites: ring build, batched predicate lookups, KV burst issue -----------

def test_hashring_sort_once_build_matches_incremental_insert():
    """The O(n log n) build must place vnodes exactly like the old
    insertion-sorted build (placement stability across versions)."""
    import bisect
    from repro.distributed.cluster import stable_hash
    for shards, vnodes in ((3, 16), (16, 64)):
        points, owners = [], []
        for shard in range(shards):           # the pre-PR O(n^2) build
            for v in range(vnodes):
                p = stable_hash(f"shard-{shard}-vnode-{v}")
                i = bisect.bisect_left(points, p)
                points.insert(i, p)
                owners.insert(i, shard)
        ring = HashRing(shards, vnodes)
        assert ring._points == points
        assert ring._owners == owners


def test_kv_get_burst_uses_one_batched_cache_lookup(kv):
    store, c = kv
    keys = [b"burst-%d" % i for i in range(12)]
    for k in keys:
        c.put(k, b"v:" + k)
    c.flush()
    c.run_until_idle()
    shard_batches = {i: s["cache"]["batched_lookups"]
                     for i, s in enumerate(store.shard_stats())}
    rids = c.submit([("get", k) for k in keys])
    c.flush()
    res = c.harvest(rids)
    assert all(s == wire.E_OK for s, _ in res.values())
    after = store.shard_stats()
    for i, s in enumerate(after):
        # every shard that saw GETs probed its table in burst(s), and the
        # counter is surfaced through the app-level stats
        got = s["cache"]["batched_lookups"] - shard_batches[i]
        if s["dpu_gets"]:
            assert got >= 1
    assert store.dpu_served_gets() == len(keys)


def test_kv_burst_apis_roundtrip(kv):
    store, c = kv
    items = [(b"bk-%d" % i, b"bv-%d" % i) for i in range(10)]
    put_rids = c.submit([("put", k, v) for k, v in items])
    c.flush()
    for rid in put_rids:
        c.wait_put(rid)
    get_rids = c.submit([("get", k) for k, _ in items])
    c.flush()
    res = c.harvest(get_rids)
    from repro.apps.kv_store import decode_record
    for (k, v), rid in zip(items, get_rids):
        st_, body = res[rid]
        assert st_ == wire.E_OK and decode_record(body) == (k, v)
    del_rids = c.submit([("delete", k) for k, _ in items[:5]])
    c.flush()
    res = c.harvest(del_rids)
    assert all(s == wire.E_OK for s, _ in res.values())
    assert c.wait_value(c.get(items[0][0])) is None
    assert c.wait_value(c.get(items[9][0])) == b"bv-9"
