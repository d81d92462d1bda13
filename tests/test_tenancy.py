"""Multi-tenant QoS: weighted-fair demux, token-bucket admission, tenancy
observability, and the unified ``submit``/``harvest`` client surface.

Covers the PR-6 tenancy machinery end to end:

  * ``TenantFairQueue`` / ``FlowDemuxWire.pop_many`` PROPERTIES: no tenant
    starves under ANY weight vector (every backlogged tenant is served
    within a bounded number of take rounds), and the queues are
    work-conserving (an idle tenant's share flows to the backlogged ones —
    total service never drops below min(budget, backlog));
  * single-tenant fast path: with one tenant the fair queues are
    byte-identical to the plain FIFOs they replaced (determinism guard for
    every pre-tenancy workload);
  * token-bucket admission conservation: ``granted + shed == offered``
    holds exactly under any arrival pattern, and shed responses carry the
    shedding tenant's bucket state (retry-after hint) as the E_SHED body;
  * sheds are charged to THEIR tenant only: another tenant's outstanding
    counters and latency stats never move;
  * tick determinism: two identical two-tenant interference runs produce
    byte-identical per-tenant latency histograms;
  * the ``submit``/``harvest`` surface: a mixed burst answers exactly as
    the same ops issued one at a time through the one-op forms.
"""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import wire
from repro.core.client import ClusterClient, DDSClient
from repro.core.dds_server import DDSStorageServer, ServerConfig
from repro.core.lifecycle import TickClock
from repro.core.qos import QoSProfile, TenantAdmission, TokenBucket
from repro.core.traffic import FiveTuple, FlowDemuxWire, Packet, TenantFairQueue
from repro.apps.kv_store import KVClient, ShardedKVStore, decode_record
from repro.distributed.cluster import DDSCluster


def _flow(tenant: int, port: int = 1000) -> FiveTuple:
    return FiveTuple("10.0.0.2", port + tenant, "10.0.0.1", 7777,
                     tenant=tenant)


# ---------------------------------------------------------------------------
# QoSProfile: validation, presets, reject-unknown-fields
# ---------------------------------------------------------------------------


def test_qos_profile_presets_and_from_dict():
    assert QoSProfile.preset("latency").coalesce_ticks == 0
    assert QoSProfile.preset("throughput").coalesce_ticks > 2
    iso = QoSProfile.preset("isolation")
    assert iso.admission_enabled()
    # from_dict layers overrides on a preset base and rejects typos.
    p = QoSProfile.from_dict({"profile": "latency", "host_drain_slice": 64})
    assert p.coalesce_ticks == 0 and p.host_drain_slice == 64
    with pytest.raises(ValueError, match="unknown QoSProfile field"):
        QoSProfile.from_dict({"coalesce_tick": 3})   # typo'd key is an ERROR
    with pytest.raises(ValueError):
        QoSProfile.preset("nope")
    with pytest.raises(ValueError):
        QoSProfile(prio_interleave=0)
    with pytest.raises(ValueError):
        QoSProfile(tenant_weights={1: 0})            # weights are >= 1
    with pytest.raises(ValueError):
        ServerConfig(qos="no-such-preset")
    # ServerConfig accepts a preset name or a config dict.
    assert ServerConfig(qos="latency").qos.deliver_ticks == 0
    assert ServerConfig(qos={"default_rate": 2.0}).qos.admission_enabled()


def test_qos_profile_weight_rate_accessors():
    p = QoSProfile(tenant_weights={2: 5}, default_rate=4.0,
                   tenant_rates={3: 0.5})
    assert p.weight_of(2) == 5 and p.weight_of(9) == 1
    assert p.rate_of(3) == 0.5 and p.rate_of(9) == 4.0
    assert p.burst_of(9) == 32.0          # default burst = 8x rate
    assert QoSProfile().admission_enabled() is False


# ---------------------------------------------------------------------------
# WFQ properties: no starvation, work conservation
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(weights=st.lists(st.integers(min_value=1, max_value=8),
                        min_size=2, max_size=5),
       backlog=st.integers(min_value=1, max_value=40),
       budget=st.integers(min_value=1, max_value=16))
def test_tenant_fair_queue_no_starvation_any_weights(weights, backlog,
                                                     budget):
    """Under ANY weight vector, every backlogged tenant gets service within
    a bounded number of take rounds — a flooding tenant cannot starve the
    others — and take order is per-tenant FIFO."""
    q = TenantFairQueue()
    prof = QoSProfile(tenant_weights={t + 1: w
                                      for t, w in enumerate(weights)})
    q.weight_of = prof.weight_of
    flows = [_flow(t + 1) for t in range(len(weights))]
    for i in range(backlog):
        for f in flows:
            q.append((f, b"m%d" % i))
    first_service = {}
    rounds = 0
    seen_per_tenant: dict[int, list] = {f.tenant: [] for f in flows}
    while len(q):
        got = q.take(budget)
        assert got, "take() made no progress on a non-empty queue"
        rounds += 1
        for item in got:
            t = item[0].tenant
            first_service.setdefault(t, rounds)
            seen_per_tenant[t].append(item[1])
    # No starvation: every tenant was first served within the rounds one
    # full WRR cycle can take at this budget.
    max_cycle = -(-sum(min(w, backlog) for w in weights) // budget)
    for t, r in first_service.items():
        assert r <= max_cycle
    # Per-tenant FIFO preserved.
    for f in flows:
        assert seen_per_tenant[f.tenant] == [b"m%d" % i
                                             for i in range(backlog)]


@settings(max_examples=25, deadline=None)
@given(budget=st.integers(min_value=1, max_value=32),
       backlog=st.integers(min_value=0, max_value=20))
def test_tenant_fair_queue_work_conserving_when_tenant_idle(budget, backlog):
    """An idle tenant's share flows to backlogged tenants: a take always
    returns min(budget, total backlog) regardless of who is idle."""
    q = TenantFairQueue()
    q.weight_of = QoSProfile(tenant_weights={1: 1, 2: 7}).weight_of
    f1 = _flow(1)
    for i in range(backlog):
        q.append((f1, bytes([i])))       # tenant 2 is entirely idle
    got = q.take(budget)
    assert len(got) == min(budget, backlog)
    assert len(q) == backlog - len(got)


def test_tenant_fair_queue_single_tenant_is_fifo():
    """With one tenant the fair queue IS the deque it replaced."""
    q = TenantFairQueue()
    f = _flow(0)
    items = [(f, bytes([i])) for i in range(10)]
    for it in items:
        q.append(it)
    assert q.take(4) == items[:4]
    assert q.take(100) == items[4:]
    assert not len(q)


def test_flow_demux_wire_fair_pop_across_tenants():
    """A flooding tenant's host-wire backlog cannot monopolize a drain
    slice: equal weights alternate tenants; per-flow FIFO holds."""
    w = FlowDemuxWire("t")
    w.weight_of = QoSProfile().weight_of      # every tenant weighs 1
    hog, victim = _flow(1), _flow(2)
    for i in range(50):
        w.push(Packet(hog, i, bytes([i])))
    w.push(Packet(victim, 0, b"v"))
    got = w.pop_many(4)
    assert len(got) == 4
    # The victim's single packet is served in the FIRST drain slice.
    assert [p.flow.tenant for p in got].count(2) == 1
    hog_payloads = [bytes(p.payload) for p in got if p.flow.tenant == 1]
    assert hog_payloads == [bytes([i]) for i in range(len(hog_payloads))]
    rest = w.pop_many(1000)
    assert len(rest) == 47 and not bool(w)


def test_flow_demux_wire_weighted_share():
    """Weights divide a contended drain slice proportionally."""
    w = FlowDemuxWire("t")
    w.weight_of = QoSProfile(tenant_weights={1: 3, 2: 1}).weight_of
    a, b = _flow(1), _flow(2)
    for i in range(40):
        w.push(Packet(a, i, b"a"))
        w.push(Packet(b, i, b"b"))
    got = w.pop_many(16)
    counts = {1: 0, 2: 0}
    for p in got:
        counts[p.flow.tenant] += 1
    assert counts[1] == 12 and counts[2] == 4   # 3:1 split of the slice


# ---------------------------------------------------------------------------
# Token-bucket admission: conservation + hints
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(arrivals=st.lists(st.tuples(st.integers(min_value=1, max_value=3),
                                   st.integers(min_value=0, max_value=12),
                                   st.integers(min_value=0, max_value=3)),
                         min_size=1, max_size=40),
       rate=st.integers(min_value=1, max_value=6))
def test_admission_conservation_granted_plus_shed_is_offered(arrivals,
                                                             rate):
    """Exact conservation under any (tenant, burst-size, tick-gap) arrival
    pattern: every offered request is either granted or shed, and the
    per-tenant shed counts sum to the aggregate."""
    clock = TickClock()
    adm = TenantAdmission(QoSProfile(default_rate=float(rate)), clock)
    granted = 0
    for tenant, n, gap in arrivals:
        for _ in range(gap):
            clock.tick()
        granted += adm.admit(tenant, n)
    assert adm.granted == granted
    assert adm.granted + adm.shed == adm.offered
    assert sum(adm.tenant_shed.values()) == adm.shed
    # retry_after is >= 1 exactly when the bucket is dry.
    for tenant, _, _ in arrivals:
        ra = adm.retry_after(tenant)
        assert ra >= 0


def test_token_bucket_refill_and_retry_after():
    clock_now = 0
    b = TokenBucket(rate=2.0, burst=4.0)
    assert b.grant(clock_now, 10) == 4        # starts full, capped at burst
    assert b.retry_after(clock_now) == 1      # 2/tick -> one tick refills
    assert b.grant(1, 10) == 2                # one tick elapsed: rate tokens
    slow = TokenBucket(rate=0.25, burst=1.0)
    assert slow.grant(0, 1) == 1
    assert slow.retry_after(0) == 4           # ceil(1 / 0.25)


def test_admission_shed_carries_retry_after_hint_and_tenant():
    """An over-limit tenant's requests shed EARLY with the bucket state as
    the E_SHED body; the under-limit tenant on the same server is
    untouched."""
    srv = DDSStorageServer(ServerConfig(
        device_capacity=1 << 24,
        qos=QoSProfile(tenant_rates={7: 1.0}, tenant_bursts={7: 2.0})))
    hog = DDSClient(srv, port=31001, tenant=7)
    good = DDSClient(srv, port=31002, tenant=8)   # no rate: unlimited
    fid = srv.frontend.create_file("adm")
    srv.frontend.write_sync(fid, 0, b"\x01" * 4096)
    srv.run_until_idle()
    hog_rids = hog.submit([("r", fid, 0, 64)] * 6)   # burst 2: 4 must shed
    good_rids = good.submit([("r", fid, 0, 64)] * 6)
    hog_got = hog.harvest(hog_rids)
    good_got = good.harvest(good_rids)
    assert all(s == wire.E_OK for s, _ in good_got.values())
    sheds = {r: v for r, v in hog_got.items() if v[0] == wire.E_SHED}
    assert len(sheds) == 4
    for _, (_, body) in sheds.items():
        tenant, retry_after = wire.decode_shed_hint(body)
        assert tenant == 7 and retry_after >= 1
    assert srv.director.stats.admission_shed == 4
    assert srv.admission.summary()["shed"] == 4
    assert srv.lifecycle.tenant_sheds == {7: 4}
    stats = srv.latency_stats()
    assert stats["admission"]["granted"] + stats["admission"]["shed"] \
        == stats["admission"]["offered"]


def test_cluster_sheds_do_not_touch_other_tenants_counters():
    """A shed is reconciled against the shedding tenant's own connection:
    the other tenant's client drains to zero outstanding with correct
    latency stats and NO shed responses."""
    cluster = DDSCluster(num_shards=2, config=ServerConfig(
        device_capacity=1 << 24,
        qos=QoSProfile(tenant_rates={3: 1.0}, tenant_bursts={3: 1.0})))
    fid = cluster.create_file("iso")
    cluster.write_sync(fid, 0, b"\x02" * 8192)
    hog = ClusterClient(cluster, port=46000, tenant=3)
    good = ClusterClient(cluster, port=46200, tenant=4)
    hog_rids = hog.submit([("r", fid, 0, 64)] * 8)
    good_rids = good.submit([("r", fid, 0, 64)] * 8)
    good_got = good.harvest(good_rids)
    assert all(s == wire.E_OK for s, _ in good_got.values())
    assert good.outstanding() == 0
    hog_got = hog.harvest(hog_rids)
    assert hog.outstanding() == 0
    statuses = [s for s, _ in hog_got.values()]
    assert wire.E_SHED in statuses            # over-limit: some shed
    for s, body in hog_got.values():
        if s == wire.E_SHED:
            assert wire.decode_shed_hint(body)[0] == 3
    # run_until_idle converges even with terminal sheds outstanding.
    hog2 = ClusterClient(cluster, port=46400, tenant=3)
    rids2 = hog2.submit([("r", fid, 0, 64)] * 8)
    hog2.run_until_idle()
    assert hog2.outstanding() == 0            # sheds reconciled, not leaked
    got2 = hog2.harvest(rids2, block=False)
    assert len(got2) == len(rids2)
    stats = cluster.latency_stats()
    assert 4 in stats["tenants"] and "sheds" not in stats["tenants"][4]
    assert stats["tenants"][3]["sheds"] >= 1


# ---------------------------------------------------------------------------
# Tick-deterministic two-tenant interference regression
# ---------------------------------------------------------------------------


def _interference_run() -> tuple[dict, dict]:
    cluster = DDSCluster(num_shards=2, config=ServerConfig(
        device_capacity=1 << 24,
        qos=QoSProfile(default_rate=8.0, default_burst=16.0)))
    fid = cluster.create_file("det")
    cluster.write_sync(fid, 0, b"\x03" * 16384)
    victim = ClusterClient(cluster, port=47000, tenant=1)
    hog = ClusterClient(cluster, port=47200, tenant=2)
    for _ in range(6):
        v = victim.submit([("r", fid, 64 * i, 64) for i in range(4)])
        h = hog.submit([("r", fid, 64 * i, 64) for i in range(24)])
        victim.harvest(v)
        hog.harvest(h, block=False)
        hog.run_until_idle()
    per_shard = [srv.lifecycle.summary() for srv in cluster.servers]
    return cluster.latency_stats(), {"shards": per_shard}


def test_two_tenant_interference_is_tick_deterministic():
    a = _interference_run()
    b = _interference_run()
    assert a == b
    stats = a[0]
    assert 1 in stats["tenants"] and 2 in stats["tenants"]
    assert stats["tenants"][1]["dpu_read"]["count"] > 0


# ---------------------------------------------------------------------------
# submit/harvest: a mixed burst answers as the one-op forms do
# ---------------------------------------------------------------------------


def _dds_ops_run(burst: bool):
    """One mixed read/write sequence on a fresh server, issued as ONE
    ``submit`` burst or one op at a time through ``read``/``write``."""
    srv = DDSStorageServer(ServerConfig(device_capacity=1 << 24))
    cli = DDSClient(srv)
    fid = srv.frontend.create_file("uni")
    srv.frontend.write_sync(fid, 0, bytes(range(256)) * 16)
    srv.run_until_idle()
    ops = [("w", fid, 0, b"A" * 64), ("read", fid, 64, 32),
           ("write", fid, 128, b"B" * 16), ("r", fid, 512, 64)]
    if burst:
        rids = cli.submit(ops)
    else:
        rids = [(cli.read if op[0] in ("r", "read") else cli.write)(*op[1:])
                for op in ops]
    got = cli.harvest(rids)
    srv.run_until_idle()       # the writes land once the pipeline quiesced
    chk = cli.submit([("r", fid, 0, 64), ("r", fid, 128, 16)])
    after = cli.harvest(chk)
    # harvest(None) drains whatever already arrived.
    cli.harvest(cli.submit([("r", fid, 128, 16)]))
    assert cli.harvest() == {}
    return ([got[r] for r in rids], [after[r] for r in chk],
            cli.stats.batches_sent)


def test_dds_client_submit_harvest_matches_wrappers():
    burst, burst_after, burst_batches = _dds_ops_run(True)
    single, single_after, single_batches = _dds_ops_run(False)
    assert burst == single and burst_after == single_after
    assert [s for s, _ in burst] == [wire.E_OK] * 4
    assert burst[1][1] == (bytes(range(256)) * 16)[64:96]
    assert burst[3][1] == (bytes(range(256)) * 16)[512:576]
    assert burst_after == [(wire.E_OK, b"A" * 64), (wire.E_OK, b"B" * 16)]
    # One network message for the burst, one per op otherwise.
    assert (burst_batches, single_batches) == (3, 6)


def test_cluster_client_submit_mixed_batch_and_harvest_nonblocking():
    cluster = DDSCluster(num_shards=2,
                         config=ServerConfig(device_capacity=1 << 24))
    fids = [cluster.create_file(f"u{i}") for i in range(3)]
    for fid in fids:
        cluster.write_sync(fid, 0, b"\x07" * 4096)
    cli = ClusterClient(cluster, port=48000)
    rids = cli.submit([("w", fids[0], 0, b"x" * 64),
                       ("r", fids[1], 0, 64),
                       ("write", fids[2], 64, b"y" * 64),
                       ("read", fids[0], 1024, 64)])
    got = cli.harvest(rids)
    assert [got[r][0] for r in rids] == [wire.E_OK] * 4
    assert got[rids[1]][1] == b"\x07" * 64
    assert got[rids[3]][1] == b"\x07" * 64
    # The one-op forms answer from the same book.
    r = [cli.read(fids[1], 0, 16), cli.read(fids[2], 64, 64)]
    got2 = cli.harvest(r)
    assert got2[r[1]][1] == b"y" * 64
    assert cli.outstanding() == 0
    # Non-blocking harvest returns only what has arrived — never raises.
    r3 = cli.submit([("r", fids[0], 0, 8)])
    part = cli.harvest(r3, block=False)
    assert set(part) <= set(r3)
    cli.harvest(r3)


_KV_PHASES = [
    [("put", b"k1", b"v1" * 8), ("put", b"k2", b"v2" * 8),
     ("put", b"k3", b"v3")],
    [("get", b"k1"), ("delete", b"k2"), ("get", b"k3"),
     ("put", b"k4", b"v4")],
    [("get", b"k2"), ("get", b"k4"), ("delete", b"k4")],
    [("get", b"k4"), ("get", b"k1")],
]


def _kv_ops_run(burst: bool):
    """The phases above on a fresh two-shard store, each phase issued as
    ONE mixed ``submit`` burst or one op at a time through
    ``put``/``get``/``delete``; every phase is harvested before the next."""
    store = ShardedKVStore(num_shards=2,
                           config=ServerConfig(device_capacity=1 << 24))
    cli = KVClient(store, tenant=5)
    one = {"put": cli.put, "get": cli.get, "delete": cli.delete}
    out = []
    for ops in _KV_PHASES:
        rids = (cli.submit(ops) if burst
                else [one[op[0]](*op[1:]) for op in ops])
        got = cli.harvest(rids)
        out.append([(got[r][0], decode_record(got[r][1])[1]
                     if op[0] == "get" and got[r][0] == wire.E_OK else None)
                    for r, op in zip(rids, ops)])
    return out, store


def test_kv_client_submit_mixed_and_wrappers():
    burst, store = _kv_ops_run(True)
    single, _ = _kv_ops_run(False)
    assert burst == single
    ok, gone = wire.E_OK, wire.E_NOENT
    assert burst == [
        [(ok, None)] * 3,
        [(ok, b"v1" * 8), (ok, None), (ok, b"v3"), (ok, None)],
        # After the DEL's ack, the mapping is gone (invalidate-on-read).
        [(gone, None), (ok, b"v4"), (ok, None)],
        [(gone, None), (ok, b"v1" * 8)],
    ]
    # Per-tenant stats accumulated under tenant 5 across shards.
    merged = store.latency_stats()
    assert 5 in merged["tenants"]


def test_tenant_rides_wire_and_stats_once_per_connection():
    """The tenant binds once per client; per-tenant histograms split by
    serving class while the aggregate equals the per-tenant sum."""
    srv = DDSStorageServer(ServerConfig(device_capacity=1 << 24))
    t1 = DDSClient(srv, port=31101, tenant=1)
    t2 = DDSClient(srv, port=31102, tenant=2)
    fid = srv.frontend.create_file("mix")
    srv.frontend.write_sync(fid, 0, b"\x09" * 4096)
    srv.run_until_idle()
    r1 = t1.submit([("r", fid, 0, 64)] * 4)
    r2 = t2.submit([("w", fid, 64 * i, b"z" * 64) for i in range(3)])
    t1.harvest(r1)
    t2.harvest(r2)
    summ = srv.lifecycle.summary()["tenants"]
    assert summ[1]["dpu_read"]["count"] == 4
    assert summ[2]["write"]["count"] == 3
    assert srv.lifecycle.hist["dpu_read"].n == 4
    assert srv.lifecycle.hist["write"].n == 3
