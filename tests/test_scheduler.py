"""Work-signaled cluster scheduler: ready-set invariants (PR 4).

The contract under test (see ``distributed.cluster``):

  * **no lost wakeups** — a shard stays runnable (armed in the ready set)
    while ``server.busy()`` holds: pending device completions, undrained
    rings/wires, in-flight host requests;
  * **idle shards cost nothing** — with traffic directed at one shard,
    the other shards take ZERO pump steps;
  * **equivalence** — ``run_until_idle`` leaves the cluster in a state
    byte-identical to the pre-overhaul poll-every-shard loop.
"""

import pytest

from repro.core import wire
from repro.core.client import ClusterClient
from repro.distributed.cluster import DDSCluster, ReadySet


def _mixed_workload(cli: ClusterClient, fids: list, rounds: int = 3) -> list:
    """A deterministic read+write mix touching every file."""
    rids = []
    for r in range(rounds):
        for i, f in enumerate(fids):
            rids.append(cli.write(f, 128 * r, bytes([r + 1]) * (64 + i)))
            rids.append(cli.read(f, 64 * r, 96))
        cli.flush()
    return rids


def _loaded_cluster(num_shards: int = 4):
    cl = DDSCluster(num_shards=num_shards)
    fids = [cl.create_file(f"s{i}") for i in range(2 * num_shards)]
    for i, f in enumerate(fids):
        cl.write_sync(f, 0, bytes([i + 1]) * 4096)
    return cl, fids


# -- ready-set primitive ---------------------------------------------------------------

def test_ready_set_mark_take_rearm_semantics():
    rs = ReadySet(4)
    assert not rs and rs.take() == []
    rs.mark(2)
    rs.mark(0)
    rs.mark(2)                      # double-mark is idempotent
    assert len(rs) == 2
    assert rs.take() == [0, 2]      # shard-index order (determinism)
    assert rs.take() == []          # take clears
    rs.mark(1)                      # re-arm after take works
    assert rs.take() == [1]


def test_ready_set_quiet_latch_cleared_by_mark():
    rs = ReadySet(2)
    rs.quiet = True
    rs.mark(0)
    assert not rs.quiet             # any doorbell invalidates verified-idle
    assert rs.take() == [0]


# -- no lost wakeups -------------------------------------------------------------------

def test_client_send_arms_the_target_shard():
    cl, fids = _loaded_cluster(4)
    cl.run_until_idle()
    cli = ClusterClient(cl)
    cl.run_until_idle()             # settle the SYN handshakes
    loc = cl.locate(fids[0])
    cli.read(fids[0], 0, 64)
    cli.flush()                     # the send IS the doorbell
    assert loc.shard in cl.runnable()


def test_busy_server_stays_runnable_until_drained():
    """THE no-lost-wakeup invariant: busy => armed, at every pump step."""
    cl, fids = _loaded_cluster(4)
    cli = ClusterClient(cl)
    rids = _mixed_workload(cli, fids)
    for _ in range(200_000):
        for i, srv in enumerate(cl.servers):
            if srv.busy():
                assert i in cl.runnable(), \
                    f"shard {i} is busy but not runnable (lost wakeup)"
        if cl.pump() + cli.poll() == 0 and cli.outstanding() == 0:
            break
    res = cli.harvest(rids)
    assert all(s == wire.E_OK for s, _ in res.values())


def test_device_backlog_keeps_shard_runnable():
    """A shard whose device holds pending completions must stay armed even
    when its own pump produced no work this step."""
    cl, _ = _loaded_cluster(2)
    cl.run_until_idle()
    srv = cl.servers[0]
    buf = bytearray(64)
    # A raw tagged submission (no file-service consumer): the device is
    # busy until polled, then its completion queue holds the cookie.
    srv.device.submit_read(0, 64, memoryview(buf), cookie=7)
    assert srv.device.busy()
    assert 0 in cl.runnable()       # the submission doorbell armed shard 0
    cl.pump()
    assert 0 in cl.runnable()       # still busy => still armed (re-arm rule)
    srv.device.drain()
    assert srv.device.busy()        # completion awaits reap: still busy
    assert 0 in cl.runnable()
    srv.device.reap()
    cl.run_until_idle()             # idle-sweep escape: terminates anyway


def test_wakeup_after_verified_idle():
    """The quiet latch must not swallow doorbells: work issued AFTER the
    cluster verified itself idle is still served."""
    cl, fids = _loaded_cluster(4)
    cli = ClusterClient(cl)
    cl.run_until_idle()
    assert cl.pump() == 0           # verified idle (quiet latch set)
    assert cl.pump() == 0           # stays idle for free
    st, body = cli.wait(cli.read(fids[0], 0, 32))
    assert st == wire.E_OK and len(body) == 32


# -- idle shards cost nothing ----------------------------------------------------------

def test_idle_shards_take_zero_pump_steps():
    cl, fids = _loaded_cluster(16)
    cli = ClusterClient(cl)
    cli.run_until_idle()
    target = cl.locate(fids[0]).shard
    mine = [f for f in fids if cl.locate(f).shard == target]
    before = list(cl.pump_steps)
    rids = []
    for r in range(4):
        rids += [cli.read(f, 32 * r, 64) for f in mine]
        cli.flush()
    res = cli.harvest(rids)
    assert all(s == wire.E_OK for s, _ in res.values())
    deltas = [after - b for after, b in zip(cl.pump_steps, before)]
    assert deltas[target] > 0
    for shard, d in enumerate(deltas):
        if shard != target:
            assert d == 0, f"idle shard {shard} was pumped {d} times"


# -- equivalence with the pre-overhaul loop --------------------------------------------

def _legacy_run_until_idle(cluster: DDSCluster, max_iters: int = 200_000):
    """The pre-PR poll-everything loop, verbatim."""
    idle = 0
    for _ in range(max_iters):
        work = 0
        for srv in cluster.servers:
            work += srv.pump()
        if work == 0:
            for srv in cluster.servers:
                srv.device.drain()
            idle += 1
            if idle >= 3:
                return
        else:
            idle = 0
    raise TimeoutError("legacy loop did not go idle")


def test_run_until_idle_matches_legacy_loop_byte_for_byte():
    results = []
    for legacy in (True, False):
        cl, fids = _loaded_cluster(4)
        cli = ClusterClient(cl)
        rids = _mixed_workload(cli, fids, rounds=4)
        if legacy:
            _legacy_run_until_idle(cl)
        else:
            cl.run_until_idle()
        while cli.poll():
            pass
        st = cl.stats()
        results.append((dict(cli.responses),
                        st.offloaded_completed, st.host_responses,
                        [bytes(s.fs.device.raw_read(0, 4096))
                         for s in cl.servers]))
        assert set(cli.responses) == set(rids)
    (resp_a, off_a, host_a, mem_a), (resp_b, off_b, host_b, mem_b) = results
    assert resp_a == resp_b          # same statuses, same payload bytes
    assert (off_a, host_a) == (off_b, host_b)
    assert mem_a == mem_b            # on-"disk" state identical


def test_cluster_run_until_idle_converges_without_idle_sweeps():
    """Once verifiably idle, run_until_idle costs O(1) pumps, not sweeps."""
    cl, _ = _loaded_cluster(8)
    cl.run_until_idle()
    before = list(cl.pump_steps)
    for _ in range(50):
        cl.run_until_idle()          # idle convergence: no server stepped
    assert cl.pump_steps == before


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
