"""The request book both storage clients share.

One parametrised test pins the book's policy on both clients: a
``DDSClient`` on one server and a ``ClusterClient`` on a one-shard cluster
must answer the same faults the same way:

  * a dropped request is resent after ``timeout_ticks`` and answered once;
  * a resend that crosses a delayed original answer surfaces its rid once:
    the late duplicate is discarded (and counted in ``dup_responses``)
    instead of resurfacing through a later ``harvest()``;
  * a shed request resolves terminally as ``(E_SHED, hint)``;
  * an epoch-fence redirect replays the SAME rid under the new epoch.
"""

import pytest

from repro.core import wire
from repro.core.client import ClusterClient, DDSClient
from repro.core.dds_server import DDSStorageServer, ServerConfig
from repro.core.faultnet import FaultSchedule, wrap_director
from repro.core.qos import QoSProfile
from repro.distributed.cluster import DDSCluster

DATA = bytes(range(256)) * 16


def _client(kind: str, cfg: ServerConfig, **kw):
    """(client, its one server, the shared tick clock, a loaded file id)."""
    if kind == "dds":
        srv = DDSStorageServer(cfg)
        fid = srv.frontend.create_file("book")
        srv.frontend.write_sync(fid, 0, DATA)
        srv.run_until_idle()
        return DDSClient(srv, **kw), srv, srv.clock, fid
    cl = DDSCluster(1, cfg, elastic=True)
    fid = cl.create_file("book")
    cl.write_sync(fid, 0, DATA)
    return ClusterClient(cl, **kw), cl.servers[0], cl.clock, fid


def _dropped_request_resent_once(kind):
    cli, srv, clock, fid = _client(kind, ServerConfig(device_capacity=1 << 24),
                                   timeout_ticks=4)
    wrap_director(srv.director, clock,
                  ingress=FaultSchedule(seed=1, drop=1.0,
                                        stop_tick=clock.now + 2))
    rid = cli.read(fid, 0, 64)
    assert cli.harvest([rid]) == {rid: (wire.E_OK, DATA[:64])}
    cli.run_until_idle()
    assert cli.harvest() == {}
    st = cli.stats
    assert (st.timeouts, st.resends, st.responses, st.dup_responses) \
        == (1, 1, 1, 0)


def _crossed_resend_surfaces_once(kind):
    cli, srv, clock, fid = _client(kind, ServerConfig(device_capacity=1 << 24),
                                   timeout_ticks=4)
    # The original answer is held 12 ticks; the resend's answer is not.
    _fin, fout = wrap_director(
        srv.director, clock,
        responses=FaultSchedule(seed=1, delay=1.0, delay_ticks=(12, 12),
                                stop_tick=clock.now + 4))
    rid = cli.read(fid, 0, 64)
    assert cli.harvest([rid]) == {rid: (wire.E_OK, DATA[:64])}
    assert cli.stats.resends == 1
    assert fout.injection_stats()["held"] == 1
    release = clock.now + 12
    while clock.now < release:   # the client owes nothing: pumps only
        cli.pump()
    # The late original drains with the next request's answer and is
    # discarded: its rid already surfaced.
    rid2 = cli.read(fid, 64, 64)
    assert cli.harvest([rid2]) == {rid2: (wire.E_OK, DATA[64:128])}
    assert cli.stats.dup_responses == 1
    assert cli.harvest() == {}
    assert cli.outstanding() == 0


def _shed_surfaces_terminally(kind):
    cli, _srv, _clock, fid = _client(kind, ServerConfig(
        device_capacity=1 << 24,
        qos=QoSProfile(tenant_rates={7: 1.0}, tenant_bursts={7: 2.0})),
        tenant=7)                   # no shed retry: surface at once
    res = cli.harvest(cli.submit([("r", fid, 0, 64)] * 6))
    sheds = [body for status, body in res.values() if status == wire.E_SHED]
    assert len(sheds) == 4          # burst 2.0: four of six shed
    for body in sheds:
        tenant, retry_after = wire.decode_shed_hint(body)
        assert tenant == 7 and retry_after >= 1
    assert sum(1 for v in res.values() if v == (wire.E_OK, DATA[:64])) == 2
    assert cli.outstanding() == 0


def _redirect_replays_under_new_epoch(kind):
    cli, srv, _clock, fid = _client(kind, ServerConfig())
    if kind == "dds":
        srv.director.epoch_of = lambda: 3
        srv.director.on_stale_epoch = srv._on_stale_epoch
        cli.epoch = 1               # stale: the fence must refuse + redirect
    else:
        cli.cluster.epoch = 3       # the ring moved on before the client saw
    rid = cli.read(fid, 0, 64)
    assert cli.wait(rid) == (wire.E_OK, DATA[:64])   # transparent replay
    assert [conn.epoch for conn in cli.conns] == [3]  # adopted the epoch
    assert srv.lifecycle.redirects >= 1
    assert cli.outstanding() == 0


@pytest.mark.parametrize("case", [_dropped_request_resent_once,
                                  _crossed_resend_surfaces_once,
                                  _shed_surfaces_terminally,
                                  _redirect_replays_under_new_epoch],
                         ids=["timeout", "crossed-resend", "shed",
                              "redirect"])
@pytest.mark.parametrize("kind", ["dds", "cluster"])
def test_request_book_policy(kind, case):
    case(kind)
