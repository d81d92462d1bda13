"""System-level property tests (hypothesis): invariants of the DDS stack.

Invariants under random workloads:
  * end-to-end linearizability vs a shadow file (reads see the latest
    acknowledged write, regardless of DPU/host routing);
  * offload-engine responses arrive in request order per client (the
    context-ring ordering discipline, Fig 13);
  * every request is answered exactly once (no loss, no duplication)
    whether served by the DPU or bounced to the host;
  * the cache table never serves a stale page after invalidate-on-read
    (partial-offload correctness, §9.1).
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import wire
from repro.core.client import DDSClient
from repro.core.dds_server import DDSStorageServer, ServerConfig
from repro.storage.pagestore import PageStore


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_random_workload_matches_shadow(data):
    srv = DDSStorageServer(ServerConfig())
    fid = srv.frontend.create_file("prop.dat")
    size = 8192
    shadow = bytearray(size)
    srv.frontend.write_sync(fid, 0, bytes(size))
    srv.run_until_idle()
    cli = DDSClient(srv)
    n_ops = data.draw(st.integers(3, 12))
    for _ in range(n_ops):
        if data.draw(st.booleans()):
            off = data.draw(st.integers(0, size - 64))
            n = data.draw(st.integers(1, 64))
            val = bytes([data.draw(st.integers(0, 255))]) * n
            status, _ = cli.wait(cli.write(fid, off, val))
            assert status == wire.E_OK
            shadow[off : off + n] = val
        else:
            off = data.draw(st.integers(0, size - 64))
            n = data.draw(st.integers(1, 64))
            status, body = cli.wait(cli.read(fid, off, n))
            assert status == wire.E_OK
            assert body == bytes(shadow[off : off + n])


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 63), st.integers(1, 128)),
                min_size=2, max_size=16))
def test_offloaded_responses_in_request_order(reqs):
    """All-read batches: responses must come back in submission order."""
    srv = DDSStorageServer(ServerConfig(offload_ring=4))  # small ring: bounces
    fid = srv.frontend.create_file("ord.dat")
    srv.frontend.write_sync(fid, 0, bytes(range(256)) * 64)
    srv.run_until_idle()
    cli = DDSClient(srv)
    rids = cli.submit([("r", fid, off * 64, n) for off, n in reqs])
    seen = []
    for _ in range(400_000):
        cli.poll()
        for r in rids:
            if r in cli.responses and r not in seen:
                seen.append(r)
        if len(seen) == len(rids):
            break
        srv.pump()
    assert sorted(seen) == sorted(rids)            # exactly once, no loss
    st_off = srv.offload.stats
    assert st_off.completed + st_off.bounced_to_host >= len(
        [r for r in reqs])                          # all accounted


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_page_store_never_serves_stale(data):
    """After invalidate-on-read, GETs fall back to the host until the next
    replay re-caches — a DPU-served page always carries the freshest LSN."""
    ps = PageStore(page_size=512, num_pages=64)
    lsns = {}
    cli = DDSClient(ps.server)
    for step in range(data.draw(st.integers(4, 12))):
        page = data.draw(st.integers(0, 7))
        action = data.draw(st.sampled_from(["replay", "host_read", "get"]))
        if action == "replay":
            lsn = lsns.get(page, 0) + 10
            lsns[page] = lsn
            ps.replay(page, lsn, f"p{page}v{lsn}".encode())
        elif action == "host_read" and page in lsns:
            ps.host_read_for_update(page)           # invalidates DPU cache
        elif page in lsns:
            want = lsns[page]
            status, body = cli.wait(cli.send_raw(
                0, lambda rid: PageStore.encode_get(rid, page, want)))
            assert status == wire.E_OK
            lsn, payload = PageStore.decode_page(body)
            assert lsn == lsns[page]                # never stale
            assert payload.rstrip(b"\x00") == f"p{page}v{lsn}".encode()
