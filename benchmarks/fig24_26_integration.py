"""Figs 24-26 functional counterpart (MEASURED offload behaviour).

The latency/CPU numbers for the production integrations are modeled
(fig14_16_model.py); what IS measurable here is the part DDS actually
contributes — the offload RATIO and correctness of the partial-offload
policy under a realistic access mix:

  * page server: replay pages (host writes), then serve GetPage@LSN where
    a fraction of requests ask for LSNs newer than the cache (must fall to
    the host) and the rest offload;
  * FASTER-style KV: uniform GETs over flushed records (DPU) vs tail
    records (host), as in §9.2 where "most requests are serviced by
    IDevice".
"""

from __future__ import annotations

import time

from benchmarks.common import emit, section
from repro.core.client import DDSClient
from repro.storage.pagestore import KVStoreServer, PageStore

N_PAGES = 64
N_GETS = 400


def page_server() -> None:
    ps = PageStore(page_size=1024, num_pages=N_PAGES * 2)
    for p in range(N_PAGES):
        ps.replay(p, lsn=100, payload=f"page-{p}".encode())
    cli = DDSClient(ps.server)
    t0 = time.perf_counter()
    for i in range(N_GETS):
        if i % 10 == 0:
            # 10%: LSN newer than the cache -> host path (partial offload);
            # dedicated page range, since the host read invalidates the page
            # until the next log replay re-caches it (§9.1 semantics).
            page, lsn = N_PAGES - 1 - (i // 10) % 8, 150
        else:
            page, lsn = (i * 13) % (N_PAGES - 8), 100
        cli.wait(cli.send_raw(
            0, lambda rid: PageStore.encode_get(rid, page, lsn)))
    dt = time.perf_counter() - t0
    st = ps.server.offload.stats
    emit("fig24_pageserver", dt / N_GETS * 1e6,
         f"dpu_served={st.completed} host_served={ps.host_served} "
         f"offload_ratio={st.completed / N_GETS:.2f} "
         f"host_cpu_s={ps.server.host_cpu_busy_s:.4f}")


def kv_server() -> None:
    kv = KVStoreServer()
    for i in range(256):
        kv.upsert(f"k{i}".encode(), f"v{i}".encode() * 4)
    kv.flush()                              # all 256 now DPU-servable
    for i in range(16):
        kv.upsert(f"hot{i}".encode(), b"tail")   # 16 host-resident keys
    cli = DDSClient(kv.server)
    t0 = time.perf_counter()
    for i in range(N_GETS):
        key = (f"hot{i % 16}" if i % 16 == 0 else f"k{(i * 7) % 256}").encode()
        cli.wait(cli.send_raw(
            0, lambda rid: KVStoreServer.encode_get(rid, key)))
    dt = time.perf_counter() - t0
    st = kv.server.offload.stats
    emit("fig25_26_kv", dt / N_GETS * 1e6,
         f"dpu_served={st.completed} "
         f"offload_ratio={st.completed / N_GETS:.2f} "
         f"host_cpu_s={kv.server.host_cpu_busy_s:.4f}")


def main() -> None:
    section("fig24-26: integration offload ratios (measured)")
    page_server()
    kv_server()


if __name__ == "__main__":
    main()
