"""Figs 18 & 23: zero-copy on the storage path and the offload engine.

MEASURED: the same request streams run with ``zero_copy`` on and off.

Fig 18 — host-issued file I/O through the rings + DPU file service, by
request size; the paper reports up to +93% throughput from eliminating the
request/response copies (§4.3).

Fig 23 — offloaded reads through the full server (traffic director ->
offload engine -> SSD): throughput and copies with and without the
pre-allocated read/packet buffers of §6.2 (paper: 520K -> 730K IOPS,
250 us -> 170 us).
"""

from __future__ import annotations

import time

from benchmarks.common import emit, section
from repro.core.client import DDSClient
from repro.core.dds_server import DDSStorageServer, ServerConfig
from repro.core.file_service import FileServiceRunner, SegmentFS
from repro.core.host_lib import DDSFrontEnd
from repro.core.ring import DMAEngine
from repro.storage.blockdev import BlockDevice

N_OPS = 400


def _file_io_rate(zero_copy: bool, size: int) -> tuple[float, int]:
    dev = BlockDevice(1 << 24, block_size=512)
    fs = SegmentFS(dev, 1 << 16)
    svc = FileServiceRunner(fs, DMAEngine(), zero_copy=zero_copy)
    fe = DDSFrontEnd(svc, ring_capacity=1 << 18)
    fid = fe.create_file("bench")
    fe.write_sync(fid, 0, bytes(size))
    gid = fe._control_group
    t0 = time.perf_counter()
    done = issued = 0
    # Pipelined: drain responses while keeping a bounded window in flight
    # (an un-drained host would otherwise trip the service's load shedding).
    window = max(2, (1 << 17) // (size + 64))
    inflight = 0
    while done < N_OPS:
        while inflight < window and issued < N_OPS:
            fe.read_file(fid, 0, size)
            issued += 1
            inflight += 1
        svc.step()
        got = len(fe.poll_wait(gid))
        done += got
        inflight -= got
    dt = time.perf_counter() - t0
    return N_OPS / dt, svc.stats.response_copies + svc.stats.request_copies


def _offload_rate(zero_copy: bool, size: int) -> tuple[float, int]:
    srv = DDSStorageServer(ServerConfig(zero_copy=zero_copy))
    fid = srv.frontend.create_file("data")
    srv.frontend.write_sync(fid, 0, bytes(max(size * 4, 4096)))
    srv.run_until_idle()
    cli = DDSClient(srv)
    t0 = time.perf_counter()
    for i in range(N_OPS):
        rid = cli.read(fid, 0, size)
        if i % 16 == 15:
            cli.wait(rid)
    # drain the rest
    for _ in range(200_000):
        srv.pump()
        cli.poll()
        if srv.offload.stats.completed + srv.offload.stats.failed >= N_OPS:
            break
    dt = time.perf_counter() - t0
    return N_OPS / dt, srv.offload.stats.data_copies


def main() -> None:
    section("fig18: storage-path zero-copy (measured)")
    for size in (512, 4096, 16384):
        zc, zc_copies = _file_io_rate(True, size)
        cp, cp_copies = _file_io_rate(False, size)
        emit(f"fig18_size{size}", 1e6 / zc,
             f"zero_copy={zc:,.0f}/s copy={cp:,.0f}/s "
             f"gain={100 * (zc / cp - 1):.0f}% copies_eliminated={cp_copies}")
    section("fig23: offload-engine zero-copy (measured)")
    for size in (1024,):
        zc, _ = _offload_rate(True, size)
        cp, copies = _offload_rate(False, size)
        emit(f"fig23_size{size}", 1e6 / zc,
             f"zero_copy={zc:,.0f}/s copy={cp:,.0f}/s "
             f"gain={100 * (zc / cp - 1):.0f}% copies_in_copy_mode={copies}")


if __name__ == "__main__":
    main()
