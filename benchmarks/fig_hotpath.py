"""Hot-path ops/sec: the simulated data plane at CPython line rate.

DDS's argument (§4-§6) is that request throughput is won by deleting
per-request overhead — batching, zero-copy, O(1) bookkeeping.  This
benchmark applies the same standard to the simulator itself: it drives a
4-shard cluster with pipelined, batched clients issuing small offloaded
reads and measures

  * **wall-clock requests/sec** of the whole request/response hot path
    (director ingress -> offload engine -> pool -> indirect packets ->
    client reassembly), and
  * **modeled µs/request** (the paper-calibrated service time, which must
    NOT change when the simulator gets faster).

Results go to ``BENCH_hotpath.json`` in the repo root.  Because wall-clock
numbers are machine-dependent, every measurement is **calibrated**: a fixed
pure-Python reference loop is timed alongside the workload, and committed
numbers are rescaled by the ratio of reference speeds before any gate is
applied.  The JSON keeps three sections:

  ``baseline``  — the pre-overhaul hot path, recorded once with
                  ``--record-baseline`` before the zero-copy overhaul
                  (PR 2) landed;
  ``current``   — the overhauled hot path, recorded with
                  ``--record-current``;
  ``last_run``  — whatever this invocation measured (always rewritten).

Gates:

  * full mode asserts >= ``FULL_SPEEDUP_GATE`` (2.0x) calibrated ops/sec
    over the recorded baseline;
  * ``--smoke`` (CI fast lane) runs a reduced config and fails on a >30%
    calibrated regression vs the recorded ``current`` numbers;
  * both modes assert the zero-copy invariant (``data_copies == 0``) and
    that every read was served.
"""

from __future__ import annotations

import gc
import json
import os
import struct
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import emit, section  # noqa: E402
from repro.core.client import ClusterClient  # noqa: E402
from repro.core.dds_server import ServerConfig  # noqa: E402
from repro.distributed.cluster import DDSCluster  # noqa: E402

JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_hotpath.json")

FULL_SPEEDUP_GATE = 2.0      # acceptance: overhaul >= 2x the pre-PR path
SMOKE_REGRESSION_GATE = 0.70  # CI: fail below 70% of recorded current

CONFIGS = {
    "full": dict(shards=4, clients=4, files_per_shard=8, rounds=16,
                 reads_per_round=256, read_size=128),
    "smoke": dict(shards=4, clients=2, files_per_shard=4, rounds=6,
                  reads_per_round=64, read_size=128),
}


def calibrate(iters: int = 200_000) -> float:
    """Reference ops/sec of a fixed pure-Python loop (machine-speed proxy).

    The loop mixes the primitives the hot path leans on (struct packing,
    dict traffic, bytes slicing) so the ratio between two machines tracks
    how the workload itself would scale.
    """
    pack = struct.Struct("<QII").pack
    blob = bytes(range(256)) * 8
    t0 = time.perf_counter()
    d: dict[int, bytes] = {}
    for i in range(iters):
        d[i & 1023] = blob[i & 255 : (i & 255) + 64]
        pack(i, i & 0xFFFF, 64)
    dt = time.perf_counter() - t0
    return iters / dt


def run_workload(cfg: dict) -> dict:
    """Drive the pipelined read workload; return measured + modeled rates."""
    # Small cache table / device: setup is untimed but repeated per rep.
    cluster = DDSCluster(num_shards=cfg["shards"],
                         config=ServerConfig(device_capacity=1 << 26,
                                             cache_items=1 << 11))
    files = [cluster.create_file(f"hot{i}")
             for i in range(cfg["shards"] * cfg["files_per_shard"])]
    file_span = 1 << 16
    for i, f in enumerate(files):
        cluster.write_sync(f, 0, bytes([i & 0xFF]) * file_span)

    clients = [ClusterClient(cluster) for _ in range(cfg["clients"])]
    total = cfg["rounds"] * cfg["reads_per_round"]
    rsize = cfg["read_size"]
    max_off = file_span - rsize

    modeled_before = cluster.makespan_s()
    gc.collect()
    gc.disable()   # keep collector pauses out of the timed region
    t0 = time.perf_counter()
    issued = 0
    poll_style = hasattr(clients[0], "poll")   # post-overhaul drain API
    for r in range(cfg["rounds"]):
        # one batched message per shard per client, pipelined behind the
        # previous round (flush, don't wait)
        per_client = [[] for _ in clients]
        for k in range(cfg["reads_per_round"]):
            f = files[(issued + k) % len(files)]
            off = ((issued + k) * 977) % max_off
            per_client[(issued + k) % len(clients)].append((f, off, rsize))
        issued += cfg["reads_per_round"]
        for cli, reads in zip(clients, per_client):
            cli.submit([("r",) + r for r in reads])
        for cli in clients:
            cli.flush()
        if poll_style:
            # one cluster step per round; every client drains only its own
            # demuxed flows
            cluster.pump()
            for cli in clients:
                cli.poll()
        else:
            for cli in clients:                    # pre-PR: each client must
                cli.pump()                         # re-step the whole cluster
    # drain: responses stream back through each client's demuxed flow
    for _ in range(1_000_000):
        if sum(c.stats.responses for c in clients) >= total:
            break
        if poll_style:
            work = cluster.pump() + sum(c.poll() for c in clients)
        else:
            work = sum(c.pump() for c in clients)
        if work == 0:
            for srv in cluster.servers:
                srv.device.drain()
    elapsed = time.perf_counter() - t0
    gc.enable()

    got = sum(c.stats.responses for c in clients)
    assert got == total, f"served {got}/{total} reads"
    copies = sum(s.offload.stats.data_copies for s in cluster.servers)
    assert copies == 0, f"zero-copy invariant violated: {copies} data copies"
    offloaded = sum(s.offload.stats.completed for s in cluster.servers)
    modeled_s = cluster.makespan_s() - modeled_before
    return {
        "requests": total,
        "wall_s": elapsed,
        "ops_per_s": total / elapsed,
        "modeled_us_per_req": modeled_s / total * 1e6,
        "offloaded_frac": offloaded / total,
    }


def load_json() -> dict:
    if os.path.exists(JSON_PATH):
        with open(JSON_PATH) as fh:
            return json.load(fh)
    return {"schema": 1, "configs": CONFIGS}


def save_json(doc: dict) -> None:
    with open(JSON_PATH, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")


def main() -> None:
    argv = sys.argv[1:]
    smoke = ("--smoke" in argv
             or os.environ.get("DDS_BENCH_SMOKE", "0") == "1")
    record = ("baseline" if "--record-baseline" in argv else
              "current" if "--record-current" in argv else None)
    mode = "smoke" if smoke else "full"
    cfg = CONFIGS[mode]

    section(f"hot path ({mode}: {cfg['shards']} shards, {cfg['clients']} "
            f"clients, {cfg['rounds']}x{cfg['reads_per_round']} pipelined reads)")
    # Shared machines are noisy: take the best workload rep (max-of-N
    # approximates an unloaded machine) and pair it with the FASTEST
    # calibration observed across the run — the least-throttled estimate of
    # this machine's speed, which makes the normalized number conservative.
    reps = 2 if smoke else 3
    calib, res = 0.0, None
    for _ in range(reps):
        calib = max(calib, calibrate())
        r = run_workload(cfg)
        if res is None or r["ops_per_s"] > res["ops_per_s"]:
            res = r
    calib = max(calib, calibrate())
    emit(f"hotpath_{mode}", 1e6 / res["ops_per_s"],
         f"tput={res['ops_per_s']:.0f}op/s "
         f"modeled={res['modeled_us_per_req']:.2f}us/req "
         f"offload={res['offloaded_frac']:.2f}")

    doc = load_json()
    doc["configs"] = CONFIGS
    res = {**res, "config": cfg}   # pin the workload the numbers came from
    entry = {"calibration_ops_per_s": calib, mode: res}
    if record:
        doc.setdefault(record, {})["calibration_ops_per_s"] = calib
        doc[record][mode] = res
        print(f"# recorded {mode} measurement into '{record}'")
    doc["last_run"] = {"mode": mode, **entry}
    base, cur = doc.get("baseline", {}), doc.get("current", {})
    if base.get("full") and cur.get("full"):
        # normalized = ops per reference-op; ratio is machine-independent
        b = base["full"]["ops_per_s"] / base["calibration_ops_per_s"]
        c = cur["full"]["ops_per_s"] / cur["calibration_ops_per_s"]
        doc["speedup_full_calibrated"] = round(c / b, 3)
        doc["speedup_full_raw"] = round(cur["full"]["ops_per_s"]
                                        / base["full"]["ops_per_s"], 3)
    save_json(doc)

    def gate_ref(section: dict, which: str):
        """Recorded numbers are only comparable on the SAME workload."""
        ref = section.get(which)
        if ref and ref.get("config") != cfg:
            print(f"# recorded {which} numbers used a different workload "
                  f"config; gate skipped — re-record with the new config")
            return None
        return ref

    failures = []
    if not smoke and not record:
        base = doc.get("baseline", {})
        ref = gate_ref(base, "full")
        if ref:
            # rescale the committed baseline to THIS machine's speed
            scale = calib / base["calibration_ops_per_s"]
            target = ref["ops_per_s"] * scale * FULL_SPEEDUP_GATE
            ok = res["ops_per_s"] >= target
            print(f"# speedup vs baseline (calibrated): "
                  f"{res['ops_per_s'] / (ref['ops_per_s'] * scale):.2f}x "
                  f"(gate {FULL_SPEEDUP_GATE:.1f}x) -> {'OK' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"hot path below {FULL_SPEEDUP_GATE}x baseline: "
                    f"{res['ops_per_s']:.0f} < {target:.0f} op/s")
        else:
            print("# no recorded baseline; gate skipped")
    if smoke and not record:
        cur = doc.get("current", {})
        ref = gate_ref(cur, "smoke")
        if ref:
            scale = calib / cur["calibration_ops_per_s"]
            target = ref["ops_per_s"] * scale * SMOKE_REGRESSION_GATE
            ok = res["ops_per_s"] >= target
            print(f"# smoke vs recorded current (calibrated): "
                  f"{res['ops_per_s'] / (ref['ops_per_s'] * scale):.2f}x "
                  f"(gate {SMOKE_REGRESSION_GATE:.2f}x) -> "
                  f"{'OK' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"hot path regressed >30% vs recorded current: "
                    f"{res['ops_per_s']:.0f} < {target:.0f} op/s")
        else:
            print("# no recorded current numbers; gate skipped")
    if failures:
        raise RuntimeError("; ".join(failures))


if __name__ == "__main__":
    main()
