"""Measured tail latency: per-request completion-tick distributions.

The paper's headline claim is LATENCY — offloaded reads complete in 780 us
vs 11 ms on the host path (§8, Figs 14a/15a) — yet the other executable
gates (hotpath/writepath/scaleout) measure only throughput.  This benchmark
measures latency the only way a cooperative simulator can do reproducibly:
in deterministic TICKS of the cluster scheduling clock (one tick per
``DDSCluster.pump``; see ``repro.core.lifecycle`` and README "Measured tail
latency").

The workload is OPEN-LOOP (fixed arrivals per tick, not closed-loop): every
tick, a fixed number of offloaded GETs and host-path writes are issued into
an 8-shard cluster whose devices have a bounded per-poll completion budget.
Writes arrive with periodic bursts — the §8.1 disaggregation scenario where
host-path write runs contend with latency-critical reads for the device.
The driver stamps each request at issue and at response drain, entirely at
the client, so THE SAME measurement runs against any tree (pre- and
post-overhaul); tick histograms are exact integers and two same-seed runs
are byte-identical (gated).

What the pre-PR tree shows: GETs queue FIFO behind write bursts at the
device, so GET p99 rides the write backlog.  Post-overhaul, offloaded reads
ride the device PRIORITY queue (with a bounded write-interleave share),
write coalescing/delivery flush on tick budgets, and the pump drains in
bounded slices — GET p99 collapses to the no-contention floor while writes
stay within their starvation bound.

Gates (all tick comparisons are machine-independent):

  * full: measured offloaded-GET p99 must be >= ``GET_P99_GATE`` (2.0x)
    LOWER than the committed pre-PR baseline; latency must not be bought
    with throughput — requests served per scheduling tick must stay
    >= 0.9x baseline (deterministic) with calibrated wall-clock ops/sec
    above a noise-floor backstop (see the gate constants); and all
    same-seed runs must produce IDENTICAL histograms;
  * --smoke (CI): fails when GET p99 regresses >30% vs the committed
    ``current`` ticks, or determinism breaks.

Results go to ``BENCH_latency.json`` (baseline recorded with
``--record-baseline`` on the pre-PR tree; current with ``--record-current``).
"""

from __future__ import annotations

import gc
import json
import os
import random
import struct
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import emit, section  # noqa: E402
from repro.core.client import ClusterClient  # noqa: E402
from repro.core.dds_server import ServerConfig  # noqa: E402
from repro.distributed.cluster import DDSCluster  # noqa: E402

JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_latency.json")

GET_P99_GATE = 2.0        # offloaded-GET p99 must drop >= 2x vs baseline
# Throughput must not pay for latency.  The HARD 0.9x criterion is gated in
# the deterministic tick domain (requests served per scheduling tick —
# exact, machine-independent: more ticks per request would mean the
# scheduler's service rate was sacrificed).  Wall-clock calibrated ops/sec
# is ALSO gated, but at a noise floor: paired same-window runs measure the
# overhaul at 0.91-0.97x, while the cross-recording measurement error on
# shared/throttled machines is +-25% even after calibration — a hard 0.9x
# wall gate would be a coin flip, so it backstops gross regressions only.
OPS_PER_TICK_GATE = 0.9
OPS_WALL_FLOOR = 0.6
SMOKE_P99_REGRESSION = 1.3  # CI: fail when GET p99 grows >30% vs current

CONFIGS = {
    "full": dict(shards=8, clients=2, read_files=64, write_files=8,
                 ticks=256, warmup=32, reads_per_tick=48, steady_writes=16,
                 burst_writes=512, burst_every=8, read_size=256,
                 write_size=256, queue_depth=16, seed=7),
    "smoke": dict(shards=4, clients=2, read_files=32, write_files=4,
                  ticks=96, warmup=16, reads_per_tick=24, steady_writes=8,
                  burst_writes=256, burst_every=8, read_size=256,
                  write_size=256, queue_depth=16, seed=7),
}


def calibrate(iters: int = 200_000) -> float:
    """Reference ops/sec of a fixed pure-Python loop (machine-speed proxy)."""
    pack = struct.Struct("<QII").pack
    blob = bytes(range(256)) * 8
    t0 = time.perf_counter()
    d: dict[int, bytes] = {}
    for i in range(iters):
        d[i & 1023] = blob[i & 255 : (i & 255) + 64]
        pack(i, i & 0xFFFF, 64)
    return iters / (time.perf_counter() - t0)


def percentile(hist: dict[int, int], p: float) -> int:
    """Exact percentile of an integer-delta histogram."""
    n = sum(hist.values())
    if not n:
        return 0
    need = -(-n * p // 100)
    cum = 0
    d = 0
    for d in sorted(hist):
        cum += hist[d]
        if cum >= need:
            return d
    return d


def hist_doc(hist: dict[int, int]) -> dict:
    """JSON-stable exact histogram + summary."""
    return {
        "counts": {str(d): hist[d] for d in sorted(hist)},
        "count": sum(hist.values()),
        "p50": percentile(hist, 50),
        "p95": percentile(hist, 95),
        "p99": percentile(hist, 99),
        "max": max(hist) if hist else 0,
    }


def run_workload(cfg: dict) -> dict:
    """Open-loop mixed GET/write drive; returns tick histograms + rates."""
    cluster = DDSCluster(num_shards=cfg["shards"],
                         config=ServerConfig(device_capacity=1 << 26,
                                             cache_items=1 << 11))
    for srv in cluster.servers:
        # Bounded per-poll completion budget: the device services a finite
        # number of ops per scheduling step, so queueing is observable in
        # ticks.  Set directly (works against pre-overhaul trees too).
        srv.device.queue_depth = cfg["queue_depth"]
    span = 1 << 16
    read_files = [cluster.create_file(f"lat-r{i}")
                  for i in range(cfg["read_files"])]
    write_files = [cluster.create_file(f"lat-w{i}")
                   for i in range(cfg["write_files"])]
    for i, f in enumerate(read_files):
        cluster.write_sync(f, 0, bytes([i & 0xFF]) * span)
    for f in write_files:
        cluster.write_sync(f, 0, b"\x00" * span)
    # FIXED ports: run-to-run identical flows => identical histograms.
    clients = [ClusterClient(cluster, port=46000 + 100 * i)
               for i in range(cfg["clients"])]
    rng = random.Random(cfg["seed"])
    rsize, wsize = cfg["read_size"], cfg["write_size"]
    payload = b"w" * wsize
    # Keyed by (client, rid): each client has its OWN rid space.
    issued: dict[tuple, tuple[int, str]] = {}
    hist = {"get": {}, "write": {}}
    tick = 0
    n_reads = n_writes = 0

    def harvest(ci, cli) -> None:
        resp = cli.responses
        while resp:
            rid, (status, _body) = resp.popitem()
            assert status == 0, f"request {rid} failed with status {status}"
            ent = issued.pop((ci, rid), None)
            if ent is None:
                continue
            t_iss, cls = ent
            if t_iss >= 0:             # warmup requests carry -1: untimed
                h = hist[cls]
                d = tick - t_iss
                h[d] = h.get(d, 0) + 1

    total_ticks = cfg["warmup"] + cfg["ticks"]
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    for t in range(total_ticks):
        rec = t >= cfg["warmup"]
        stamp = tick if rec else -1
        reads = [(read_files[rng.randrange(len(read_files))],
                  rng.randrange(0, span - rsize), rsize)
                 for _ in range(cfg["reads_per_tick"])]
        wn = cfg["steady_writes"] + (cfg["burst_writes"]
                                     if t % cfg["burst_every"] == 0 else 0)
        # 1 KiB-strided offsets: consecutive writes land non-adjacent, so
        # runs do not coalesce away — the device sees one op per write.
        writes = [(write_files[rng.randrange(len(write_files))],
                   (rng.randrange(0, (span - wsize) // 1024) * 1024 + 512)
                   % (span - wsize), payload)
                  for _ in range(wn)]
        # Contiguous per-client chunks (the last client takes the tail) —
        # generalizes to any client count without reshuffling the 2-client
        # split the committed baselines were recorded with.
        nc = len(clients)
        chunk_r, chunk_w = len(reads) // nc, len(writes) // nc
        for ci, cli in enumerate(clients):
            r_end = (ci + 1) * chunk_r if ci < nc - 1 else len(reads)
            w_end = (ci + 1) * chunk_w if ci < nc - 1 else len(writes)
            rr = reads[ci * chunk_r : r_end]
            ww = writes[ci * chunk_w : w_end]
            for rid in cli.submit([("r",) + r for r in rr]):
                issued[(ci, rid)] = (stamp, "get")
            for rid in cli.submit([("w",) + w for w in ww]):
                issued[(ci, rid)] = (stamp, "write")
            if rec:
                n_reads += len(rr)
                n_writes += len(ww)
            cli.flush()
        cluster.pump()      # ONE scheduling step == one tick (open loop)
        tick += 1
        for ci, cli in enumerate(clients):
            cli.poll()
            harvest(ci, cli)
    # Drain: arrivals stop; keep ticking until every request is answered.
    for _ in range(200_000):
        if not issued:
            break
        work = cluster.pump()
        tick += 1
        for ci, cli in enumerate(clients):
            cli.poll()
            harvest(ci, cli)
        if work == 0:
            for srv in cluster.servers:
                srv.device.drain()
    elapsed = time.perf_counter() - t0
    gc.enable()
    assert not issued, f"{len(issued)} requests never completed"

    total = n_reads + n_writes
    offloaded = sum(s.offload.stats.completed for s in cluster.servers)
    bounced = sum(s.offload.stats.bounced_to_host for s in cluster.servers)
    # Every GET must be DPU-served, or "GET" ticks would mix serving paths.
    assert bounced == 0, f"{bounced} reads bounced to host; retune workload"
    got_gets = sum(hist["get"].values())
    assert got_gets == n_reads, f"harvested {got_gets}/{n_reads} GETs"
    res = {
        "requests": total,
        "reads": n_reads,
        "writes": n_writes,
        "ticks": tick,
        "wall_s": elapsed,
        "ops_per_s": total / elapsed,
        "get": hist_doc(hist["get"]),
        "write": hist_doc(hist["write"]),
    }
    # Post-overhaul trees also expose server-side lifecycle histograms;
    # cross-check the counts (the distributions measure different segments:
    # ingress->publish vs issue->drain).
    if hasattr(cluster, "latency_stats"):
        stats = cluster.latency_stats()
        dpu = stats.get("classes", {}).get("dpu_read", {})
        assert dpu.get("count", 0) >= n_reads, \
            f"server-side dpu_read count {dpu} < driver reads {n_reads}"
        res["server"] = stats
    return res


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

    section(f"tail latency ({mode}: {cfg['shards']} shards, open-loop "
            f"{cfg['reads_per_tick']} GET/tick + {cfg['steady_writes']}"
            f"+{cfg['burst_writes']}/{cfg['burst_every']} writes/tick, "
            f"{cfg['ticks']} ticks)")
    # Same-seed reps: determinism gate AND noise reduction.  Tick
    # histograms must be identical across reps; for wall-clock, each rep's
    # ops/sec is paired with the MEAN of its two surrounding calibrations
    # (the best local estimate of machine speed during that rep — shared
    # machines throttle in phases, so a global calibration is unfair) and
    # the best normalized rep is gated.
    reps = []
    calib = 0.0
    for _ in range(2 if smoke else 3):
        c1 = calibrate()
        r = run_workload(cfg)
        c2 = calibrate()
        calib = max(calib, c1, c2)
        r["ops_norm"] = r["ops_per_s"] / ((c1 + c2) / 2)
        reps.append(r)
    identical = all(r["get"]["counts"] == reps[0]["get"]["counts"]
                    and r["write"]["counts"] == reps[0]["write"]["counts"]
                    for r in reps[1:])
    res = max(reps, key=lambda r: r["ops_norm"])
    g, w = res["get"], res["write"]
    emit(f"latency_{mode}", 1e6 / res["ops_per_s"],
         f"get_p50={g['p50']}t get_p99={g['p99']}t write_p99={w['p99']}t "
         f"tput={res['ops_per_s']:.0f}op/s deterministic={identical}")

    doc = load_json()
    doc["configs"] = CONFIGS
    res = {k: v for k, v in res.items() if k != "server"}
    res["config"] = cfg
    res["deterministic"] = identical
    entry = {"calibration_ops_per_s": calib, mode: res}
    if record:
        doc.setdefault(record, {})["calibration_ops_per_s"] = calib
        doc[record][mode] = res
        print(f"# recorded {mode} measurement into '{record}'")
    doc["last_run"] = {"mode": mode, **entry}
    base, cur = doc.get("baseline", {}), doc.get("current", {})
    if base.get("full") and cur.get("full"):
        doc["get_p99_improvement"] = round(
            base["full"]["get"]["p99"] / max(cur["full"]["get"]["p99"], 1), 3)
    save_json(doc)

    def gate_ref(section_doc: dict, which: str):
        ref = section_doc.get(which)
        if ref and ref.get("config") != cfg:
            print(f"# recorded {which} numbers used a different workload "
                  f"config; gate skipped — re-record with the new config")
            return None
        return ref

    failures = []
    if not identical:
        failures.append("two same-seed runs produced different histograms "
                        "(determinism gate)")
    if not smoke and not record:
        ref = gate_ref(base, "full")
        if ref:
            base_p99 = ref["get"]["p99"]
            cur_p99 = max(g["p99"], 1)
            ratio = base_p99 / cur_p99
            ok = ratio >= GET_P99_GATE
            print(f"# offloaded-GET p99: {base_p99} -> {g['p99']} ticks "
                  f"({ratio:.2f}x lower; gate {GET_P99_GATE:.1f}x) -> "
                  f"{'OK' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"GET p99 not {GET_P99_GATE}x lower than baseline: "
                    f"{g['p99']} vs {base_p99} ticks")
            # Deterministic throughput criterion: requests per scheduling
            # tick (exact on both sides — no calibration involved).
            rpt_base = ref["requests"] / ref["ticks"]
            rpt_cur = res["requests"] / res["ticks"]
            ratio_rpt = rpt_cur / rpt_base
            rpt_ok = ratio_rpt >= OPS_PER_TICK_GATE
            print(f"# requests/tick vs baseline (deterministic): "
                  f"{rpt_cur:.1f} vs {rpt_base:.1f} ({ratio_rpt:.2f}x; "
                  f"gate {OPS_PER_TICK_GATE:.2f}x) -> "
                  f"{'OK' if rpt_ok else 'FAIL'}")
            if not rpt_ok:
                failures.append(
                    f"latency must not be bought with throughput: "
                    f"{ratio_rpt:.2f}x < {OPS_PER_TICK_GATE:.2f}x "
                    f"requests/tick vs baseline")
            # Wall-clock backstop (noise floor; see OPS_WALL_FLOOR note).
            ratio_ops = res["ops_norm"] / ref["ops_norm"]
            ops_ok = ratio_ops >= OPS_WALL_FLOOR
            print(f"# ops/sec vs baseline (calibrated wall-clock, "
                  f"noise-floor backstop): {ratio_ops:.2f}x "
                  f"(floor {OPS_WALL_FLOOR:.2f}x) -> "
                  f"{'OK' if ops_ok else 'FAIL'}")
            if not ops_ok:
                failures.append(
                    f"wall-clock collapsed: {ratio_ops:.2f}x < "
                    f"{OPS_WALL_FLOOR:.2f}x calibrated ops/sec vs baseline")
        else:
            print("# no recorded baseline; gate skipped")
    if smoke and not record:
        ref = gate_ref(cur, "smoke")
        if ref:
            limit = ref["get"]["p99"] * SMOKE_P99_REGRESSION
            ok = g["p99"] <= limit
            print(f"# smoke GET p99 vs recorded current: {g['p99']} vs "
                  f"{ref['get']['p99']} ticks (limit {limit:.1f}) -> "
                  f"{'OK' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"GET p99 regressed >30% vs recorded current: "
                    f"{g['p99']} > {limit:.1f} ticks")
        else:
            print("# no recorded current numbers; gate skipped")
    if failures:
        raise RuntimeError("; ".join(failures))


if __name__ == "__main__":
    main()
