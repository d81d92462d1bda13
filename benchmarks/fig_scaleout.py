"""Cluster-scale scheduler ops/sec: 16 shards under Zipfian-skewed KV load.

PR 2/3 made one server's read and write paths O(1) per request; this
benchmark holds the CLUSTER layer to the same standard.  The pre-overhaul
run loop polled every shard on every iteration (``DDSCluster.pump`` stepped
all N servers; ``run_until_idle`` swept them three more times to detect
quiescence), so wall-clock cost per op grew with shard count even when most
shards were idle — the opposite of scale-out economics.  The work-signaled
ready-set scheduler makes a scheduling round cost track *active* work.

Two measurements, both on the §9.2 sharded KV store:

  * **zipf** — a 16-shard cluster under a Zipfian-skewed mixed workload:
    two clients run closed-loop READ-MODIFY-WRITE rounds against a fixed
    hot key set with Zipf(a)-distributed ranks (a handful of shards own
    nearly all the heat): a burst of GETs settles (``run_until_idle``),
    then overwrite-PUTs conditioned on those reads settle, plus a slow
    fresh-PUT/DEL churn stream.  Each round has several settle points —
    the bursty, dependency-chained pattern where dispatch-loop overhead
    dominates and which no other benchmark covers (``fig_hotpath``/
    ``fig_writepath`` drive saturated open-loop pipelines).  GETs touch
    only warmed keys, so every GET is DPU-served and the modeled us/req
    is fully deterministic.
  * **idle-cost** — the same round shape with ALL keys placed on one shard,
    run against a 16-shard and a 1-shard cluster: the calibrated ops/sec
    ratio is the price of fifteen idle shards (the pre-overhaul loop paid
    ~16x pump overhead here; the ready set must keep it near parity).

Results go to ``BENCH_scaleout.json``.  Calibration, JSON layout
(``baseline``/``current``/``last_run``) and the gates mirror
``fig_writepath``:

  * full mode asserts >= ``FULL_SPEEDUP_GATE`` (2.0x) calibrated ops/sec
    over the recorded pre-overhaul baseline, with modeled us/req within
    ``MODELED_DRIFT`` (5%) of it — the simulator got faster, the physics
    did not move;
  * full mode also asserts the idle-cost criterion: single-shard traffic
    on the 16-shard cluster reaches >= ``IDLE_PARITY_GATE`` (70%) of the
    1-shard cluster's rate;
  * ``--smoke`` (CI fast lane) runs a reduced config and fails on a >30%
    calibrated regression vs the recorded ``current`` numbers.

The driver is version-agnostic (burst client APIs are used when present,
per-op calls otherwise), so ``--record-baseline`` runs unmodified against
the pre-overhaul tree.
"""

from __future__ import annotations

import gc
import json
import os
import struct
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import emit, section  # noqa: E402
from repro.apps.kv_store import KVClient, ShardedKVStore  # noqa: E402
from repro.core.dds_server import ServerConfig  # noqa: E402
from repro.distributed.cluster import HashRing  # noqa: E402

JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_scaleout.json")

FULL_SPEEDUP_GATE = 2.0       # acceptance: scheduler >= 2x the pre-PR loop
SMOKE_REGRESSION_GATE = 0.70  # CI: fail below 70% of recorded current
MODELED_DRIFT = 0.05          # modeled us/req must stay within 5%
IDLE_PARITY_GATE = 0.70       # 1-of-16-shard traffic >= 70% of 1-shard rate

CONFIGS = {
    "full": dict(shards=16, clients=2, hot_keys=48, zipf_a=3.0, rounds=120,
                 gets=2, overwrites=1, churn_every=4, value_size=64,
                 idle_rounds=120, idle_gets=8, idle_overwrites=2),
    "smoke": dict(shards=16, clients=2, hot_keys=48, zipf_a=3.0, rounds=48,
                  gets=2, overwrites=1, churn_every=4, value_size=64,
                  idle_rounds=0, idle_gets=8, idle_overwrites=2),
}

ZIPF_SEED = 0xD15C0


def calibrate(iters: int = 200_000) -> float:
    """Reference ops/sec of a fixed pure-Python loop (machine-speed proxy).

    Same spirit as ``fig_hotpath``/``fig_writepath``: struct packing, dict
    traffic and bytes slicing — the primitives the scheduler loop leans on.
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


def _issue_gets(cli: KVClient, keys: list) -> None:
    cli.submit([("get", k) for k in keys])


def _issue_puts(cli: KVClient, items: list) -> None:
    cli.submit([("put", k, v) for k, v in items])


def _settle(clients: list) -> None:
    """End-of-round convergence: let every client's run loop go idle."""
    for cli in clients:
        cli.net.run_until_idle()


def _zipf_ranks(cfg: dict, total: int) -> list[int]:
    """The skewed rank sequence, precomputed (untimed) and seeded: the
    exact same key sequence every rep, every run, every machine."""
    rng = np.random.default_rng(ZIPF_SEED)
    return [(int(z) - 1) % cfg["hot_keys"]
            for z in rng.zipf(cfg["zipf_a"], size=total)]


def _warm(store: ShardedKVStore, clients: list, keys: list, value: bytes,
          fresh: list) -> None:
    """Untimed: PUT-ack every hot key (arms the DPU cache) + churn pool."""
    for k in keys:
        clients[0].put(k, value)
    for k in fresh:
        clients[0].put(k, value)
    clients[0].flush()
    _settle(clients)


def run_zipf_workload(cfg: dict) -> dict:
    """Drive the settle-per-round Zipfian workload; return measured rates."""
    store = ShardedKVStore(num_shards=cfg["shards"],
                           config=ServerConfig(device_capacity=1 << 26,
                                               cache_items=1 << 14))
    cluster = store.cluster
    clients = [KVClient(store) for _ in range(cfg["clients"])]
    value = bytes(range(256))[: cfg["value_size"]]
    hot = [b"hot-%04d" % i for i in range(cfg["hot_keys"])]
    fresh = [b"fresh-w%d" % i for i in range(8)]
    _warm(store, clients, hot, value, fresh)

    per_round = cfg["gets"] + cfg["overwrites"]
    ranks = _zipf_ranks(cfg, cfg["rounds"] * cfg["clients"] * per_round)
    rk = iter(ranks)
    total = 0
    gets_total = 0
    dpu_before = store.dpu_served_gets()
    modeled_before = cluster.makespan_s()
    gc.collect()
    gc.disable()   # keep collector pauses out of the timed region
    t0 = time.perf_counter()
    for r in range(cfg["rounds"]):
        # Read phase: every client GETs its Zipf-ranked keys and BLOCKS on
        # the values (closed loop — the writes below depend on them).
        for cli in clients:
            _issue_gets(cli, [hot[next(rk)] for _ in range(cfg["gets"])])
            total += cfg["gets"]
            gets_total += cfg["gets"]
            cli.flush()
        _settle(clients)
        # Modify phase: read-modify-write — overwrite-PUT the hot keys the
        # reads conditioned on, and settle before the next round's reads.
        for cli in clients:
            _issue_puts(cli, [(hot[next(rk)], value)
                              for _ in range(cfg["overwrites"])])
            total += cfg["overwrites"]
            cli.flush()
        if r % cfg["churn_every"] == 0:
            # slow churn stream: one fresh append + one DEL of a key that
            # settled at least a full round ago (host read-for-update,
            # fires invalidate-on-read) — always through client 0
            k = b"fresh-r%d" % r
            clients[0].put(k, value)
            fresh.append(k)
            clients[0].delete(fresh.pop(0))
            total += 2
            clients[0].flush()
        _settle(clients)
    elapsed = time.perf_counter() - t0
    gc.enable()

    dpu_gets = store.dpu_served_gets() - dpu_before
    assert dpu_gets == gets_total, \
        f"GET offload not deterministic: {dpu_gets}/{gets_total} DPU-served"
    modeled_s = cluster.makespan_s() - modeled_before
    return {
        "requests": total,
        "wall_s": elapsed,
        "ops_per_s": total / elapsed,
        "modeled_us_per_req": modeled_s / total * 1e6,
        "dpu_get_frac": dpu_gets / max(gets_total, 1),
    }


def _single_shard_keys(n: int, ring_shards: int) -> list:
    """Keys that the ``ring_shards``-way ring places on shard 0."""
    ring = HashRing(ring_shards)
    keys, i = [], 0
    while len(keys) < n:
        k = b"idle-%d" % i
        if ring.shard_for(k) == 0:
            keys.append(k)
        i += 1
    return keys


def run_idle_workload(cfg: dict, num_shards: int) -> float:
    """Ops/sec with every key on ONE shard of a ``num_shards`` cluster."""
    store = ShardedKVStore(num_shards=num_shards,
                           config=ServerConfig(device_capacity=1 << 26,
                                               cache_items=1 << 14))
    cli = KVClient(store)
    value = bytes(range(256))[: cfg["value_size"]]
    # Placement is ring-stable: keys chosen for shard 0 of the 16-ring all
    # live on the only shard of a 1-shard ring too, so both clusters run
    # the IDENTICAL workload.
    keys = _single_shard_keys(cfg["hot_keys"], cfg["shards"])
    fresh: list = []
    _warm(store, [cli], keys, value, fresh)

    per_round = cfg["idle_gets"] + cfg["idle_overwrites"]
    total = 0
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    for r in range(cfg["idle_rounds"]):
        _issue_gets(cli, [keys[(r + i) % len(keys)]
                          for i in range(cfg["idle_gets"])])
        _issue_puts(cli, [(keys[(r + i) % len(keys)], value)
                          for i in range(cfg["idle_overwrites"])])
        total += per_round
        cli.flush()
        _settle([cli])
    elapsed = time.perf_counter() - t0
    gc.enable()
    return total / elapsed


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

    section(f"scale-out scheduler ({mode}: {cfg['shards']} shards, "
            f"{cfg['clients']} clients, {cfg['rounds']} settle-rounds, "
            f"Zipf a={cfg['zipf_a']} over {cfg['hot_keys']} hot keys)")
    # Noise strategy: identical to fig_writepath — every workload rep is
    # PAIRED with the calibration measured right around it (max of
    # before/after) and the best *normalized* rep wins, which controls for
    # mid-run CPU throttling; the committed number stays an
    # (ops, calibration) pair from one moment in time.
    reps = 2 if smoke else 6
    calib, res = 0.0, None
    c_before = calibrate()
    for _ in range(reps):
        r = run_zipf_workload(cfg)
        c_after = calibrate()
        c = max(c_before, c_after)
        if res is None or r["ops_per_s"] / c > res["ops_per_s"] / calib:
            calib, res = c, r
        c_before = c_after
    emit(f"scaleout_{mode}", 1e6 / res["ops_per_s"],
         f"tput={res['ops_per_s']:.0f}op/s "
         f"modeled={res['modeled_us_per_req']:.2f}us/req "
         f"dpu_gets={res['dpu_get_frac']:.2f}")

    idle_ratio = None
    if cfg["idle_rounds"]:
        # The machine-noise floor swings single measurements by 2x, so the
        # criterion is the MEDIAN of three interleaved (1-shard, 16-shard)
        # ratio pairs — each ratio compares two runs seconds apart, and the
        # median discards a pair that straddled a throttling event.
        ratios = []
        for _ in range(3):
            one = run_idle_workload(cfg, 1)
            wide = run_idle_workload(cfg, cfg["shards"])
            ratios.append(wide / one)
        idle_ratio = sorted(ratios)[1]
        res["idle_parity"] = round(idle_ratio, 3)
        emit("scaleout_idle_parity", idle_ratio,
             f"1-of-{cfg['shards']}-shard traffic at "
             f"{idle_ratio:.2f}x the 1-shard rate "
             f"(median of {[round(r, 2) for r in ratios]})")

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
        b = base["full"]["ops_per_s"] / base["calibration_ops_per_s"]
        c = cur["full"]["ops_per_s"] / cur["calibration_ops_per_s"]
        doc["speedup_full_calibrated"] = round(c / b, 3)
        doc["speedup_full_raw"] = round(cur["full"]["ops_per_s"]
                                        / base["full"]["ops_per_s"], 3)
    save_json(doc)

    def gate_ref(sec: dict, which: str):
        """Recorded numbers are only comparable on the SAME workload."""
        ref = sec.get(which)
        if ref and ref.get("config") != cfg:
            print(f"# recorded {which} numbers used a different workload "
                  f"config; gate skipped — re-record with the new config")
            return None
        return ref

    failures = []

    def check_modeled(ref: dict) -> None:
        """Modeled time is the physics; the scheduler must not move it."""
        b, c = ref["modeled_us_per_req"], res["modeled_us_per_req"]
        if abs(c - b) > MODELED_DRIFT * b:
            failures.append(
                f"modeled us/req drifted: {c:.3f} vs recorded {b:.3f}")

    if not smoke and not record:
        ref = gate_ref(doc.get("baseline", {}), "full")
        if ref:
            scale = calib / doc["baseline"]["calibration_ops_per_s"]
            target = ref["ops_per_s"] * scale * FULL_SPEEDUP_GATE
            ok = res["ops_per_s"] >= target
            print(f"# speedup vs baseline (calibrated): "
                  f"{res['ops_per_s'] / (ref['ops_per_s'] * scale):.2f}x "
                  f"(gate {FULL_SPEEDUP_GATE:.1f}x) -> {'OK' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"scale-out below {FULL_SPEEDUP_GATE}x baseline: "
                    f"{res['ops_per_s']:.0f} < {target:.0f} op/s")
            check_modeled(ref)
        else:
            print("# no recorded baseline; gate skipped")
        if idle_ratio is not None and idle_ratio < IDLE_PARITY_GATE:
            failures.append(
                f"idle-cost criterion failed: single-shard traffic on "
                f"{cfg['shards']} shards at {idle_ratio:.2f}x the 1-shard "
                f"rate (gate {IDLE_PARITY_GATE:.2f}x)")
    if smoke and not record:
        ref = gate_ref(doc.get("current", {}), "smoke")
        if ref:
            scale = calib / doc["current"]["calibration_ops_per_s"]
            target = ref["ops_per_s"] * scale * SMOKE_REGRESSION_GATE
            ok = res["ops_per_s"] >= target
            print(f"# smoke vs recorded current (calibrated): "
                  f"{res['ops_per_s'] / (ref['ops_per_s'] * scale):.2f}x "
                  f"(gate {SMOKE_REGRESSION_GATE:.2f}x) -> "
                  f"{'OK' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"scale-out regressed >30% vs recorded current: "
                    f"{res['ops_per_s']:.0f} < {target:.0f} op/s")
            check_modeled(ref)
        else:
            print("# no recorded current numbers; gate skipped")
    if failures:
        raise RuntimeError("; ".join(failures))


if __name__ == "__main__":
    main()
