#!/usr/bin/env python3
"""Read the two numbers a cell's limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,3 [--control]

In one process, so the cell compiles once: for each seed, weights and
prompts from that seed, the batches that a run's sample draws from served
at the cell's load to their last token, and as many requests compared with
the plain reference as a run compares.  It prints, per seed, the widest gap of
a served token below the reference's best (the program's reading) and,
with ``--control``, that of the token which the reference computed in fp8
puts first at the same positions (the control's reading); beside each, the
mean of the gaps.  The last line gives, per statistic, the largest
program reading and the smallest control reading.  The limit in
``cells/<name>.json`` lies between the largest program reading over a dozen
seeds or more and the smallest control reading.  The benchmark's own runs
never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.traffic import ClosedBatches  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        cell = harness.load_cell(ROOT, args.workload)
        harness.import_program(ROOT)
        harness.enable_compile_cache()
        devices, _, _ = harness.devices_for(
            cell, ROOT / harness.BENCH / "peaks.json")
        sess = harness.Session(cell, devices)
    except harness.BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    readings = []
    for seed in seeds:
        t = time.perf_counter()
        params = sess.weights(seed)
        mix = ClosedBatches(cell.mix, slots=sess.slots,
                            vocab=cell.config["vocab_size"], seed=seed)
        n = cell.sizes["sample_requests"]
        batches, _, _ = harness.serve_window(
            sess, params, mix, 0.0, harness.batches_for(n, sess.slots))
        prompts, tokens = harness.sample(batches, mix.gen_len, n, seed)
        gaps = harness.compare(cell, params, prompts, tokens,
                               control=args.control)
        del params
        line = {"workload": args.workload, "seed": seed,
                "compared_tokens": int(tokens.size)}
        for side, g in gaps.items():
            for name, value in harness.gap_stats(g).items():
                line[f"{side}.{name}"] = value
        line["seconds"] = round(time.perf_counter() - t, 1)
        readings.append(line)
        print(json.dumps(line), flush=True)
    summary = {"workload": args.workload, "seeds": len(seeds)}
    for name in harness.GAP_STATS:
        summary[f"lower.{name}"] = max(r[f"served.{name}"] for r in readings)
        if args.control:
            summary[f"upper.{name}"] = min(r[f"control.{name}"]
                                           for r in readings)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
