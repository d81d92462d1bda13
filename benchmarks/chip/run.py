#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for.  With ``--trace 0`` the result's metrics are the cell's
end-to-end metrics; with ``--trace 1`` the batches that the comparison's
sample needs are served to their last token under the profiler (the
traced window) and the metrics are the cell's per-layer ones, read from
the trace.  Both runs
compare served tokens with the plain reference and print each compared
number beside its limit: as the last lines on standard error, and under
``checks``, the last key of the result.  The result is the last line on
standard output.  Without a TPU, or on a device kind the peaks table does
not know, the run prints no result and exits 2.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        harness.import_program(ROOT)
        harness.enable_compile_cache()
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), T0)
    except harness.BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
