"""Benchmark entry point: one module per paper table/figure.

``python -m benchmarks.run``            everything (measured + model)
``python -m benchmarks.run fig17``      one module
``python -m benchmarks.run --smoke``    CI nightly gate (modules that
                                        support it run reduced sizes)

Output rows: ``name,us_per_call,derived``.
"""

from __future__ import annotations

import os
import sys
import traceback

# Allow direct invocation (`python benchmarks/run.py`) in addition to
# `python -m benchmarks.run`: put the repo root and src/ on the path.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks import (compare, fig14_16_model, fig17_rings,
                        fig18_23_zerocopy, fig22_cache_table,
                        fig24_26_integration, fig_chaos,
                        fig_cluster_scaling, fig_failover, fig_getstorm,
                        fig_hotpath, fig_latency, fig_reshard,
                        fig_scaleout, fig_tenancy, fig_writepath,
                        kernels_bench)

MODULES = {
    "cluster": fig_cluster_scaling,
    "hotpath": fig_hotpath,
    "writepath": fig_writepath,
    "scaleout": fig_scaleout,
    "latency": fig_latency,
    "tenancy": fig_tenancy,
    "failover": fig_failover,
    "getstorm": fig_getstorm,
    "chaos": fig_chaos,
    "reshard": fig_reshard,
    "fig14_16": fig14_16_model,
    "fig17": fig17_rings,
    "fig18_23": fig18_23_zerocopy,
    "fig22": fig22_cache_table,
    "fig24_26": fig24_26_integration,
    "kernels": kernels_bench,
    "compare": compare,
}


def main() -> None:
    args = sys.argv[1:]
    if "--smoke" in args:
        # Size reduction is opt-in per module: modules that support it (so
        # far: cluster) read DDS_BENCH_SMOKE; the rest run at full size.
        os.environ["DDS_BENCH_SMOKE"] = "1"
        args = [a for a in args if a != "--smoke"]
    wanted = args or list(MODULES)
    failures = 0
    for name in wanted:
        mod = MODULES.get(name)
        if mod is None:
            print(f"# unknown benchmark {name}; choices: {list(MODULES)}")
            failures += 1
            continue
        try:
            mod.main()
        except Exception:
            failures += 1
            print(f"# BENCHMARK {name} FAILED")
            traceback.print_exc()
    if failures:
        raise SystemExit(failures)


if __name__ == "__main__":
    main()
