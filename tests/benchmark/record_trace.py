#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` reads, on a TPU.

    python3 tests/benchmark/record_trace.py <out.xplane.pb>

It also writes the compiled program's HLO text beside it (``.hlo.txt``),
from which the kernel's operations are found by their Mosaic name.

Three steps of a jitted program that runs the program's Pallas flash
kernel and a matmul, under the benchmark's host spans (``bench.window``
around them, ``bench.decode`` and ``bench.fetch`` in each step), with a
sleep inside one ``bench.fetch`` so the device has one long idle gap.
"""

import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from benchmarks.chip import trace as tr  # noqa: E402
from repro.kernels.flash_attention.kernel import flash_attention_pallas  # noqa: E402


def step(q, k, v, w):
    o = flash_attention_pallas(q, k, v, causal=True)
    return o, jnp.tanh(w @ w)[0, :4]


def main() -> None:
    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, 512, 8, 128), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, 512, 2, 128), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, 512, 2, 128), jnp.bfloat16)
    w = jax.random.normal(keys[3], (2048, 2048), jnp.bfloat16)
    fn = jax.jit(step)
    jax.block_until_ready(fn(q, k, v, w))
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for i in range(3):
            with TraceAnnotation("bench.decode"):
                o, x = fn(q, k, v, w)
            with TraceAnnotation("bench.fetch"):
                np.asarray(x)
                if i == 1:
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(tr.find_xplane(log_dir), out)
    shutil.rmtree(log_dir)
    hlo = out.replace(".xplane.pb", ".hlo.txt")
    with open(hlo, "w") as f:
        f.write(fn.lower(q, k, v, w).compile().as_text())
    print(f"wrote {out}: {os.path.getsize(out)} bytes, and {hlo}")


if __name__ == "__main__":
    main()
