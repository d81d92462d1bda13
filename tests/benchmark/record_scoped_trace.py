#!/usr/bin/env python3
"""Record the scoped trace that ``test_scopes.py`` reads, on a TPU.

    python3 tests/benchmark/record_scoped_trace.py <out.xplane.pb>

It also writes the compiled program's HLO text beside it (``.hlo.txt``),
whose ``op_name`` metadata names each operation's scopes.

Three steps of a jitted program named with the decoder's scopes, under
the benchmark's host spans (``bench.window`` around them, ``bench.decode``
and ``bench.fetch`` in each step): a two-layer scan (``layers``) over
stacked weights and a stacked cache, whose body runs the program's Pallas
flash kernel (``attn_core``), writes one entry into its layer's cache
(``kv_write``), and multiplies by its layer's weights (``mlp``).
"""

import os
import shutil
import sys
import tempfile
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


def step(q, k, v, w, cache, pos):
    def body(h, xs):
        wl, cl = xs
        with jax.named_scope("attn_core"):   # q moves with h: not hoisted
            o = flash_attention_pallas(q * h[0, 0].astype(q.dtype), k, v,
                                       causal=True)
        with jax.named_scope("kv_write"):
            cl = jax.lax.dynamic_update_slice_in_dim(
                cl, o[:, :1, :cl.shape[2]], pos, axis=1)
        with jax.named_scope("mlp"):
            h = jnp.tanh(h @ wl)
        return h, cl

    with jax.named_scope("layers"):
        h, cache = jax.lax.scan(body, w[0, :8], (w, cache))
    return h[0, :4], cache


def main() -> None:
    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (1, 512, 8, 128), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, 512, 2, 128), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, 512, 2, 128), jnp.bfloat16)
    w = jax.random.normal(keys[3], (2, 2048, 2048), jnp.bfloat16) / 45.0
    cache = jax.random.normal(keys[4], (2, 1, 4096, 2, 128), jnp.bfloat16)
    pos = jnp.int32(700)
    fn = jax.jit(step)
    jax.block_until_ready(fn(q, k, v, w, cache, pos))
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.decode"):
                x, _ = fn(q, k, v, w, cache, pos)
            with TraceAnnotation("bench.fetch"):
                np.asarray(x)
    jax.profiler.stop_trace()
    shutil.copy(tr.find_xplane(log_dir), out)
    shutil.rmtree(log_dir)
    hlo = out.replace(".xplane.pb", ".hlo.txt")
    with open(hlo, "w") as f:
        f.write(fn.lower(q, k, v, w, cache, pos).compile().as_text())
    print(f"wrote {out}: {os.path.getsize(out)} bytes, and {hlo}")


if __name__ == "__main__":
    main()
