"""The expert layer under a (2, 4) mesh of 8 host devices equals the layer
on one device: its output, ``aux_loss`` and ``dropped_frac``, dropless and
with a capacity factor.  Under a mesh ``moe_fwd`` runs shard by shard
(``sharding.per_shard_experts``): each data shard routes and sorts its own
rows, each model shard computes a slice of d_ff, and the partial outputs
are summed over ``model``.

A subprocess, since JAX's device count is fixed when it first starts.
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, json
import jax, jax.numpy as jnp, numpy as np

from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import moe

D, F, E, K, S = 64, 32, 8, 2, 16
mesh = make_mesh((2, 4), ("data", "model"))
params, _ = moe.init_moe(jax.random.PRNGKey(0), D, F, E, K)
# unit-scale router logits: no near-tie in any token's top k
params["router"] = (jax.random.normal(jax.random.PRNGKey(1), (D, E))
                    * D ** -0.5).astype(jnp.bfloat16)
out = {}
for name, cf, B, mode in [("dropless", None, 4, "train"),
                          ("capacity", 1.0, 4, "train"),
                          ("decode", None, 4, "decode"),
                          ("batch_not_split", 1.0, 3, "train")]:
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, D), jnp.bfloat16)
    f = jax.jit(functools.partial(moe.moe_fwd, num_experts=E, top_k=K,
                                  capacity_factor=cf))
    y1, a1 = f(params, x)
    with mesh, sh.activation_sharding_scope(mesh, mode):
        y8, a8 = f(params, x)
    y1, y8 = np.asarray(y1, np.float32), np.asarray(y8, np.float32)
    out[name] = {"max_abs_diff": float(np.abs(y8 - y1).max()),
                 "max_abs": float(np.abs(y1).max()),
                 "aux_loss": [float(a1["aux_loss"]), float(a8["aux_loss"])],
                 "dropped_frac": [float(a1["dropped_frac"]),
                                  float(a8["dropped_frac"])]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["dropless", "capacity", "decode",
                                  "batch_not_split"])
def test_sharded_expert_layer_equals_one_device(readings, case):
    r = readings[case]
    # Each of the 4 model shards rounds its partial output (a quarter of
    # d_ff) to bfloat16 before the sum, where one device rounds once: a few
    # bfloat16 steps (2**-8 relative) of the output's scale apart.
    assert r["max_abs_diff"] <= 4 * 2 ** -8 * r["max_abs"]
    one, many = r["aux_loss"]
    assert many == pytest.approx(one, rel=1e-5)
    one, many = r["dropped_frac"]
    assert many == pytest.approx(one, abs=1e-7)
    if case in ("capacity", "batch_not_split"):
        assert one > 0            # the capacity factor drops assignments
    else:
        assert one == 0.0
