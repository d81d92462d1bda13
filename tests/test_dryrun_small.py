"""Dry-run machinery on a small 8-device mesh (subprocess: jax device count
is locked at first init, so the 8-device world must be a fresh process).

Validates the full lower->compile->analyze path for one train, one decode,
and one MoE cell on a (2, 4) mesh — the same code path the 512-device
production dry-run uses.
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import dataclasses
from repro.configs import get_config, reduced_config, ShapeConfig
from repro.distributed import sharding as sh
from repro.launch.dryrun import collective_bytes
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.optim import AdamWState
from repro.train.loop import TrainConfig, abstract_init, make_train_fn
from repro.serve.engine import make_serve_fns

mesh = make_mesh((2, 4), ("data", "model"))
out = {}

for arch in ("tinyllama_1p1b", "granite_moe_3b_a800m"):
    cfg = reduced_config(get_config(arch))
    api = build_model(cfg)
    shape = ShapeConfig("t", "train", 64, 8)
    specs = api.input_specs(shape)
    pshapes, axes = abstract_init(api)
    tcfg = TrainConfig()
    step = make_train_fn(api, tcfg)
    pspecs = sh.sanitize_tree(sh.param_specs(axes, mesh, cfg), pshapes, mesh)
    opt_specs = AdamWState(P(), pspecs, pspecs)
    bspecs = {k: P(("data",), None) for k in specs}
    ns = lambda t: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt_shapes = AdamWState(jax.ShapeDtypeStruct((), jnp.int32),
                            jax.tree_util.tree_map(f32, pshapes),
                            jax.tree_util.tree_map(f32, pshapes))
    with mesh, sh.activation_sharding_scope(mesh):
        fn = jax.jit(step, in_shardings=(ns(pspecs), ns(opt_specs), None,
                                         ns(bspecs), NamedSharding(mesh, P())),
                     out_shardings=(ns(pspecs), ns(opt_specs), None,
                                    ns({"loss": P(), "grad_norm": P(),
                                        "lr": P()})))
        lowered = fn.lower(pshapes, opt_shapes, None, specs,
                           jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    out[arch] = {
        "flops": float(cost.get("flops", 0)),
        "collective_bytes": sum(v for k, v in coll.items()
                                if not k.startswith("n_")),
    }

# decode path
cfg = reduced_config(get_config("tinyllama_1p1b"))
api = build_model(cfg)
shape = ShapeConfig("d", "decode", 64, 8)
specs = api.input_specs(shape)
pshapes, axes = abstract_init(api)
with mesh, sh.activation_sharding_scope(mesh, "decode"):
    _, decode_jit = make_serve_fns(api, mesh, axes, shape, pshapes)
    fn = decode_jit(specs["cache"])
    compiled = fn.lower(pshapes, specs["cache"], specs["kv_len"],
                        specs["token"]).compile()
out["decode_ok"] = True
print(json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_on_8_device_mesh():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["decode_ok"]
    for arch in ("tinyllama_1p1b", "granite_moe_3b_a800m"):
        assert out[arch]["flops"] > 0
        assert out[arch]["collective_bytes"] > 0  # sharded: collectives exist


COMPRESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, re
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import dataclasses
from repro.configs import get_config, reduced_config
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.optim import AdamWState, adamw_init
from repro.optim.compression import CompressionState
from repro.train.loop import (TrainConfig, abstract_init,
                              make_compressed_pod_train_fn,
                              init_pod_compression)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = dataclasses.replace(reduced_config(get_config("tinyllama_1p1b")),
                          num_layers=2, vocab_size=256)
api = build_model(cfg)
params, axes = api.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
comp = init_pod_compression(params, 2)
step = make_compressed_pod_train_fn(api, TrainConfig(peak_lr=1e-3,
                                                     warmup_steps=1,
                                                     total_steps=10), mesh)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32)}
with mesh, sh.activation_sharding_scope(mesh):
    fn = jax.jit(step)
    losses = []
    for i in range(6):
        params, opt, comp, metrics = fn(params, opt, comp, batch,
                                        jnp.asarray(i, jnp.int32))
        losses.append(float(metrics["loss"]))
# int8 wire check on the lowered HLO
with mesh, sh.activation_sharding_scope(mesh):
    hlo = fn.lower(params, opt, comp, batch,
                   jnp.asarray(0, jnp.int32)).compile().as_text()
n_s8 = len(re.findall(r"s8\[[\d,]+\][^=]*all-gather", hlo))
print(json.dumps({"losses": losses, "s8_allgathers": n_s8}))
"""


@pytest.mark.slow
def test_compressed_pod_grads_trains_and_uses_int8_wire():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    proc = subprocess.run([sys.executable, "-c", COMPRESS_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    losses = out["losses"]
    assert all(l == l for l in losses)          # finite
    assert losses[-1] < losses[0]               # memorizing the fixed batch
    assert out["s8_allgathers"] > 0             # int8 actually on the wire
