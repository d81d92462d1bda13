"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts: unaligned slices,
primitives Mosaic cannot lower, kernels over the VMEM budget, programs over
the device's memory.  These tests run the compiler on the kernels, on the
StarCoder2-7B decode and train steps and on Granite-3.0-3B-A800M's expert
layer and decode at published widths, so such faults show up without chip
time.  Nothing executes, so results are not checked
here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker imports
this file.
"""

import dataclasses
import json
import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ShapeConfig, get_config
from repro.distributed import sharding as sh
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.ssm_scan.kernel import gla_scan_pallas
from repro.launch.mesh import make_mesh
from repro.models import moe
from repro.models import transformer as TF
from repro.models.registry import build_model
from repro.optim import AdamWState
from repro.serve.engine import make_serve_fns
from repro.train.loop import TrainConfig, abstract_init, make_train_fn
from test_decode_in_place import (KEEP, aliases, entry_name, in_place_write,
                                  parse_hlo, while_bodies)

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmarks.chip import counts, moe_scopes  # noqa: E402

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
SC2 = get_config("starcoder2_7b")    # Hq 36, Hkv 4, head dim 128
GRANITE = dataclasses.replace(         # the published multipliers, dropless
    get_config("granite_moe_3b_a800m"), embedding_multiplier=12.0,
    attention_multiplier=0.015625, residual_multiplier=0.22,
    logits_scaling=6.0, capacity_factor=None)


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_dispatch(monkeypatch):
    """The dispatchers pick Pallas only when JAX's backend is a TPU; here
    the backend is the CPU, so steer them for the compile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_fits_with_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_paged_attention_compiles_at_starcoder2_widths(one_chip):
    B, pages_per_seq, page = 8, 32, 128
    q = _sds(one_chip, (B, SC2.num_heads, SC2.hd))
    pool = _sds(one_chip, (B * pages_per_seq, page, SC2.num_kv_heads, SC2.hd))
    table = _sds(one_chip, (B, pages_per_seq), jnp.int32)
    lens = _sds(one_chip, (B,), jnp.int32)
    compiled = jax.jit(paged_attention_pallas).lower(
        q, pool, pool, table, lens).compile()
    _assert_fits_with_kernel(compiled)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_fwd_and_grad_compile_at_starcoder2_widths(
        one_chip, causal):
    S = 2048
    q = _sds(one_chip, (1, S, SC2.num_heads, SC2.hd))
    kv = _sds(one_chip, (1, S, SC2.num_kv_heads, SC2.hd))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal, impl="pallas")

    def loss(q, k, v):  # squared, so the backward needs the kernel's output
        return jnp.sum(jnp.square(fwd(q, k, v).astype(jnp.float32)))

    _assert_fits_with_kernel(jax.jit(fwd).lower(q, kv, kv).compile())
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    _assert_fits_with_kernel(grad.lower(q, kv, kv).compile())


def test_gla_compiles_at_rwkv6_widths(one_chip):
    B, H, S, K = 1, 64, 2048, 64
    x = _sds(one_chip, (B, H, S, K))
    compiled = jax.jit(lambda q, k, v, w: gla_scan_pallas(
        q, k, v, w, chunk=128)).lower(x, x, x, x).compile()
    _assert_fits_with_kernel(compiled)


def test_paged_decode_step_compiles_at_16_layers(one_chip, pallas_dispatch):
    cfg = dataclasses.replace(SC2, num_layers=16)
    api = build_model(cfg)
    pshapes, _ = abstract_init(api)
    page = 128
    cache = jax.eval_shape(lambda: TF.lm_init_paged_cache(
        cfg, batch=8, max_len=4096, page=page))
    del cache["page"]

    def step(params, pools, kv_len, token):
        return TF.lm_decode_step_paged(params, cfg, dict(pools, page=page),
                                       kv_len, token)

    compiled = jax.jit(step).lower(
        _on(one_chip, pshapes), _on(one_chip, cache),
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (8, 1), jnp.int32),
    ).compile()
    _assert_fits_with_kernel(compiled)


def test_train_step_compiles_at_1_layer(one_chip, pallas_dispatch):
    cfg = dataclasses.replace(SC2, num_layers=1)
    api = build_model(cfg)
    pshapes, _ = abstract_init(api)
    f32 = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), pshapes)
    opt = AdamWState(jax.ShapeDtypeStruct((), jnp.int32), f32, f32)
    batch = {"tokens": _sds(one_chip, (1, 2048), jnp.int32),
             "labels": _sds(one_chip, (1, 2048), jnp.int32)}
    step = make_train_fn(api, TrainConfig())
    compiled = jax.jit(step).lower(
        _on(one_chip, pshapes), _on(one_chip, opt), None, batch,
        _sds(one_chip, (), jnp.int32)).compile()
    _assert_fits_with_kernel(compiled)


def test_serving_decode_keeps_its_cache_in_place_at_16_layers(topo):
    """``make_serve_fns``' decode at the sc2-decode cell's shapes (16
    layers, 16 slots, a 4096-entry cache): the donated cache aliases the
    output; no operation in the scan's loop makes an array as large as one
    layer's cache (no slice copied out, relaid out or stacked back); and
    outside the loop the stacked cache is only written in place."""
    cfg = dataclasses.replace(SC2, num_layers=16)
    api = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    pshapes, axes = abstract_init(api)
    B, S, CACHE = 16, 1020, 4096
    _, decode_jit = make_serve_fns(
        api, mesh, axes, ShapeConfig("sc2", "prefill", S, B), pshapes)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    cache = jax.eval_shape(
        lambda p, b: api.prefill(p, b, cache_len=CACHE), pshapes, batch)[1]
    step = (jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32))
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        compiled = decode_jit(cache).lower(pshapes, cache, *step).compile()
    text = compiled.as_text()
    n_weights = len(jax.tree_util.tree_leaves(pshapes))
    assert aliases(text) == {1: n_weights, 2: n_weights + 1}
    cache_bytes = 2 * cache["k"].size * cache["k"].dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 64

    comps = parse_hlo(text)
    layer = cache["k"].size // cfg.num_layers
    big = [i["name"] for b in while_bodies(text) for i in comps[b]
           if i["op"] not in KEEP and math.prod(i["dims"]) >= layer]
    assert big == []
    made = [i for i in comps[entry_name(text)]
            if i["dims"] == cache["k"].shape and i["op"] not in KEEP]
    assert len(made) == 2 and all(in_place_write(comps, i) for i in made)


def _grouped_matmuls(text: str) -> int:
    return len([line for line in text.split("\n")
                if 'custom_call_target="tpu_custom_call"' in line
                and re.match(r"\s*(ROOT )?%ragged-dot-none", line)])


def _dense_over_experts(text: str, tokens, cfg) -> list[str]:
    """Arrays that hold a row for every token and every expert at a
    width: what a grouped matmul expanded densely would make."""
    widths = {cfg.d_model, cfg.d_ff}
    return [i["name"] for comp in parse_hlo(text).values() for i in comp
            if cfg.num_experts in i["dims"] and widths & set(i["dims"])
            and set(tokens) & set(i["dims"])]


def test_granite_expert_layer_compiles_to_grouped_matmuls_in_prefill(
        topo, one_chip):
    """The expert layer over a prefill of 16 x 1020 tokens: the gate, up
    and down projections are three grouped-matmul kernels, and no array
    spans tokens x experts x a width."""
    cfg, B, S = GRANITE, 16, 1020
    E, K, D, F = cfg.num_experts, cfg.top_k, cfg.d_model, cfg.d_ff
    params = {"router": _sds(one_chip, (D, E)),
              "wi_gate": _sds(one_chip, (E, D, F)),
              "wi_up": _sds(one_chip, (E, D, F)),
              "wo": _sds(one_chip, (E, F, D))}
    layer = jax.jit(lambda p, x: moe.moe_fwd(
        p, x, num_experts=E, top_k=K, capacity_factor=None)[0])
    text = layer.lower(params, _sds(one_chip, (B, S, D))).compile().as_text()
    assert _grouped_matmuls(text) == 3
    assert _dense_over_experts(text, (S, B * S, B * S * K), cfg) == []


def _stack_readers(text: str, stacks) -> list[str]:
    """The top-level instructions of the loop bodies that take an operand
    of one of the shapes ``stacks``."""
    comps, readers = parse_hlo(text), []
    for body in while_bodies(text):
        holders = {i["name"] for i in comps[body] if i["dims"] in stacks}
        readers += [i["name"] for i in comps[body]
                    if i["op"] not in KEEP and holders & set(i["operands"])]
    return readers


def test_granite_decode_reads_the_expert_weights_in_place(topo):
    """``make_serve_fns``' decode of 16 slots over a 2048-entry cache runs
    each layer's experts as batched dots that read the scan's stacks of
    expert weights where they lie: no grouped-matmul kernel, no operation
    in the loop makes an array of one layer's expert weights (no copy), the
    fusions that read the stacks are the expert layer's ``moe_experts``
    (none ``moe_weights``, the benchmark's label for a copy), and the
    donated cache aliases the output."""
    cfg, B, S, CACHE = GRANITE, 16, 1020, 2048
    api = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    pshapes, axes = abstract_init(api)
    _, decode_jit = make_serve_fns(
        api, mesh, axes, ShapeConfig("granite", "prefill", S, B), pshapes)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    cache = jax.eval_shape(
        lambda p, b: api.prefill(p, b, cache_len=CACHE), pshapes, batch)[1]
    step = (jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32))
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        compiled = decode_jit(cache).lower(pshapes, cache, *step).compile()
    text = compiled.as_text()
    assert _grouped_matmuls(text) == 0 and "ragged-dot" not in text

    weights = moe_scopes.weight_shapes(counts.Dims.from_config(json.loads(
        (ROOT / "benchmarks/chip/configs/granite-3.0-3b-a800m.json")
        .read_text())))
    comps = parse_hlo(text)
    copies = [i["name"] for b in while_bodies(text) for i in comps[b]
              if i["op"] not in KEEP and i["dims"] in weights]
    assert copies == []
    readers = _stack_readers(
        text, {(cfg.num_layers,) + w for w in weights})
    labels = moe_scopes.op_labels(text, weights)
    assert readers and {labels[r] for r in readers} == {"moe_experts"}

    n_weights = len(jax.tree_util.tree_leaves(pshapes))
    assert aliases(text) == {1: n_weights, 2: n_weights + 1}
    cache_bytes = 2 * cache["k"].size * cache["k"].dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
