"""Sharding-rule invariants (pure functions; no multi-device mesh needed
beyond a 1x1, since the rules operate on axis-name/shape arithmetic)."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh


class FakeMesh:
    """Mesh stand-in: sharding rules only read .axis_names and .shape."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _axis_size(entry):
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= MESH.shape.get(a, MESH3.shape.get(a, 1))
        return n
    return MESH.shape.get(entry, MESH3.shape.get(entry, 1))


@settings(max_examples=100, deadline=None)
@given(st.lists(st.sampled_from(["vocab", "embed", "heads", "kv", "ff",
                                 "experts", "layers", None]),
                min_size=1, max_size=4))
def test_spec_no_duplicate_mesh_axes(axes):
    rules = sh.param_rules(MESH, get_config("tinyllama_1p1b"))
    spec = sh.spec_from_axes(tuple(axes), rules)
    used = []
    for entry in spec:
        names = (entry if isinstance(entry, (tuple, list))
                 else [entry] if entry else [])
        for n in names:
            assert n not in used, f"axis {n} used twice in {spec}"
            used.append(n)


@settings(max_examples=100, deadline=None)
@given(st.lists(st.integers(1, 4096), min_size=1, max_size=4),
       st.lists(st.sampled_from(["data", "model", None]),
                min_size=1, max_size=4))
def test_sanitize_always_divides(shape, entries):
    entries = entries[: len(shape)]
    spec = P(*entries)
    out = sh.sanitize_spec(spec, tuple(shape), MESH)
    for dim, entry in zip(shape, list(out) + [None] * (len(shape) - len(out))):
        assert dim % _axis_size(entry) == 0


def test_embedding_keeps_vocab_only():
    """Embedding tables must never be FSDP-sharded on d_model (§Perf it. 2)."""
    rules = sh.param_rules(MESH, get_config("gemma3_4b"))
    spec = sh.spec_from_axes(("vocab", "embed"), rules)
    assert spec[0] == "model" and spec[1] is None
    spec = sh.spec_from_axes(("embed", "vocab"), rules)
    assert spec[1] == "model" and spec[0] is None


def test_moe_experts_replicated_ff_tp():
    """MoE layout: experts replicated, d_ff TP, d_model FSDP (§Perf it. 8)."""
    rules = sh.param_rules(MESH, get_config("dbrx_132b"))
    spec = sh.spec_from_axes(("experts", "embed", "ff"), rules)
    assert spec[0] is None          # experts NOT sharded over model
    assert spec[1] == "data"        # FSDP
    assert spec[2] == "model"       # TP


def test_cache_specs_pick_divisible_kv_or_hd():
    cfg = get_config("dbrx_132b")   # kv=8 (not /16), hd=128 (/16)
    from repro.configs import SHAPES
    cache = {
        "k": jax.ShapeDtypeStruct((40, 128, 8, 32769, 128), np.dtype("bfloat16")),
        "v": jax.ShapeDtypeStruct((40, 128, 8, 32769, 128), np.dtype("bfloat16")),
    }

    class M:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    specs = sh.cache_specs(cache, M(), cfg, SHAPES["decode_32k"])
    for s in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)):
        assert s[2] is None          # kv heads 8 can't take model=16
        assert s[4] == "model"       # head_dim 128 can


def test_dp_axes_respects_skip():
    assert sh.dp_axes(MESH3) == ("pod", "data")
    with sh.activation_sharding_scope(
            make_mesh((1, 1), ("data", "model")),
            skip_axes=frozenset({"pod"})):
        assert "pod" not in sh.dp_axes(MESH3)
