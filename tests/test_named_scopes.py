"""The decoder's named scopes (``layers.SCOPES``) reach the op_name
metadata of the compiled serving programs, for the dense, expert and
local:global families, and the programs are named ``jit_prefill`` and
``jit_decode``."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ShapeConfig, get_config, reduced_config
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import transformer as TF
from repro.models.layers import SCOPES
from repro.models.registry import build_model
from repro.serve.engine import make_serve_fns
from repro.train.loop import abstract_init

ARCHS = {"dense": "starcoder2_7b", "moe": "granite_moe_3b_a800m",
         "local_global": "gemma3_4b"}
B, S, CACHE = 2, 16, 48


def scopes_in(hlo_text: str) -> set[str]:
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {c for n in names for c in re.split(r"[/;]", n)} & set(SCOPES)


def module_name(compiled) -> str:
    return compiled.as_text().split("\n", 1)[0].split()[1].rstrip(",")


@pytest.fixture(scope="module", params=sorted(ARCHS))
def serving(request):
    """(kind, config, compiled prefill, compiled decode) through
    ``make_serve_fns``, as the chip benchmark builds them."""
    cfg = reduced_config(get_config(ARCHS[request.param]))
    api = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    pshapes, axes = abstract_init(api)
    prefill_jit, decode_jit = make_serve_fns(
        api, mesh, axes, ShapeConfig("t", "prefill", S, B), pshapes)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        prefill = prefill_jit(batch, cache_len=CACHE).lower(
            pshapes, batch).compile()
    cache = jax.eval_shape(
        lambda p, b: api.prefill(p, b, cache_len=CACHE), pshapes, batch)[1]
    step = (jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32))
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        decode = decode_jit(cache).lower(pshapes, cache, *step).compile()
    return request.param, cfg, prefill, decode


def expected(kind: str) -> set[str]:
    return set(SCOPES) - {"mlp" if kind == "moe" else "moe"}


def test_every_scope_reaches_the_compiled_prefill(serving):
    kind, _, prefill, _ = serving
    assert scopes_in(prefill.as_text()) == expected(kind)


def test_every_scope_reaches_the_compiled_decode_step(serving):
    kind, _, _, decode = serving
    assert scopes_in(decode.as_text()) == expected(kind)


def test_the_serving_programs_are_named(serving):
    _, _, prefill, decode = serving
    assert module_name(prefill) == "jit_prefill"
    assert module_name(decode) == "jit_decode"


def test_every_scope_reaches_the_paged_decode_step():
    cfg = dataclasses.replace(reduced_config(get_config("starcoder2_7b")),
                              num_layers=2)
    api = build_model(cfg)
    pshapes, _ = abstract_init(api)
    page = 16
    cache = jax.eval_shape(lambda: TF.lm_init_paged_cache(
        cfg, batch=B, max_len=CACHE, page=page))
    del cache["page"]

    def step(params, pools, kv_len, token):
        return TF.lm_decode_step_paged(params, cfg, dict(pools, page=page),
                                       kv_len, token)

    compiled = jax.jit(step).lower(
        pshapes, cache, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32)).compile()
    assert scopes_in(compiled.as_text()) == expected("dense")
