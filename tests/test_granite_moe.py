"""Granite-3.0-MoE through the serving path, against the plain reference
``benchmarks/chip/configs/granite_moe_ref.py``, at a small size on the CPU.

The configuration carries the family's four multipliers and dropless
routing; the router is skewed so that three of the sixteen experts are in
nearly every token's top four, where a capacity factor would drop most of
their assignments.  The program prefills, then decodes through its donated
cache, and its logits are held to the reference's full forward pass.  The
expert layer with a capacity factor is held to the capacity layer it
replaced (kept here as ``old_moe_fwd``), and a fault in routing has to
fail the comparison.
"""

import dataclasses
import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_config
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import moe
from repro.models import transformer as TF
from repro.models.registry import build_model
from repro.serve.engine import make_serve_fns
from repro.train.loop import abstract_init

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks.chip.configs import granite_moe_ref as ref  # noqa: E402

CFG = dataclasses.replace(
    get_config("granite_moe_3b_a800m"), num_layers=2, d_model=128,
    num_heads=4, num_kv_heads=2, head_dim=32, d_ff=64, vocab_size=509,
    num_experts=16, top_k=4, embedding_multiplier=12.0,
    attention_multiplier=0.05, residual_multiplier=0.22, logits_scaling=6.0,
    capacity_factor=None, tie_embeddings=False)
REF_CFG = {
    "num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 509,
    "num_local_experts": 16, "num_experts_per_tok": 4, "rope_theta": 1e4,
    "capacity_factor": None, "embedding_multiplier": 12.0,
    "attention_multiplier": 0.05, "residual_multiplier": 0.22,
    "logits_scaling": 6.0}
B, S, GEN, CACHE = 4, 12, 4, 24
SKEWED = 3          # experts the router favours

# The program computes in bfloat16 and the reference in float32 from the
# same bfloat16 weights; the logits have a standard deviation of 0.17.  A
# router near-tie that the bfloat16 rounding of the layer's input flips
# moves one token's logits by up to 0.18 (seeds 0, 3 and 6 of 0 to 9 have
# one), so the comparison holds the mean absolute difference, as the chip
# benchmark holds a mean gap.  It is at most 0.0054 over seeds 0 to 9,
# against 0.049 or more for the layer with a capacity factor of 1.25 (it
# drops assignments of the favoured experts) and 0.15 or more for the
# routing fault.  The limit lies between, over twice the largest reading.
MEAN_ATOL = 0.012


def params_for(seed: int):
    """The program's weights with the router at unit-scale logits, and
    skewed: every embedding row shares a direction ``u``, which the
    router's first ``SKEWED`` columns favour."""
    api = build_model(CFG)
    params = api.init(jax.random.PRNGKey(seed))[0]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1000))
    D = CFG.d_model
    u = jax.random.normal(k1, (D,))
    u = u / jnp.linalg.norm(u)
    emb = params["embedding"]["embed"].astype(jnp.float32)
    emb = emb + 0.02 * math.sqrt(D) * 0.7 * u
    params["embedding"]["embed"] = emb.astype(jnp.bfloat16)
    router = jax.random.normal(k2, params["blocks"]["moe"]["router"].shape)
    router = router * D ** -0.5
    router = router.at[..., :SKEWED].add(0.6 * u[:, None])
    params["blocks"]["moe"]["router"] = router.astype(jnp.bfloat16)
    return api, params


def serve(api, params, tokens):
    """Prefill the first S tokens, then decode the rest one at a time
    through ``make_serve_fns``' decode, which donates its cache: the
    logits at positions S-1 .. S+GEN-1."""
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    pshapes, axes = abstract_init(api)
    prefill_jit, decode_jit = make_serve_fns(
        api, mesh, axes, ShapeConfig("t", "prefill", S, B), pshapes)
    batch = {"tokens": tokens[:, :S]}
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        logits, cache = prefill_jit(batch, cache_len=CACHE)(params, batch)
        decode = decode_jit(cache)
        out = [logits]
        for t in range(S, S + GEN - 1):
            logits, cache = decode(params, cache, jnp.int32(t),
                                   tokens[:, t:t + 1])
            out.append(logits)
    return np.stack([np.asarray(o, np.float32)[:, :CFG.vocab_size]
                     for o in out], axis=1)


def reference(params, tokens):
    return np.asarray(ref.logits(params, REF_CFG, np.asarray(tokens),
                                 prompt_len=S, start=S - 1))


def tokens_for(seed: int):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (B, S + GEN - 1)), jnp.int32)


def layer0_input(params, tokens):
    """The expert layer's input in the first block."""
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = TF._embed(params, CFG, tokens)
    pos = TF._positions(CFG, *tokens.shape)
    a, _ = TF.L.attention_fwd(blk["attn"], TF._norm1(blk, CFG, x),
                              TF._attn_cfg(CFG), pos)
    return blk["moe"], TF._norm2(blk, CFG, TF._residual(CFG, x, a))


@pytest.mark.parametrize("seed", [0, 1])
def test_serving_matches_the_reference_with_skewed_routing(seed):
    api, params = params_for(seed)
    tokens = tokens_for(seed)
    mp, h = layer0_input(params, tokens)
    _, idx, _ = moe.route(mp, h, CFG.top_k)
    share = float(jnp.mean(idx < SKEWED)) * CFG.top_k / SKEWED
    assert share > 0.8             # the favoured experts take most tokens
    kw = dict(num_experts=CFG.num_experts, top_k=CFG.top_k)
    _, dropless = moe.moe_fwd(mp, h, capacity_factor=None, **kw)
    _, capped = moe.moe_fwd(mp, h, capacity_factor=1.25, **kw)
    assert float(dropless["dropped_frac"]) == 0.0
    assert float(capped["dropped_frac"]) > 0.1

    got, want = serve(api, params, tokens), reference(params, tokens)
    assert np.abs(got - want).mean() < MEAN_ATOL


def fault_route(params, x, top_k):
    """Each token's gates given to the experts ranked 2 to k+1."""
    logits = jnp.dot(x, params["router"], preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(logits, top_k + 1)
    return logits, idx[..., 1:], jax.nn.softmax(top[..., :top_k], axis=-1)


@pytest.mark.parametrize("fault", ["routing", "capacity"])
def test_a_fault_in_the_expert_layer_fails_the_comparison(monkeypatch,
                                                          fault):
    api, params = params_for(0)
    tokens = tokens_for(0)
    want = reference(params, tokens)
    if fault == "routing":
        monkeypatch.setattr(moe, "route", fault_route)
    else:                 # drops what the favoured experts cannot hold
        api = build_model(dataclasses.replace(CFG, capacity_factor=1.25))
    got = serve(api, params, tokens)
    assert np.abs(got - want).mean() > 2 * MEAN_ATOL


def test_router_logits_are_float32():
    """bfloat16 logits would be 0.004 apart near unit scale: about 4% of
    top-8-of-40 choices would tie or flip against a float32 reference."""
    D, E = 256, 40
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (2, 8, D), jnp.bfloat16)
    w = (jax.random.normal(k2, (D, E)) * D ** -0.5).astype(jnp.bfloat16)
    logits, _, gates = moe.route({"router": w}, x, 8)
    assert logits.dtype == gates.dtype == jnp.float32
    exact = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
    np.testing.assert_allclose(logits, exact, rtol=0, atol=1e-5)


def old_moe_fwd(params, x, *, num_experts, top_k, capacity_factor):
    """The capacity layer ``moe_fwd`` was before it sorted into grouped
    matmuls: per sequence, assignments sorted by expert, scattered into
    buffers of C rows an expert, the rest dropped, and gathered back."""
    B, S, D = x.shape
    E, K = num_experts, top_k
    probs = jax.nn.softmax((x @ params["router"]).astype(jnp.float32), -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    A = S * K
    C = int(max(1, -(-A * capacity_factor // E)))

    def row(xr, exp_r, gate_r):
        flat_exp = exp_r.reshape(A)
        order = jnp.argsort(flat_exp)
        sexp = flat_exp[order]
        stok = jnp.repeat(jnp.arange(S), K)[order]
        sgate = gate_r.reshape(A)[order]
        pos = jnp.arange(A) - jnp.searchsorted(sexp, sexp, side="left")
        keep = pos < C
        buf = jnp.zeros((E, C, D), xr.dtype).at[
            jnp.where(keep, sexp, 0), jnp.where(keep, pos, 0)].add(
            jnp.where(keep[:, None], xr[stok], 0))
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["wi_gate"]))
             * jnp.einsum("ecd,edf->ecf", buf, params["wi_up"]))
        ob = jnp.einsum("ecf,efd->ecd", h, params["wo"])
        vals = ob[jnp.where(keep, sexp, 0), jnp.where(keep, pos, 0)]
        vals = jnp.where(keep[:, None], vals, 0) * sgate[:, None]
        return jnp.zeros((S, D), ob.dtype).at[stok].add(vals), keep

    out, keep = jax.vmap(row)(x, gate_idx, gate_vals)
    return out, 1.0 - jnp.mean(keep.astype(jnp.float32))


@pytest.mark.parametrize("capacity_factor", [1.0, 1.25, 2.0])
def test_capacity_factor_keeps_the_old_capacity_layer(capacity_factor):
    """In float32, so that the two orders of summation agree to 1e-5."""
    _, params = params_for(2)
    tokens = tokens_for(2)
    mp, h = layer0_input(params, tokens)
    mp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), mp)
    h = h.astype(jnp.float32)
    kw = dict(num_experts=CFG.num_experts, top_k=CFG.top_k,
              capacity_factor=capacity_factor)
    want, want_dropped = old_moe_fwd(mp, h, **kw)
    got, aux = moe.moe_fwd(mp, h, **kw)
    assert float(want_dropped) > 0.0
    assert float(aux["dropped_frac"]) == pytest.approx(float(want_dropped))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("capacity_factor", [None, 1.25])
@pytest.mark.parametrize("batch", [1, 16])
def test_a_decode_step_gives_the_grouped_layers_result(monkeypatch, batch,
                                                       capacity_factor):
    """At S == 1 the batched dots over every expert give what the sorted,
    grouped layer gives on the same tokens, its loss, and drop nothing.  In
    float32, so that the two orders of summation agree to 1e-5."""
    _, params = params_for(2)
    mp, h = layer0_input(params, tokens_for(2))
    mp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), mp)
    h = h.astype(jnp.float32).reshape(-1, 1, CFG.d_model)[:batch]
    kw = dict(num_experts=CFG.num_experts, top_k=CFG.top_k,
              capacity_factor=capacity_factor)
    got, aux = moe.moe_fwd(mp, h, **kw)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)     # the grouped layer
    want, want_aux = moe.moe_fwd(mp, h, **kw)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(aux["aux_loss"]) == pytest.approx(
        float(want_aux["aux_loss"]), rel=1e-6)
    assert float(aux["dropped_frac"]) == float(want_aux["dropped_frac"]) == 0


@pytest.mark.parametrize("batch,seq,grouped", [
    (16, 1, 0), (128, 1, 0), (129, 1, 3), (4, 12, 3)])
def test_only_a_decode_step_of_few_tokens_leaves_the_grouped_matmuls(
        batch, seq, grouped):
    E, D, F = CFG.num_experts, CFG.d_model, CFG.d_ff
    shapes = {"router": (D, E), "wi_gate": (E, D, F), "wi_up": (E, D, F),
              "wo": (E, F, D)}
    mp = {k: jax.ShapeDtypeStruct(v, jnp.bfloat16) for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct((batch, seq, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, x: moe.moe_fwd(
        p, x, num_experts=E, top_k=CFG.top_k, capacity_factor=None))(mp, x)
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert sum(n.startswith("ragged_dot") for n in prims) == grouped


def test_expert_scopes_reach_the_compiled_steps():
    api = build_model(CFG)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    pshapes, axes = abstract_init(api)
    prefill_jit, decode_jit = make_serve_fns(
        api, mesh, axes, ShapeConfig("t", "prefill", S, B), pshapes)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        prefill = prefill_jit(batch, cache_len=CACHE).lower(
            pshapes, batch).compile()
        cache = jax.eval_shape(
            lambda p, b: api.prefill(p, b, cache_len=CACHE), pshapes,
            batch)[1]
        decode = decode_jit(cache).lower(
            pshapes, cache, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32)).compile()
    for compiled in (prefill, decode):
        names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
        found = {m.group(1) for n in names
                 for m in re.finditer(r"/moe/(moe_\w+)/", n)}
        assert found == {"moe_route", "moe_experts", "moe_combine"}


def test_paged_decode_matches_the_cache_decode_with_multipliers():
    """The paged block applies the multipliers as the cached one does.  In
    float32, where the two attentions differ only in summation order."""
    api, params = params_for(3)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    tokens = tokens_for(3)
    _, cache = api.prefill(params, {"tokens": tokens[:, :8]}, cache_len=16)
    paged = TF.lm_init_paged_cache(CFG, batch=B, max_len=16, page=4,
                                   dtype=jnp.float32)
    for t in range(8):
        _, paged = TF.lm_decode_step_paged(params, CFG, paged, jnp.int32(t),
                                           tokens[:, t:t + 1])
    for t in range(8, 12):
        want, cache = api.decode_step(params, cache, jnp.int32(t),
                                      tokens[:, t:t + 1])
        got, paged = TF.lm_decode_step_paged(params, CFG, paged,
                                             jnp.int32(t), tokens[:, t:t + 1])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_benchmark_configuration_runs_what_it_states():
    """The benchmark's Granite file gives the reference its multipliers at
    the top level and the program its overrides; the harness checks the
    sizes, and this the multipliers and dropless routing."""
    import json

    from benchmarks.chip import harness

    root = Path(__file__).resolve().parents[1]
    config = json.loads((root / "benchmarks/chip/configs"
                         / "granite-3.0-3b-a800m.json").read_text())
    cfg = harness.program_config(config)
    for key in ("embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling"):
        assert getattr(cfg, key) == config[key], key
    assert cfg.capacity_factor is None and config["capacity_factor"] is None
    assert set(config["reduced"]) == set(config["published"])
    assert all(config[k] != v for k, v in config["published"].items())
