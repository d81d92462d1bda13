"""The decode step keeps its KV cache in place.

``make_serve_fns``' decode donates its cache; the scan over layers reads
each layer's cache where it lies and emits only the layer's new K and V
entry, and one ``dynamic-update-slice`` a leaf after the scan writes them
into the donated cache.  The compiled program is checked here: its cache
inputs alias its cache outputs, no operation inside the scan's loop makes
an array of the stacked cache's shape, and outside the loop the only such
arrays are the in-place writes.  (The same is checked for the v5e's
compiler, at StarCoder2-7B widths, in ``test_chip_compile.py``.)

The weights are float32 in the compiled checks: XLA:CPU computes bfloat16
dots in float32 and hoists that conversion of the whole stacked cache out
of the loop, a copy of the CPU backend's own.

The attention's contract changed with it: it attends over the cache's
entries before ``kv_len`` and the new entry held apart, in place of writing
the entry and attending over the cache.  The equivalence tests pin that to
the old write-then-attend, in a sliding-window ring and for a whole step.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_config, reduced_config
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import layers as L
from repro.models import transformer as TF
from repro.models.registry import build_model
from repro.serve.engine import make_serve_fns
from repro.train.loop import abstract_init

ARCHS = {"dense": "starcoder2_7b", "moe": "granite_moe_3b_a800m"}
B, S, CACHE = 2, 16, 48
KEEP = {"parameter", "get-tuple-element", "tuple"}   # pass a buffer on


def small_config(arch: str):
    """The reduced config with two KV heads, so that the cache's head and
    position axes are both wider than one."""
    return dataclasses.replace(reduced_config(get_config(arch)),
                               num_kv_heads=2)


def f32(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), tree)


# ---------------------------------------------------------------------------
# The compiled program.
# ---------------------------------------------------------------------------

INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                   r"([\w-]+)\((.*)$")


def parse_hlo(text: str) -> dict[str, list[dict]]:
    """Computation name -> its instructions whose result is an array:
    name, dims, opcode, operands, and the computations they call."""
    comps: dict[str, list[dict]] = {}
    current = None
    for line in text.split("\n"):
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            current = comps.setdefault(head.group(1), [])
            continue
        m = INSTR.match(line)
        if m and current is not None:
            calls = re.findall(r"(?:calls|body)=%([\w.\-]+)", m.group(5))
            current.append({"name": m.group(1),
                            "dims": tuple(int(d) for d in m.group(3).split(",")
                                          if d),
                            "op": m.group(4), "calls": calls,
                            "operands": re.findall(
                                r"%([\w.\-]+)", m.group(5).split(")")[0]),
                            "root": line.lstrip().startswith("ROOT ")})
    return comps


def while_bodies(text: str) -> list[str]:
    return re.findall(r"while\(.*?body=%([\w.\-]+)", text)


def entry_name(text: str) -> str:
    return re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)


def aliases(text: str) -> dict[int, int]:
    """Output index -> the parameter it aliases, from the module header."""
    header = text.split("\n", 1)[0]
    return {int(o): int(p) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}


def in_place_write(comps, instr) -> bool:
    """A ``dynamic-update-slice``, or a fusion whose root is one."""
    if instr["op"] == "dynamic-update-slice":
        return True
    return instr["op"] == "fusion" and any(
        i["root"] and i["op"] == "dynamic-update-slice"
        for c in instr["calls"] for i in comps[c])


@pytest.fixture(scope="module", params=sorted(ARCHS))
def compiled_decode(request):
    """(number of weight leaves, cache shapes, compiled decode) through
    ``make_serve_fns``, as the chip benchmark builds it."""
    api = build_model(small_config(ARCHS[request.param]))
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    pshapes, axes = abstract_init(api)
    pshapes = f32(pshapes)
    _, decode_jit = make_serve_fns(
        api, mesh, axes, ShapeConfig("t", "prefill", S, B), pshapes)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    cache = jax.eval_shape(
        lambda p, b: api.prefill(p, b, cache_len=CACHE), pshapes, batch)[1]
    step = (jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32))
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        decode = decode_jit(cache).lower(pshapes, cache, *step).compile()
    return (len(jax.tree_util.tree_leaves(pshapes)),
            [c.shape for c in (cache["k"], cache["v"])], decode.as_text())


def test_the_cache_inputs_alias_the_cache_outputs(compiled_decode):
    n_weights, _, text = compiled_decode
    # arguments: the weights' leaves, cache k and v, kv_len, token;
    # outputs: logits, cache k and v
    assert aliases(text) == {1: n_weights, 2: n_weights + 1}


def test_no_operation_in_the_loop_makes_a_stacked_cache(compiled_decode):
    _, (stack, _), text = compiled_decode
    comps = parse_hlo(text)
    bodies = while_bodies(text)
    assert bodies
    made = [i["name"] for b in bodies for i in comps[b]
            if i["dims"] == stack and i["op"] not in KEEP]
    assert made == []


def test_outside_the_loop_the_stacked_cache_is_only_written_in_place(
        compiled_decode):
    _, (stack, _), text = compiled_decode
    comps = parse_hlo(text)
    made = [i for i in comps[entry_name(text)]
            if i["dims"] == stack and i["op"] not in KEEP]
    assert len(made) == 2                       # one write per leaf
    assert all(in_place_write(comps, i) for i in made), made


def test_the_donated_cache_is_deleted_after_one_step():
    cfg = small_config("starcoder2_7b")
    api = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    pshapes, axes = abstract_init(api)
    prefill_jit, decode_jit = make_serve_fns(
        api, mesh, axes, ShapeConfig("t", "prefill", S, B), pshapes)
    params, _ = api.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, S)), jnp.int32)
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        logits, cache = prefill_jit({"tokens": tokens}, cache_len=CACHE)(
            params, {"tokens": tokens})
        decode = decode_jit(jax.eval_shape(lambda: cache))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        logits, new = decode(params, cache, jnp.int32(S), tok)
    assert all(c.is_deleted() for c in jax.tree_util.tree_leaves(cache))
    assert not any(c.is_deleted() for c in jax.tree_util.tree_leaves(new))
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# The attention's contract against the old write-then-attend.
# ---------------------------------------------------------------------------


def old_attention_decode(params, x, cfg: L.AttnConfig, k_cache, v_cache,
                         kv_len, positions):
    """The attention before the cache stayed in place: caches (B, S, KV,
    hd); the new entry is written at slot ``kv_len % S``, then the query
    attends over the first min(kv_len + 1, S) slots."""
    B = x.shape[0]
    q, k_new, v_new = L._qkv(params, x, cfg, positions)
    S_cache = k_cache.shape[1]
    slot = jnp.asarray(kv_len) % S_cache
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    valid = jnp.minimum(kv_len + 1, S_cache)
    H, hd = q.shape[2], q.shape[3]
    KV = k_cache.shape[2]
    qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(B, KV, H // KV, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32))
    s = jnp.where(jnp.arange(S_cache) < valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    out = o.reshape(B, 1, H * hd).astype(q.dtype) @ params["wo"]
    return out, k_cache, v_cache


W = 8          # the ring's size


@pytest.mark.parametrize("kv_len", [3, W - 1, W, W + 5, 2 * W + 3],
                         ids=["below", "window-1", "window", "ring",
                              "ring-twice"])
def test_attention_matches_write_then_attend_in_a_ring(kv_len):
    acfg = L.AttnConfig(d_model=64, num_heads=4, num_kv_heads=2,
                        head_dim=16, window=W)
    params, _ = L.init_attention(jax.random.PRNGKey(1), acfg, jnp.float32)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k1, (3, 1, 64), jnp.float32)
    # a full ring: slots past kv_len hold stale entries the mask must skip
    k_old = jax.random.normal(k2, (3, W, 2, 16), jnp.float32)
    v_old = jax.random.normal(k3, (3, W, 2, 16), jnp.float32)
    pos = jnp.full((3, 1), kv_len)

    want, k_want, v_want = old_attention_decode(
        params, x, acfg, k_old, v_old, kv_len, pos)
    out, k_new, v_new = L.attention_decode(
        params, x, acfg, jnp.swapaxes(k_old, 1, 2), jnp.swapaxes(v_old, 1, 2),
        kv_len, pos)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    for old, new, cache in ((k_old, k_new, k_want), (v_old, v_new, v_want)):
        written = L.write_kv(jnp.swapaxes(old, 1, 2), new, kv_len)
        np.testing.assert_array_equal(written, jnp.swapaxes(cache, 1, 2))


def old_decode_step(params, cfg, cache, kv_len, token):
    """``lm_decode_step``'s uniform path before the cache stayed in place:
    the scan carries each layer's (B, S, KV, hd) cache through the old
    attention and stacks the written caches back."""
    B = token.shape[0]
    x = TF._embed(params, cfg, token)
    pos = TF._positions(cfg, B, 1, offset=kv_len)
    acfg = TF._attn_cfg(cfg)

    def body(x, xs):
        blk, kc, vc = xs
        a, kc, vc = old_attention_decode(blk["attn"], TF._norm1(blk, cfg, x),
                                         acfg, kc, vc, kv_len, pos)
        x = x + a
        m, _ = TF._mix(blk, cfg, TF._norm2(blk, cfg, x))
        return x + m, (kc, vc)

    x, (k, v) = jax.lax.scan(body, x, (params["blocks"], cache["k"],
                                       cache["v"]))
    return TF._final(params, cfg, x)[:, 0], {"k": k, "v": v}


@pytest.mark.parametrize("kind", sorted(ARCHS))
def test_decode_steps_match_the_old_uniform_step(kind):
    cfg = small_config(ARCHS[kind])
    api = build_model(cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32),
                                    api.init(jax.random.PRNGKey(0))[0])
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (B, S + 3)), jnp.int32)
    _, cache = api.prefill(params, {"tokens": tokens[:, :S]},
                           cache_len=CACHE)
    old = jax.tree_util.tree_map(lambda c: jnp.swapaxes(c, 2, 3), cache)
    new_step = jax.jit(api.decode_step)
    old_step = jax.jit(lambda p, c, n, t: old_decode_step(p, cfg, c, n, t))
    for t in range(S, S + 3):
        tok = tokens[:, t:t + 1]
        logits, cache = new_step(params, cache, jnp.int32(t), tok)
        want, old = old_step(params, old, jnp.int32(t), tok)
        np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(cache[leaf], jnp.swapaxes(old[leaf], 2, 3),
                                   rtol=1e-5, atol=1e-5)
