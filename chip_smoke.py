#!/usr/bin/env python3
"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]              # one chip: every phase
    python chip_smoke.py --chips 4 [--seed N]    # four chips: sharded serving

One chip, in order:
  1. the device (no TPU: exit non-zero, no CPU fallback);
  2. the Pallas flash and paged kernels against their XLA references at
     StarCoder2-7B widths;
  3. serving StarCoder2-7B at full widths with 16 of its 32 layers: prefill
     and decode through ``make_serve_fns``, decode checked against a full
     forward pass, the continuous-batching ``BatchScheduler``, and the paged
     decode step checked against the dense one;
  4. KV pages copied off the device, spilled to the DDS page store and
     fetched back through the offload path;
  5. training one layer at full widths with ``Trainer``, and a DDS
     checkpoint of its state restored byte for byte.
With ``--chips 4``: the whole 32-layer model served over a 2x2 mesh, and the
16-layer cut on that mesh against the same cut on one of its chips.

Weights and data come from ``--seed``.  A failed phase or comparison prints
its traceback and the script exits non-zero without the last line.  Times
and memory are informational, not a benchmark.  The last line of a passing
run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ShapeConfig, get_config  # noqa: E402
from repro.distributed import sharding as sh  # noqa: E402
from repro.launch.device import device_report, enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh, make_test_mesh  # noqa: E402
from repro.models import transformer as TF  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serve.engine import make_serve_fns  # noqa: E402
from repro.train.loop import abstract_init  # noqa: E402

ARCH = "starcoder2_7b"
SERVE_LAYERS = 16       # of 32; the other 16 would be a second pipeline stage
BATCH, PROMPT, CACHE = 8, 2048, 4096
PAGE = 128
DECODE_STEPS = 3
SCHED_REQUESTS, SCHED_NEW = 16, 32
TRAIN_SEQ, TRAIN_STEPS = 2048, 5

# (relative L2 error, largest error over largest |reference|) and why.
KERNEL_TOL = (2e-2, 5e-2,
              "bf16 q/k/v and output, f32 accumulation: each side rounds its "
              "output to bf16 (2^-8 relative) and may take f32 matmul "
              "operands in one bf16 pass; an fp8 path (2^-4) would fail")
MODEL_TOL = (5e-2, 1e-1,
             "bf16 weights and activations over 16 layers: the two programs "
             "round attention differently (2^-8 each time) and the residual "
             "stream carries it up the stack; fp8 activations would fail")


# ---------------------------------------------------------------------------
# Helpers.
# ---------------------------------------------------------------------------


def memory_line() -> str:
    stats = [d.memory_stats() or {} for d in jax.devices()]
    used = max(s.get("bytes_in_use", 0) for s in stats)
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    return (f"bytes_in_use {used / 2**30:.2f} GiB, peak_bytes_in_use "
            f"{peak / 2**30:.2f} GiB (largest device, process so far)")


def compile_timed(jitted, *args):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def run_timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def require_kernel(compiled, what: str) -> None:
    """A Pallas kernel lowers to a ``tpu_custom_call``; its absence means
    the dispatch fell back to XLA."""
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{what}: no tpu_custom_call in the program")
    print(f"   {what}: tpu_custom_call present", flush=True)


def check_close(what: str, got, want, tol) -> None:
    l2_tol, max_tol, why = tol
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite values")
    diff = got - want
    rel_l2 = float(np.linalg.norm(diff) / np.linalg.norm(want))
    rel_max = float(np.abs(diff).max() / np.abs(want).max())
    print(f"   {what}: rel_l2 {rel_l2:.4e} (tol {l2_tol:g}), max_err/max_ref "
          f"{rel_max:.4e} (tol {max_tol:g}) -- {why}", flush=True)
    if rel_l2 > l2_tol or rel_max > max_tol:
        raise AssertionError(f"{what}: outside tolerance")


def normal(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class FixedBatch:
    """A pipeline that serves the same batch at every step."""

    def __init__(self, batch: dict):
        self.batch = batch

    def batch_at(self, step: int) -> dict:
        return self.batch


@dataclasses.dataclass
class Session:
    """One model prefilled through ``make_serve_fns`` on one mesh."""
    params: dict
    cache: dict
    prefill_logits: jax.Array
    decode: object          # compiled decode step


def serve_session(api, mesh, key, prompt: np.ndarray) -> Session:
    """Compile prefill and decode on ``mesh``, make the weights from ``key``
    in the prefill's layout, and prefill ``prompt`` into a CACHE-long cache."""
    B, S = prompt.shape
    shape = ShapeConfig("smoke", "prefill", S, B)
    pshapes, axes = abstract_init(api)
    prefill_jit, decode_jit = make_serve_fns(api, mesh, axes, shape, pshapes)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    mode = "train" if api.cfg.pin_prefill else "decode"
    with mesh, sh.activation_sharding_scope(mesh, mode):
        prefill, t_pre = compile_timed(prefill_jit(batch, cache_len=CACHE),
                                       pshapes, batch)
    require_kernel(prefill, "prefill")
    param_sh = prefill.input_shardings[0][0]
    params = jax.block_until_ready(jax.jit(
        lambda k: api.init(k)[0], out_shardings=param_sh)(key))
    (logits, cache), dt = run_timed(prefill, params, {"tokens": prompt})
    print(f"   prefill {B} x {S} into a {CACHE}-entry cache: compile "
          f"{t_pre:.1f} s, run {dt:.3f} s", flush=True)
    cache_like = jax.eval_shape(lambda: cache)
    step = (jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32))
    with mesh, sh.activation_sharding_scope(mesh, "decode"):
        decode, t_dec = compile_timed(decode_jit(cache_like), pshapes,
                                      cache_like, *step)
    print(f"   decode step: compile {t_dec:.1f} s", flush=True)
    # Prefill and decode may place the weights differently (1D vs 2D TP).
    params = jax.device_put(params, decode.input_shardings[0][0])
    return Session(params, cache, logits, decode)


def decode_one(sess: Session, kv_len: int, token: np.ndarray):
    (logits, sess.cache), dt = run_timed(
        sess.decode, sess.params, sess.cache, np.int32(kv_len), token)
    return logits, dt


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    dev = device_report()
    cache_dir = enable_compile_cache()
    print(f"device: platform {dev['platform']}, kind {dev['kind']}, count "
          f"{dev['count']}; compile cache {cache_dir}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']} devices")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, "
                         f"found {dev['count']}")
    return dev


def phase_kernels(key) -> None:
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ops import flash_attention_xla
    from repro.kernels.paged_attention.kernel import paged_attention_pallas
    from repro.kernels.paged_attention.ref import paged_attention_ref

    cfg = get_config(ARCH)
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 7)
    q = normal(ks[0], (1, PROMPT, Hq, D))
    k = normal(ks[1], (1, PROMPT, Hkv, D))
    v = normal(ks[2], (1, PROMPT, Hkv, D))
    for causal in (True, False):
        name = f"flash S={PROMPT} causal={causal}"
        kern, t_c = compile_timed(jax.jit(functools.partial(
            flash_attention_pallas, causal=causal)), q, k, v)
        require_kernel(kern, name)
        out, dt = run_timed(kern, q, k, v)
        ref = jax.jit(functools.partial(flash_attention_xla,
                                        causal=causal))(q, k, v)
        print(f"   {name}: compile {t_c:.1f} s, run {dt * 1e3:.2f} ms")
        check_close(f"{name} vs flash_attention_xla", out, ref, KERNEL_TOL)

    pps = CACHE // PAGE
    pool_pages = BATCH * pps + 16
    qd = normal(ks[3], (BATCH, Hq, D))
    kp = normal(ks[4], (pool_pages, PAGE, Hkv, D))
    vp = normal(ks[5], (pool_pages, PAGE, Hkv, D))
    table = jax.random.permutation(ks[6], pool_pages)[:BATCH * pps]
    table = table.reshape(BATCH, pps).astype(jnp.int32)
    lens = jnp.asarray(np.minimum(
        [1, 100, 128, 129, 1000, 2048, 3001, CACHE], CACHE)[:BATCH], jnp.int32)
    kern, t_c = compile_timed(jax.jit(paged_attention_pallas),
                              qd, kp, vp, table, lens)
    require_kernel(kern, "paged decode")
    out, dt = run_timed(kern, qd, kp, vp, table, lens)
    ref = jax.jit(paged_attention_ref)(qd, kp, vp, table, lens)
    print(f"   paged decode B={BATCH} seq_lens={np.asarray(lens).tolist()}: "
          f"compile {t_c:.1f} s, run {dt * 1e3:.2f} ms")
    check_close("paged decode vs paged_attention_ref", out, ref, KERNEL_TOL)


def phase_serving(key, rng):
    """Returns the model, its weights and the paged KV pools after
    decoding, for the scheduler and DDS phases."""
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=SERVE_LAYERS)
    print(f"   {ARCH} at full widths with {SERVE_LAYERS} of {full.num_layers} "
          f"layers: the other {full.num_layers - SERVE_LAYERS} would be a "
          f"second pipeline stage")
    api = build_model(cfg)
    tokens = rng.integers(0, cfg.vocab_size, (BATCH, PROMPT + DECODE_STEPS),
                          dtype=np.int32)
    sess = serve_session(api, make_test_mesh(1), key, tokens[:, :PROMPT])

    # The paged pool holds the same prefilled cache: lm_init_paged_cache's
    # block table maps sequence b's page p to pool page b * pages + p.
    paged_like = jax.eval_shape(
        lambda: TF.lm_init_paged_cache(cfg, BATCH, CACHE, PAGE))
    pps = CACHE // PAGE

    def to_pool(x):                     # (L, B, KV, S, hd) -> pages
        x = jnp.swapaxes(x, 2, 3)
        return x.reshape(x.shape[0], BATCH * pps, PAGE, *x.shape[3:])

    pools = jax.jit(lambda c: {
        "k_pool": to_pool(c["k"]), "v_pool": to_pool(c["v"]),
        "block_table": jnp.arange(BATCH * pps, dtype=jnp.int32).reshape(
            BATCH, pps)})(sess.cache)
    for name in pools:
        if pools[name].shape != paged_like[name].shape:
            raise AssertionError(f"paged cache layout changed: {name}")

    def paged_step(params, pools, kv_len, token):
        logits, new = TF.lm_decode_step_paged(
            params, cfg, dict(pools, page=PAGE), kv_len, token)
        del new["page"]
        return logits, new

    paged, t_c = compile_timed(jax.jit(paged_step), sess.params, pools,
                               np.int32(PROMPT), tokens[:, PROMPT:PROMPT + 1])
    require_kernel(paged, "paged decode step")
    print(f"   paged decode step: compile {t_c:.1f} s")
    decode_logits = []
    for t in range(DECODE_STEPS):
        kv_len = PROMPT + t
        token = tokens[:, kv_len:kv_len + 1]
        d_logits, dt = decode_one(sess, kv_len, token)
        (p_logits, pools), pdt = run_timed(paged, sess.params, pools,
                                           np.int32(kv_len), token)
        print(f"   decode at kv_len {kv_len}: dense {dt * 1e3:.2f} ms, "
              f"paged {pdt * 1e3:.2f} ms")
        check_close(f"paged vs dense decode logits, kv_len {kv_len}",
                    p_logits, d_logits, MODEL_TOL)
        decode_logits.append(np.asarray(d_logits, np.float32))
    prefill_logits = np.asarray(sess.prefill_logits, np.float32)
    params = sess.params
    del sess                            # drop the dense cache

    # Reference: one full forward pass over the prompt and the first
    # decoded token, through the same weights.
    fwd = jax.jit(lambda p, t: api.forward(p, {"tokens": t})[0][:, -2:])
    full, t_c = compile_timed(fwd, params, tokens[:, :PROMPT + 1])
    require_kernel(full, f"forward over {PROMPT + 1} tokens (padded flash)")
    ref, dt = run_timed(full, params, tokens[:, :PROMPT + 1])
    print(f"   full forward {BATCH} x {PROMPT + 1}: compile {t_c:.1f} s, "
          f"run {dt:.3f} s")
    ref = np.asarray(ref, np.float32)
    check_close(f"prefill logits vs forward at position {PROMPT - 1}",
                prefill_logits, ref[:, 0], MODEL_TOL)
    check_close(f"decode logits vs forward at position {PROMPT}",
                decode_logits[0], ref[:, 1], MODEL_TOL)
    return api, params, pools


def phase_scheduler(api, params, rng) -> None:
    from repro.serve.engine import BatchScheduler, Request

    sched = BatchScheduler(api, params, slots=BATCH, cache_len=CACHE)
    reqs = [Request(i, rng.integers(0, api.cfg.vocab_size, size=16),
                    max_new=SCHED_NEW) for i in range(SCHED_REQUESTS)]
    for r in reqs:
        sched.submit(r)
    times, done = [], 0
    while done < SCHED_REQUESTS and len(times) < 10 * SCHED_NEW:
        t0 = time.perf_counter()
        done += sched.step()            # syncs: it reads the sampled tokens
        times.append(time.perf_counter() - t0)
    if done != SCHED_REQUESTS or any(
            len(r.generated) != SCHED_NEW for r in reqs):
        raise AssertionError(f"scheduler finished {done}/{SCHED_REQUESTS}")
    toks = np.asarray([r.generated for r in reqs])
    if toks.min() < 0 or toks.max() >= api.cfg.padded_vocab:
        raise AssertionError("scheduler sampled a token outside the vocab")
    steady = float(np.median(times[1:]))
    print(f"   BatchScheduler: {SCHED_REQUESTS} requests x {SCHED_NEW} tokens "
          f"over {BATCH} slots, cache {CACHE}: {len(times)} steps, first "
          f"(compile) {times[0]:.1f} s, median step {steady * 1e3:.2f} ms, "
          f"{BATCH / steady:.1f} tokens/s while full")


def phase_kv_pages(pools) -> None:
    from repro.serve.engine import PagedKVEngine
    from repro.storage.pagestore import PAGE_HDR, PageStore

    n = 4                               # layer 0, sequence 0's first pages
    k = np.asarray(pools["k_pool"][0, :n])
    v = np.asarray(pools["v_pool"][0, :n])
    pages = [k[i].tobytes() + v[i].tobytes() for i in range(n)]
    if not any(np.any(k[i]) for i in range(n)):
        raise AssertionError("KV pages are empty: prefill did not fill them")
    page_bytes = len(pages[0])
    store = PageStore(page_size=-(-(page_bytes + PAGE_HDR.size) // 4096) * 4096,
                      num_pages=2 * n)
    eng = PagedKVEngine(store, block_bytes=page_bytes, hbm_blocks=2)
    for i, data in enumerate(pages):
        eng.put_block(0, 0, i, data)
    for i in range(eng.hbm_blocks):     # push every page above out of HBM
        eng.put_block(1, 0, i, bytes(page_bytes))
    served = store.server.offload.stats.completed
    for i, data in enumerate(pages):
        got = eng.get_block(0, 0, i)
        if got is None or bytes(got[:page_bytes]) != data:
            raise AssertionError(f"KV page {i} came back different")
    offloaded = store.server.offload.stats.completed - served
    if offloaded != n:
        raise AssertionError(f"{offloaded} of {n} fetches were offloaded")
    print(f"   {n} KV pages of {page_bytes} bytes (K and V, one layer): "
          f"{eng.spills} spills, {eng.fetches} fetches, {offloaded} served "
          f"by the offload path, bytes identical")


def phase_training(key, rng) -> None:
    from repro.storage.checkpoint import CheckpointManager
    from repro.train.loop import TrainConfig, Trainer

    cfg = dataclasses.replace(get_config(ARCH), num_layers=1)
    api = build_model(cfg)
    toks = rng.integers(0, cfg.vocab_size, (1, TRAIN_SEQ + 1), dtype=np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    tcfg = TrainConfig(peak_lr=1e-3, warmup_steps=1, total_steps=100)
    trainer = Trainer(api, tcfg, FixedBatch(batch), ckpt_every=TRAIN_STEPS,
                      key=key)
    trainer.ckpt = CheckpointManager.sized_for(trainer.state_tree(), keep=1)
    times = []
    for _ in range(TRAIN_STEPS):        # the last step also saves
        t0 = time.perf_counter()
        trainer.run(1)                  # syncs: it reads the loss
        times.append(time.perf_counter() - t0)
    losses = [h["loss"] for h in trainer.history]
    print(f"   1 layer, full widths and vocab, batch 1 x {TRAIN_SEQ}: losses "
          f"{[round(x, 4) for x in losses]}; first step (compile) "
          f"{times[0]:.1f} s, median step {np.median(times[1:-1]):.3f} s")
    if not np.isfinite(losses).all() or not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")

    info = trainer.ckpt._history[-1]
    saved = jax.device_get(trainer.state_tree())
    t0 = time.perf_counter()
    back = trainer.ckpt.restore(trainer.ckpt.latest_step(), saved)
    t_restore = time.perf_counter() - t0
    leaves = zip(jax.tree_util.tree_leaves(saved),
                 jax.tree_util.tree_leaves(back))
    for a, b in leaves:
        if np.asarray(a).tobytes() != np.asarray(b).tobytes():
            raise AssertionError("restored checkpoint differs")
    print(f"   checkpoint of params + Adam moments at step {info.step}: "
          f"{info.nbytes / 2**30:.2f} GiB in {info.leaves} leaves, save "
          f"{info.wall_s:.1f} s, restore {t_restore:.1f} s, bytes identical")


def phase_sharded(key, rng) -> None:
    """Four chips: the whole model, then the 16-layer cut vs one chip."""
    full = get_config(ARCH)
    mesh4 = make_test_mesh(4)
    print(f"   mesh {dict(mesh4.shape)} over {mesh4.devices.size} chips")
    tokens = rng.integers(0, full.vocab_size, (BATCH, PROMPT + 1),
                          dtype=np.int32)
    prompt, token = tokens[:, :PROMPT], tokens[:, PROMPT:]

    sess = serve_session(build_model(full), mesh4, key, prompt)
    logits, dt = decode_one(sess, PROMPT, token)
    logits = np.asarray(logits, np.float32)
    if logits.shape != (BATCH, full.padded_vocab) or not np.isfinite(
            logits).all() or not np.isfinite(
            np.asarray(sess.prefill_logits, np.float32)).all():
        raise AssertionError("32-layer logits are not finite or misshapen")
    print(f"   {ARCH}, all {full.num_layers} layers: prefill and decode "
          f"(step {dt * 1e3:.2f} ms) give finite logits {logits.shape}")
    del sess

    cut = build_model(dataclasses.replace(full, num_layers=SERVE_LAYERS))
    results = {}
    for name, mesh in (("one chip", make_mesh((1, 1), ("data", "model"),
                                              devices=jax.devices()[:1])),
                       ("2x2 mesh", mesh4)):
        sess = serve_session(cut, mesh, key, prompt)
        logits, dt = decode_one(sess, PROMPT, token)
        results[name] = (np.asarray(sess.prefill_logits, np.float32),
                         np.asarray(logits, np.float32))
        print(f"   {SERVE_LAYERS} layers on {name}: decode step "
              f"{dt * 1e3:.2f} ms")
        del sess
    for i, what in enumerate(("prefill", "decode")):
        check_close(f"{SERVE_LAYERS}-layer {what} logits, 2x2 mesh vs one "
                    f"chip", results["2x2 mesh"][i], results["one chip"][i],
                    MODEL_TOL)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded serving path and its reference")
    args = ap.parse_args()

    dev = phase_device(args.chips)
    key = jax.random.PRNGKey(args.seed)
    rng = np.random.default_rng(args.seed)

    def phase(name, fn, *a):
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        out = fn(*a)
        print(f"   {name}: passed in {time.perf_counter() - t0:.1f} s; "
              f"{memory_line()}", flush=True)
        return out

    if args.chips == 4:
        phase("sharded serving", phase_sharded, key, rng)
    else:
        phase("kernels", phase_kernels, jax.random.fold_in(key, 1))
        api, params, pools = phase("serving", phase_serving,
                                   jax.random.fold_in(key, 2), rng)
        phase("KV pages through DDS", phase_kv_pages, pools)
        del pools
        phase("continuous batching", phase_scheduler, api, params, rng)
        del api, params
        phase("training", phase_training, jax.random.fold_in(key, 3), rng)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
