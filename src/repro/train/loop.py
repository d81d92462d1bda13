"""Distributed training step + Trainer with fault-tolerant checkpointing.

``make_train_step`` builds a pjit-able (params, opt, batch, step) ->
(params, opt, metrics) function:

  * gradients via jax.grad over the registry loss (remat inside the model's
    layer scan keeps activation memory at O(sqrt) levels);
  * optional microbatch gradient accumulation (lax.scan over batch splits);
  * AdamW with global-norm clipping; optimizer state inherits parameter
    sharding (ZeRO via GSPMD);
  * optional int8 error-feedback compression of the cross-pod gradient
    reduction (repro.optim.compression) — the pod axis all-reduce is the
    slowest hop at multi-pod scale.

``Trainer`` drives steps with data from the ring-prefetched pipeline and
checkpoints through the DDS storage path (write-behind, atomic manifest).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as sh
from repro.models.registry import ModelAPI
from repro.optim import adamw_init, adamw_update, warmup_cosine
from repro.optim.compression import (compress_tree, decompress_tree,
                                     init_compression)


@dataclass
class TrainConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    max_grad_norm: float = 1.0
    microbatch: int = 1           # gradient-accumulation splits
    fsdp: bool = True
    compress_pod_grads: bool = False
    b1: float = 0.9
    b2: float = 0.95


def abstract_init(api: ModelAPI, key=None):
    """(param ShapeDtypeStructs, axes tree) without allocating anything."""
    key = key if key is not None else jax.random.PRNGKey(0)
    captured: dict[str, Any] = {}

    def initfn(k):
        p, a = api.init(k)
        captured["axes"] = a
        return p

    shapes = jax.eval_shape(initfn, key)
    return shapes, captured["axes"]


def _split_micro(batch: dict, n: int) -> dict:
    def sp(x):
        B = x.shape[0]
        return x.reshape(n, B // n, *x.shape[1:])
    return {k: sp(v) for k, v in batch.items()}


def make_train_fn(api: ModelAPI, tcfg: TrainConfig) -> Callable:
    """The un-jitted step (used by both jit and lower paths)."""

    def lr_fn(step):
        return warmup_cosine(step, peak_lr=tcfg.peak_lr,
                             warmup_steps=tcfg.warmup_steps,
                             total_steps=tcfg.total_steps)

    def compute_grads(params, batch):
        def loss_of(p, b):
            loss, metrics = api.loss_fn(p, b)
            return loss, metrics

        if tcfg.microbatch > 1:
            micro = _split_micro(batch, tcfg.microbatch)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (loss, _), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + loss), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g, loss_sum), _ = jax.lax.scan(acc_body,
                                            (zero_g, jnp.zeros(())), micro)
            inv = 1.0 / tcfg.microbatch
            g = jax.tree_util.tree_map(lambda x: x * inv, g)
            return g, loss_sum * inv
        (loss, _), g = jax.value_and_grad(loss_of, has_aux=True)(params, batch)
        return g, loss

    def train_step(params, opt_state, comp_state, batch, step):
        grads, loss = compute_grads(params, batch)
        if tcfg.compress_pod_grads and comp_state is not None:
            # int8 error-feedback quantization of the gradient exchange.
            q, scales, comp_state = compress_tree(grads, comp_state)
            grads = decompress_tree(q, scales)
        new_params, new_opt, gnorm = adamw_update(
            grads, opt_state, params, lr_fn(step),
            b1=tcfg.b1, b2=tcfg.b2, weight_decay=tcfg.weight_decay,
            max_grad_norm=tcfg.max_grad_norm)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr_fn(step)}
        return new_params, new_opt, comp_state, metrics

    return train_step


def make_compressed_pod_train_fn(api: ModelAPI, tcfg: TrainConfig,
                                 mesh: Mesh) -> Callable:
    """Train step with WIRE-LEVEL int8 cross-pod gradient exchange.

    shard_map manual over the ``pod`` axis only: each pod computes its
    gradients with GSPMD (data/model stay auto), quantizes them to int8
    with error feedback, and the CROSS-POD exchange is an all-gather of the
    int8 payloads + per-tensor scales — 4x fewer bytes on the slow pod
    links than the fp32 all-reduce GSPMD would insert.  Error-feedback
    residuals live per pod (leading pod dim on the compression state).
    """
    import functools

    from repro.distributed.sharding import activation_sharding_scope
    from repro.optim.compression import CompressionState, _dequantize, _quantize

    npods = mesh.shape["pod"]

    def lr_fn(step):
        return warmup_cosine(step, peak_lr=tcfg.peak_lr,
                             warmup_steps=tcfg.warmup_steps,
                             total_steps=tcfg.total_steps)

    def per_pod(params, comp_err, batch):
        # comp_err arrives with a leading per-pod dim of size 1 (P("pod")).
        comp_err = jax.tree_util.tree_map(lambda e: e[0], comp_err)
        # Inside: manual over 'pod'; data/model remain auto (GSPMD).
        with activation_sharding_scope(mesh, "train",
                                       skip_axes=frozenset({"pod"})):
            def loss_of(p):
                loss, _ = api.loss_fn(p, batch)
                return loss

            loss, grads = jax.value_and_grad(loss_of)(params)

        def exchange(g, e):
            x = g.astype(jnp.float32) + e
            q, s = _quantize(x)
            new_e = x - _dequantize(q, s)
            qg = jax.lax.all_gather(q, "pod")      # int8 on the pod links
            sg = jax.lax.all_gather(s, "pod")
            deq = qg.astype(jnp.float32) * sg.reshape(
                (npods,) + (1,) * g.ndim)
            return deq.mean(0), new_e

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_e = treedef.flatten_up_to(comp_err)
        outs = [exchange(g, e) for g, e in zip(flat_g, flat_e)]
        mean_g = treedef.unflatten([o[0] for o in outs])
        new_err = treedef.unflatten([o[1][None] for o in outs])  # re-add pod dim
        return mean_g, new_err, jax.lax.pmean(loss, "pod")

    def train_step(params, opt_state, comp_state, batch, step):
        pod_specs = jax.tree_util.tree_map(
            lambda _: jax.sharding.PartitionSpec(), params)
        batch_specs = {k: jax.sharding.PartitionSpec("pod")
                       for k in batch}
        err_specs = jax.tree_util.tree_map(
            lambda _: jax.sharding.PartitionSpec("pod"), params)
        fn = jax.shard_map(
            per_pod, mesh=mesh, axis_names={"pod"}, check_vma=False,
            in_specs=(pod_specs, err_specs, batch_specs),
            out_specs=(pod_specs, err_specs,
                       jax.sharding.PartitionSpec()))
        grads, new_err, loss = fn(params, comp_state.error, batch)
        new_params, new_opt, gnorm = adamw_update(
            grads, opt_state, params, lr_fn(step),
            b1=tcfg.b1, b2=tcfg.b2, weight_decay=tcfg.weight_decay,
            max_grad_norm=tcfg.max_grad_norm)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr_fn(step)}
        return new_params, new_opt, CompressionState(new_err), metrics

    return train_step


def init_pod_compression(params, npods: int) -> "CompressionState":
    """Per-pod error-feedback residuals (leading pod dim)."""
    from repro.optim.compression import CompressionState
    return CompressionState(error=jax.tree_util.tree_map(
        lambda p: jnp.zeros((npods,) + p.shape, jnp.float32), params))


def make_train_step(api: ModelAPI, mesh: Mesh, axes_tree, tcfg: TrainConfig,
                    batch_spec: dict | None = None):
    """jit the train step with explicit in/out shardings for ``mesh``."""
    pspecs = sh.param_specs(axes_tree, mesh, api.cfg, fsdp=tcfg.fsdp)
    opt_specs = (P(), pspecs, pspecs)  # count, mu, nu
    comp_specs = (pspecs,) if tcfg.compress_pod_grads else None
    dp = sh.dp_axes(mesh)
    bspec = batch_spec or {"tokens": P(dp, None), "labels": P(dp, None),
                           "frames": P(dp, None, None),
                           "embeds": P(dp, None, None)}
    step_fn = make_train_fn(api, tcfg)

    def filter_bspec(batch_like):
        return {k: bspec.get(k, P(dp, None)) for k in batch_like}

    def jit_for(batch_like):
        in_shardings = (pspecs, opt_specs, comp_specs,
                        filter_bspec(batch_like), P())
        out_shardings = (pspecs, opt_specs, comp_specs,
                         {"loss": P(), "grad_norm": P(), "lr": P()})
        return jax.jit(step_fn,
                       in_shardings=jax.tree_util.tree_map(
                           lambda s: NamedSharding(mesh, s), in_shardings,
                           is_leaf=lambda x: isinstance(x, P)),
                       out_shardings=jax.tree_util.tree_map(
                           lambda s: NamedSharding(mesh, s), out_shardings,
                           is_leaf=lambda x: isinstance(x, P)))

    return step_fn, jit_for


def init_train_state(api: ModelAPI, tcfg: TrainConfig, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    params, axes = api.init(key)
    opt = adamw_init(params)
    comp = (init_compression(params),) if tcfg.compress_pod_grads else None
    return params, opt, comp, axes


class Trainer:
    """End-to-end driver: pipeline -> train step -> DDS checkpoints."""

    def __init__(self, api: ModelAPI, tcfg: TrainConfig, pipeline,
                 checkpoint_mgr=None, mesh: Mesh | None = None,
                 ckpt_every: int = 100, key=None):
        self.api = api
        self.tcfg = tcfg
        self.pipeline = pipeline
        self.ckpt = checkpoint_mgr
        self.ckpt_every = ckpt_every
        self.mesh = mesh
        self.params, self.opt, self.comp, self.axes = init_train_state(
            api, tcfg, key)
        self.step = 0
        self.history: list[dict] = []
        self._step_fn = jax.jit(make_train_fn(api, tcfg))

    def restore_latest(self) -> bool:
        if self.ckpt is None:
            return False
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        back = self.ckpt.restore(latest, self.state_tree())
        self.params = back["params"]
        self.opt = self.opt._replace(
            mu=back["mu"], nu=back["nu"],
            count=jnp.asarray(latest, jnp.int32))
        self.step = latest
        return True

    def state_tree(self) -> dict:
        """What a checkpoint holds: parameters and Adam's moments."""
        return {"params": self.params, "mu": self.opt.mu, "nu": self.opt.nu}

    def run(self, steps: int) -> list[dict]:
        for _ in range(steps):
            batch = self.pipeline.batch_at(self.step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt, self.comp, metrics = self._step_fn(
                self.params, self.opt, self.comp, batch,
                jnp.asarray(self.step, jnp.int32))
            rec = {k: float(v) for k, v in metrics.items()}
            rec["step"] = self.step
            self.history.append(rec)
            self.step += 1
            if self.ckpt is not None and self.step % self.ckpt_every == 0:
                self.ckpt.save_async(self.step, self.state_tree())
        if self.ckpt is not None:
            self.ckpt.wait_async()
        return self.history
