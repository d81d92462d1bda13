"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Selects an architecture config, builds the mesh-aware train step, and runs
steps with DDS checkpointing and the ring-prefetched pipeline.  On a real
TPU slice, mesh axes map onto the pod topology via ``make_production_mesh``;
on CPU the test mesh is used and widths can be scaled down.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.data.pipeline import BatchSpec, TokenPipeline
from repro.launch.device import device_report, enable_compile_cache
from repro.models.registry import build_model
from repro.storage.checkpoint import CheckpointManager
from repro.train.loop import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="tinyllama_1p1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--compress-pod-grads", action="store_true")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    api = build_model(cfg)
    print(f"arch={cfg.name} family={cfg.family} "
          f"params~{cfg.param_count() / 1e9:.2f}B "
          f"devices={device_report()} compile_cache={cache_dir}")

    pipeline = TokenPipeline(BatchSpec(args.batch, args.seq, cfg.vocab_size),
                             seed=0)
    tcfg = TrainConfig(peak_lr=args.lr, warmup_steps=max(2, args.steps // 10),
                       total_steps=args.steps, microbatch=args.microbatch,
                       compress_pod_grads=args.compress_pod_grads)
    trainer = Trainer(api, tcfg, pipeline, ckpt_every=args.ckpt_every)
    trainer.ckpt = CheckpointManager.sized_for(trainer.state_tree(), keep=3)
    if trainer.restore_latest():
        print(f"resumed at step {trainer.step}")
    t0 = time.time()
    hist = trainer.run(args.steps)
    dt = time.time() - t0
    print(f"{args.steps} steps in {dt:.1f}s; "
          f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
