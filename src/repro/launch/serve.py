"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Stands up the continuous-batching scheduler for an architecture (the
reduced same-family config unless ``--no-reduced``) and serves synthetic
requests, reporting decode throughput on the device it ran on and the DDS
KV-paging statistics when --paged is set.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.launch.device import device_report, enable_compile_cache
from repro.models.registry import build_model
from repro.serve.engine import BatchScheduler, PagedKVEngine, Request
from repro.storage.pagestore import PageStore


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="tinyllama_1p1b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (--no-reduced: "
                         "published widths)")
    ap.add_argument("--paged", action="store_true",
                    help="demonstrate DDS KV-block paging")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    dev = device_report()

    cfg = reduced_config(get_config(args.arch)) if args.reduced else \
        get_config(args.arch)
    api = build_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    sched = BatchScheduler(api, params, slots=args.slots,
                           cache_len=args.cache_len)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        sched.submit(Request(rid, rng.integers(0, cfg.vocab_size, size=4),
                             max_new=args.max_new))
    t0 = time.time()
    done = steps = 0
    while done < args.requests and steps < 10_000:
        done += sched.step()
        steps += 1
    dt = time.time() - t0
    toks = args.requests * args.max_new
    print(f"arch={cfg.name}: {args.requests} requests x {args.max_new} "
          f"tokens over {args.slots} slots: {steps} steps, "
          f"{toks / dt:,.0f} tok/s on {dev['count']} x {dev['kind']} "
          f"({dev['platform']}; compile cache {cache_dir})")

    if args.paged:
        store = PageStore(page_size=4096, num_pages=256)
        eng = PagedKVEngine(store, block_bytes=2048, hbm_blocks=8)
        for blk in range(24):
            eng.put_block(0, 0, blk, bytes(2048))
        for blk in range(4):
            eng.get_block(0, 0, blk)
        print(f"kv paging: spills={eng.spills} offload_fetches={eng.fetches} "
              f"hbm_hits={eng.hits}")


if __name__ == "__main__":
    main()
