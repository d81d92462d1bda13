"""A cell at a size the CPU holds, written into a directory as data.

``make_root`` lays out a checkout-shaped directory with its own
``BENCHMARK.json``, configuration, traffic, cell and per-layer metric
files, and a peaks table that knows the CPU, the way a later change adds a
cell: files only.  The harness finds each by name.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[2]
BENCH = Path("benchmarks") / "chip"

DENSE = {
    "name": "tiny-dense", "source": "test", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "mlp": "gelu_tanh", "norm": "layer_norm",
    "qkv_bias": True, "rope_theta": 100000.0, "tie_word_embeddings": False,
    "dtype": "bfloat16", "reference": "decoder_ref", "mesh": [1, 1],
    "program": {"arch": "starcoder2_7b", "overrides": {
        "num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab_size": 512}},
}
MOE = {
    "name": "tiny-moe", "source": "test", "hidden_size": 128,
    "intermediate_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 509, "num_local_experts": 16, "num_experts_per_tok": 4,
    "capacity_factor": 1.25, "mlp": "swiglu", "norm": "rms_norm",
    "qkv_bias": False, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "dtype": "bfloat16", "reference": "decoder_ref", "mesh": [1, 1],
    "program": {"arch": "granite_moe_3b_a800m", "overrides": {
        "num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 32, "d_ff": 64, "vocab_size": 509, "num_experts": 16,
        "top_k": 4}},
}
MIX = {"loop": "closed_batches", "prompt_len": 16, "gen_len": 44}
# Limits from CPU readings over seeds 1 to 25, one batch of 8 each, every
# slot compared (the program's largest reading / the fp8 control's
# smallest): dense, widest gap 0.0336 / 0.0788 and mean gap 0.00022 /
# 0.00171.  With two layers a routing flip moves a large share of the
# output, so the expert configuration's readings do not separate at this
# size: only the dense one carries the control and the faults.
LIMITS = {"tiny-dense": {"max_logit_gap": 0.05, "mean_logit_gap": 0.0007},
          "tiny-moe": {"max_logit_gap": 0.3}}
METRIC = {"name": "peak_hbm_gib", "unit": "GiB", "better": "lower",
          "source": "program_counter", "layer": "device",
          "moves": "tokens_per_s"}
E2E = [{"name": n, "unit": u, "better": b, "bound": 0.05,
        "source": "host_clock"}
       for n, u, b in (("tokens_per_s", "tokens/s", "higher"),
                       ("ttft_mean_ms", "ms", "lower"),
                       ("decode_gap16_p95_ms", "ms", "lower"),
                       ("setup_s", "s", "lower"))]


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def sizes(config: dict, stats=None) -> dict:
    limits = LIMITS[config["name"]]
    return {"slots": 8, "cache_len": 64, "sample_requests": 8,
            "limits": {k: v for k, v in limits.items()
                       if stats is None or k in stats}}


def make_root(tmp: Path, config: dict = DENSE, mix: dict = MIX,
              stats=None, metrics=(METRIC,)) -> Path:
    """Returns the root of a directory that holds one cell, ``tiny``,
    whose limits are those of ``stats`` (all of them by default)."""
    bench = tmp / BENCH
    _write(tmp / "BENCHMARK.json", {
        "configs": [{"name": config["name"], "source": "test",
                     "file": str(BENCH / "configs" / f"{config['name']}.json"),
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny", "config": config["name"],
                       "traffic": "tiny-mix", "chips": 1, "why": "test"}],
        "end_to_end": E2E, "per_layer": list(metrics)})
    _write(bench / "configs" / f"{config['name']}.json", config)
    _write(bench / "traffic" / "tiny-mix.json", mix)
    _write(bench / "cells" / "tiny.json", sizes(config, stats))
    kind = jax.devices()[0].device_kind
    _write(bench / "peaks.json", {kind: {"bf16_flops_per_s": 1e12,
                                         "hbm_bytes_per_s": 1e11,
                                         "source": "test"}})
    for rel in ("configs/decoder_ref.py", "metrics/peak_hbm_gib.py"):
        (bench / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / BENCH / rel, bench / rel)
    (tmp / "src").symlink_to(REPO / "src")
    return tmp
