"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
the appropriate step function (train_step / prefill / decode_step) is
lowered with explicit in/out shardings onto the production mesh
(single-pod 16x16 and multi-pod 2x16x16), compiled, and its
``memory_analysis()`` / ``cost_analysis()`` + collective-bytes breakdown
(parsed from the compiled HLO) are written to ``results/dryrun/*.json`` —
the inputs to the §Roofline analysis.

Run as a script, it asks XLA for 512 virtual CPU devices before JAX first
initializes its backend (the device count is fixed from then on); importing
the module changes nothing.

Usage:
  python -m repro.launch.dryrun --arch tinyllama_1p1b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (SHAPES, ARCH_IDS, applicable_shapes, get_config)
from repro.distributed import sharding as sh
from repro.launch.mesh import (CHIPS_PER_POD, HBM_BW, ICI_LINK_BW,
                               PEAK_FLOPS_BF16, make_production_mesh)
from repro.models.registry import build_model
from repro.serve.engine import make_serve_fns
from repro.train.loop import TrainConfig, abstract_init, make_train_fn

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\(?([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u64": 8, "s64": 8,
                "u32": 4, "s32": 4, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result bytes of every collective op in the compiled HLO."""
    out: dict[str, float] = {op: 0.0 for op in COLLECTIVE_OPS}
    counts: dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        if not any(op in line for op in COLLECTIVE_OPS):
            continue
        m = _SHAPE_RE.match(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        if "-start" in line and f"{op}-start" not in line:
            pass
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        out[op] += nbytes
        counts[op] += 1
    out_counts = {f"n_{k}": v for k, v in counts.items()}
    return {**out, **out_counts}


def _ns_tree(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def with_layers(cfg, n: int):
    """Same architecture with a reduced layer count (roofline two-point
    extrapolation: XLA cost analysis counts a scan body ONCE, so totals are
    reconstructed from two depths: body=(f(2u)-f(u))/u, total=f0+L*body)."""
    import dataclasses
    changes: dict = {"num_layers": n}
    if cfg.family == "encdec":
        changes.update(encoder_layers=max(1, n // 2),
                       decoder_layers=max(1, n // 2))
    return dataclasses.replace(cfg, **changes)


def layer_unit(cfg) -> int:
    """Layer-count granularity that keeps the arch's group structure valid."""
    if cfg.attention == "local_global":
        return cfg.group_size
    if cfg.family == "hybrid":
        return cfg.attn_every
    if cfg.family == "encdec":
        return 2
    return 1


def lower_cell(arch: str, shape_name: str, mesh, *, fsdp: bool = True,
               microbatch: int = 1, layers: int | None = None):
    """Lower the cell's step fn.  Returns (lowered, meta)."""
    cfg = get_config(arch)
    if layers is not None:
        cfg = with_layers(cfg, layers)
    api = build_model(cfg)
    shape = SHAPES[shape_name]
    specs = api.input_specs(shape)
    pshapes, axes = abstract_init(api)

    if shape.kind == "train":
        from repro.optim import AdamWState
        tcfg = TrainConfig(microbatch=microbatch, fsdp=fsdp)
        step = make_train_fn(api, tcfg)
        pspecs = sh.param_specs(axes, mesh, cfg, fsdp=fsdp)
        pspecs = sh.sanitize_tree(pspecs, pshapes, mesh)
        opt_specs = AdamWState(P(), pspecs, pspecs)
        bspecs = sh.batch_specs(mesh, shape, cfg)
        in_b = {k: bspecs.get(k, P(sh.dp_axes(mesh), None)) for k in specs}
        in_b = sh.sanitize_tree(in_b, specs, mesh)
        in_sh = (_ns_tree(mesh, pspecs), _ns_tree(mesh, opt_specs), None,
                 _ns_tree(mesh, in_b), NamedSharding(mesh, P()))
        out_sh = (_ns_tree(mesh, pspecs), _ns_tree(mesh, opt_specs), None,
                  _ns_tree(mesh, {"loss": P(), "grad_norm": P(), "lr": P()}))
        f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
        opt_shapes = AdamWState(
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.tree_util.tree_map(f32, pshapes),
            jax.tree_util.tree_map(f32, pshapes))
        stepno = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        lowered = fn.lower(pshapes, opt_shapes, None, specs, stepno)
    elif shape.kind == "prefill":
        prefill_jit, _ = make_serve_fns(api, mesh, axes, shape)
        fn = prefill_jit(specs)
        lowered = fn.lower(pshapes, specs)
    else:  # decode
        _, decode_jit = make_serve_fns(api, mesh, axes, shape)
        fn = decode_jit(specs["cache"])
        lowered = fn.lower(pshapes, specs["cache"], specs["kv_len"],
                           specs["token"])
    return lowered, {"arch": arch, "shape": shape_name, "kind": shape.kind,
                     "cfg": cfg}


def analyze(lowered, compiled, mesh, cfg, shape_name) -> dict:
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    nchips = 1
    for v in mesh.shape.values():
        nchips *= v
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    coll_total = sum(v for k, v in coll.items() if not k.startswith("n_"))
    # Per-chip roofline terms (seconds). cost_analysis is per-device on SPMD.
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_acc / HBM_BW
    collective_s = coll_total / ICI_LINK_BW
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        model_flops = 6 * cfg.active_param_count() * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        model_flops = 2 * cfg.active_param_count() * shape.global_batch * shape.seq_len
    else:
        model_flops = 2 * cfg.active_param_count() * shape.global_batch
    out = {
        "nchips": nchips,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_acc,
        "collective_bytes_per_chip": coll_total,
        "collectives": coll,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": max(("compute", compute_s), ("memory", memory_s),
                        ("collective", collective_s), key=lambda t: t[1])[0],
        "model_flops_global": model_flops,
        "useful_flops_ratio": (model_flops / (flops * nchips)
                               if flops else 0.0),
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size_bytes":
                getattr(mem, "generated_code_size_in_bytes", 0),
        },
    }
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, outdir: str,
             *, fsdp: bool = True, microbatch: int = 1,
             verbose: bool = True, layers: int | None = None) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "status": "ok", "layers_override": layers,
                 "fsdp": fsdp, "microbatch": microbatch}
    try:
        # Batch-pinned activations help train/prefill (big activations,
        # FSDP weights) but hurt decode, where activations are tiny and the
        # cheap plan gathers THEM, not the 2D-sharded weights; decode mode
        # keeps only the KV-cache layout pins.  Per-arch pin_prefill lets
        # GLA-recurrence archs opt out for prefill (EXPERIMENTS §Perf).
        kind = SHAPES[shape_name].kind
        cfg0 = get_config(arch)
        mode = ("decode" if kind == "decode"
                or (kind == "prefill" and not cfg0.pin_prefill) else "train")
        with mesh, sh.activation_sharding_scope(mesh, mode):
            lowered, meta = lower_cell(arch, shape_name, mesh, fsdp=fsdp,
                                       microbatch=microbatch, layers=layers)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            rec.update(analyze(lowered, compiled, mesh, meta["cfg"],
                               shape_name))
            rec["lower_s"] = round(t_lower, 2)
            rec["compile_s"] = round(t_compile, 2)
            if verbose:
                print(compiled.memory_analysis())
                ca = compiled.cost_analysis()
                print({k: ca[k] for k in ("flops", "bytes accessed")
                       if k in ca})
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    os.makedirs(outdir, exist_ok=True)
    suffix = f"__L{layers}" if layers is not None else ""
    path = os.path.join(outdir,
                        f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    if verbose:
        dom = rec.get("dominant", "-")
        print(f"[{rec['status']}] {arch} x {shape_name} x {mesh_kind} "
              f"dominant={dom} ({time.time() - t0:.1f}s)")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--layers", type=int, default=None,
                    help="override layer count (roofline extrapolation)")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCH_IDS:
            for sname, status in applicable_shapes(arch).items():
                if status == "run":
                    cells.append((arch, sname))
                else:
                    rec = {"arch": arch, "shape": sname, "status": "skipped",
                           "reason": status}
                    os.makedirs(args.out, exist_ok=True)
                    for mk in (["single", "multi"] if args.mesh == "both"
                               else [args.mesh]):
                        with open(os.path.join(
                                args.out,
                                f"{arch}__{sname}__{mk}.json"), "w") as f:
                            json.dump(dict(rec, mesh=mk), f, indent=1)
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = 0
    for arch, sname in cells:
        for mk in meshes:
            rec = run_cell(arch, sname, mk, args.out,
                           fsdp=not args.no_fsdp,
                           microbatch=args.microbatch, layers=args.layers)
            failures += rec["status"] == "error"
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
