"""One cell of the chip benchmark, run once: the parts ``run.py`` drives.

A cell is found by name.  ``BENCHMARK.json`` names its configuration and its
traffic mix; everything else is a file of its own that the harness finds by
a name:

    <config file>                   sizes as run, the program they map to,
                                    and the name of the plain reference
    benchmarks/chip/configs/<ref>.py   that reference
    benchmarks/chip/traffic/<mix>.json the mix, read by ``traffic.py``
    benchmarks/chip/cells/<cell>.json  slots, cache length, the sample the
                                    comparison takes, and its limit
    benchmarks/chip/metrics/<metric>.py  one reader per per-layer metric
    benchmarks/chip/peaks.json      the device's peaks, by device kind

The program under test is driven only through ``make_serve_fns``: its
prefill and decode programs, compiled at the cell's shapes on the cell's
mesh.  The loop around them is the benchmark's own client.  The weights and
the prompts come from the seed.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from benchmarks.chip import counts
from benchmarks.chip import trace as tr
from benchmarks.chip.traffic import ClosedBatches

BENCH = Path("benchmarks") / "chip"
GAP_BLOCK = 16            # a decode gap is read over 16 consecutive tokens:
                          # a host-clock reading spans 250 ms or more
SPAN = "bench."           # prefix of the host spans the trace reduction reads
AHEAD = 2                 # decode steps dispatched beyond the one whose
                          # tokens the host fetches, so that the chip
                          # decodes through a host stall of up to two steps.
                          # Each step in flight holds a whole cache of its
                          # own (the step copies its cache); at sc2-decode's
                          # size a 16 GiB chip holds three, so the second
                          # step's dispatch waits until the step being
                          # fetched has ended, and a third would hold back
                          # the fetch itself.

# Configuration-file key -> the program's ModelConfig field it must equal.
PROGRAM_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "head_dim": "hd", "rope_theta": "rope_theta", "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "num_experts", "num_experts_per_tok": "top_k",
    "capacity_factor": "capacity_factor",
}
PROGRAM_NAMES = {"mlp": ("mlp", {"gelu_tanh": "gelu", "swiglu": "swiglu"}),
                 "norm": ("norm", {"layer_norm": "ln", "rms_norm": "rms"})}


class BenchError(Exception):
    """A run that cannot give a result: it prints no result line."""


# ---------------------------------------------------------------------------
# The cell, found by name.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    mix: dict
    sizes: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def dims(self) -> counts.Dims:
        return counts.Dims.from_config(self.config)

    def reference(self):
        return _load_module(self.root / BENCH / "configs" /
                            f"{self.config['reference']}.py")

    def reader(self, metric: str):
        return _load_module(self.root / BENCH / "metrics" / f"{metric}.py")


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def _load_module(path: Path):
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: Path, name: str) -> Cell:
    spec = _load_json(root / "BENCHMARK.json")
    work = [w for w in spec["workloads"] if w["name"] == name]
    if not work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    work = work[0]
    conf = [c for c in spec["configs"] if c["name"] == work["config"]][0]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name) and m["moves"] in names]
    return Cell(root=root, name=name, chips=work["chips"],
                config=_load_json(root / conf["file"]),
                mix=_load_json(root / BENCH / "traffic" /
                               f"{work['traffic']}.json"),
                sizes=_load_json(root / BENCH / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


# ---------------------------------------------------------------------------
# The program and the device.
# ---------------------------------------------------------------------------


def import_program(root: Path) -> None:
    """Put the checkout's own program first on the path; fail without it."""
    src = root / "src"
    if not (src / "repro" / "serve" / "engine.py").is_file():
        raise BenchError(f"the program is not in this checkout ({src})")
    sys.path.insert(0, str(src))


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless JAX_COMPILATION_CACHE_DIR says otherwise), keeping every
    program, so that a later run of the cell compiles nothing."""
    import jax

    from repro.launch.device import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_accelerator(report: dict) -> None:
    if report["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {report['platform']} devices")


def devices_for(cell: Cell, peaks_path: Path):
    """The cell's devices, a report of them as JAX names them, and the
    device kind's row of the peaks table."""
    import jax

    from repro.launch.device import device_report

    devs = jax.devices()
    report = device_report()
    print(f"device: platform {report['platform']}, kind {report['kind']}, "
          f"count {report['count']}", file=sys.stderr, flush=True)
    require_accelerator(report)
    if len(devs) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips, JAX "
                         f"found {len(devs)}")
    peaks = _load_json(peaks_path)
    if report["kind"] not in peaks:
        raise BenchError(f"device kind {report['kind']!r} is not in "
                         f"{peaks_path.name}")
    return devs[:cell.chips], report, peaks[report["kind"]]


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: the named
    architecture with the file's overrides.  Every size the file states is
    checked against what the program will run."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    for key, field in PROGRAM_FIELDS.items():
        if key in config and getattr(cfg, field) != config[key]:
            raise BenchError(f"{key} is {config[key]} in the configuration "
                             f"file but {getattr(cfg, field)} in the program")
    for key, (field, names) in PROGRAM_NAMES.items():
        if names.get(config[key]) != getattr(cfg, field):
            raise BenchError(f"{key} {config[key]!r} is not the program's "
                             f"{getattr(cfg, field)!r}")
    return cfg


def _param_values(path, leaf, key, vocab: int, layers: int):
    """Weights for one leaf, by its name: norms near their identity, biases
    small, matrices with unit-variance outputs, and embedding rows or
    output columns past the true vocabulary zero (a padded checkpoint).
    The projections back into the residual stream (``wo`` of attention and
    of the MLP or experts) are scaled by 1/sqrt(2 * layers), as GPT-2
    initialises them: at unit scale the random layers' outputs pile up into
    one direction shared by every position, and greedy decoding settles on
    a few tokens whose lead no rounding can change."""
    import jax
    import jax.numpy as jnp

    name = str(path[-1].key)
    z = jax.random.normal(key, leaf.shape, jnp.float32)
    if name in ("norm1", "norm2", "final_norm"):       # RMSNorm: x * (1 + w)
        x = 0.1 * z
    elif name in ("norm1_w", "norm2_w"):
        x = 1.0 + 0.1 * z
    elif name in ("norm1_b", "norm2_b", "bq", "bk", "bv"):
        x = 0.02 * z
    elif name == "embed":
        x = jnp.where(jnp.arange(leaf.shape[0])[:, None] < vocab, z, 0.0)
    elif name == "unembed":
        x = jnp.where(jnp.arange(leaf.shape[1])[None, :] < vocab,
                      z * leaf.shape[0] ** -0.5, 0.0)
    elif name == "wo":
        x = z * (leaf.shape[-2] * 2 * layers) ** -0.5
    else:                                              # (..., fan_in, out)
        x = z * leaf.shape[-2] ** -0.5
    return x.astype(leaf.dtype)


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key holding all 64 bits of the seed."""
    seed %= 1 << 64
    return np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)


class Session:
    """The cell's programs, compiled at its shapes on its mesh: the
    program's prefill and decode step from ``make_serve_fns``, as
    ``chip_smoke.serve_session`` builds them, and two small programs of the
    benchmark's own: the weights from a seed, and greedy argmax over the
    true vocabulary."""

    def __init__(self, cell: Cell, devices):
        import jax
        import jax.numpy as jnp

        from repro.configs import ShapeConfig
        from repro.distributed import sharding as sh
        from repro.launch.mesh import make_mesh
        from repro.models.registry import build_model
        from repro.serve.engine import make_serve_fns
        from repro.train.loop import abstract_init

        self.cell = cell
        self.slots = cell.sizes["slots"]
        self.cache_len = cell.sizes["cache_len"]
        self.prompt_len = cell.mix["prompt_len"]
        self.gen_len = cell.mix["gen_len"]
        if self.prompt_len + self.gen_len > self.cache_len:
            raise BenchError("prompt and generated tokens overrun the cache")
        window = cell.config.get("sliding_window")
        if window and self.prompt_len + self.gen_len > window:
            raise BenchError("the context passes the sliding window, which "
                             "the program does not apply")
        mcfg = program_config(cell.config)
        api = build_model(mcfg)
        mesh_shape = tuple(cell.config["mesh"])
        if int(np.prod(mesh_shape)) != cell.chips:
            raise BenchError(f"mesh {mesh_shape} is not {cell.chips} chips")
        mesh = make_mesh(mesh_shape, ("data", "model"), devices=devices)
        B, S = self.slots, self.prompt_len
        pshapes, axes = abstract_init(api)
        prefill_jit, decode_jit = make_serve_fns(
            api, mesh, axes, ShapeConfig(cell.name, "prefill", S, B), pshapes)
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        mode = "train" if mcfg.pin_prefill else "decode"
        with mesh, sh.activation_sharding_scope(mesh, mode):
            self.prefill = prefill_jit(batch, cache_len=self.cache_len).lower(
                pshapes, batch).compile()
        cache_like = jax.eval_shape(functools.partial(
            api.prefill, cache_len=self.cache_len), pshapes, batch)[1]
        step = (jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((B, 1), jnp.int32))
        with mesh, sh.activation_sharding_scope(mesh, "decode"):
            self.decode = decode_jit(cache_like).lower(
                pshapes, cache_like, *step).compile()
        self.prefill_module = _module_name(self.prefill)
        self.decode_module = _module_name(self.decode)

        vocab = cell.config["vocab_size"]
        leaves, treedef = jax.tree_util.tree_flatten_with_path(pshapes)

        def make(key):
            return jax.tree_util.tree_unflatten(treedef, [
                _param_values(path, leaf, jax.random.fold_in(key, i), vocab,
                              mcfg.num_layers)
                for i, (path, leaf) in enumerate(leaves)])

        params_sh = self.decode.input_shardings[0][0]
        same = jax.tree.map(lambda a, b, s: a.is_equivalent_to(b, len(s.shape)),
                            self.prefill.input_shardings[0][0], params_sh,
                            pshapes)
        if not all(jax.tree.leaves(same)):
            raise BenchError("prefill and decode lay the weights out "
                             "differently; one copy cannot serve both")
        key_like = jax.ShapeDtypeStruct((2,), jnp.uint32)
        self._make = jax.jit(make, out_shardings=params_sh).lower(
            key_like).compile()
        logits_like = self.decode.out_info[0]
        self.argmax = jax.jit(
            lambda lg: jnp.argmax(lg[:, :vocab], axis=-1).astype(
                jnp.int32)[:, None]).lower(logits_like).compile()

    def weights(self, seed: int):
        """The weights from ``seed``, made on the device in one program."""
        import jax

        return jax.block_until_ready(self._make(seed_key(seed)))


def _module_name(compiled) -> str:
    """The XLA module name that the trace gives the program's executions."""
    first = compiled.as_text().split("\n", 1)[0]       # "HloModule <name>, ..."
    return first.split()[1].rstrip(",")


# ---------------------------------------------------------------------------
# The client: closed-loop batches.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Batch:
    prompts: np.ndarray               # (slots, prompt_len)
    sent: float                       # host time the batch was sent
    times: list[float]                # host time each token step arrived
    tokens: list[np.ndarray]          # (slots,) per step
    kv_lens: list[int]                # kv_len of each decode step


def serve_batch(sess: Session, params, prompts, deadline: float) -> Batch:
    """Prefill ``prompts``, then decode ``gen_len`` tokens greedily.  Each
    step's tokens stay on the device as the next step's input, and the host
    fetches every step's tokens, in order, as a streaming server sends them.
    ``AHEAD`` steps are dispatched beyond the one being fetched, so the chip
    keeps decoding while the host fetches.  Once the deadline has passed no
    further step is dispatched; every step already dispatched is fetched."""
    from jax.profiler import TraceAnnotation

    b = Batch(prompts, time.perf_counter(), [], [], [])
    with TraceAnnotation(SPAN + "prefill"):
        logits, cache = sess.prefill(params, {"tokens": prompts})
    with TraceAnnotation(SPAN + "argmax"):
        toks = [sess.argmax(logits)]
    for i in range(sess.gen_len):
        while (len(toks) < sess.gen_len and len(toks) <= i + AHEAD
               and time.perf_counter() < deadline):
            kv_len = sess.prompt_len + len(toks) - 1
            with TraceAnnotation(SPAN + "decode"):
                logits, cache = sess.decode(params, cache, np.int32(kv_len),
                                            toks[-1])
            with TraceAnnotation(SPAN + "argmax"):
                toks.append(sess.argmax(logits))
            b.kv_lens.append(kv_len)
        if i == len(toks):
            break
        with TraceAnnotation(SPAN + "fetch"):
            b.tokens.append(np.asarray(toks[i])[:, 0])
        b.times.append(time.perf_counter())
    return b


def warm_up(sess: Session, params) -> None:
    """One prefill and one decode step of the cell's shapes: every program
    the window drives has run once before it opens."""
    prompts = np.zeros((sess.slots, sess.prompt_len), np.int32)
    logits, cache = sess.prefill(params, {"tokens": prompts})
    tok = sess.argmax(logits)
    np.asarray(tok)
    logits, cache = sess.decode(params, cache, np.int32(sess.prompt_len), tok)
    np.asarray(sess.argmax(logits))


def serve_window(sess: Session, params, mix: ClosedBatches, seconds: float,
                 batches: int = 1):
    """Batches one after another until ``seconds`` have passed; with 0
    seconds, ``batches`` batches served to their last token (the traced
    window).  The window closes once every step dispatched before its time
    was up has reached the host: all of that work counts, over all of that
    time."""
    from jax.profiler import TraceAnnotation

    served = []
    with TraceAnnotation(SPAN + "window"):
        start = time.perf_counter()
        deadline = start + seconds if seconds else float("inf")
        while (len(served) < batches if not seconds
               else time.perf_counter() < deadline):
            served.append(serve_batch(sess, params, mix.prompts(len(served)),
                                      deadline))
        end = time.perf_counter()
    return served, start, end


def end_to_end(batches: list[Batch], slots: int, start: float, end: float):
    """The window's end-to-end metrics, from host times of tokens that
    arrived inside it.  Every request of a batch shares its batch's times,
    so each batch's readings count ``slots`` times.  Beside them, under
    ``each_gap_p95_ms``, the 95th percentile of single inter-token gaps: a
    diagnostic, printed and not reported."""
    tokens, ttft, blocks, each = 0, [], [], []
    for b in batches:
        times = [t for t in b.times if t <= end]
        tokens += slots * len(times)
        if times:
            ttft.append(times[0] - b.sent)
        gaps = np.diff(times)
        each.extend(gaps)
        for i in range(0, len(gaps) - GAP_BLOCK + 1, GAP_BLOCK):
            blocks.append(float(np.mean(gaps[i:i + GAP_BLOCK])))
    out = {"tokens_per_s": tokens / (end - start)}
    if ttft:
        out["ttft_mean_ms"] = 1e3 * float(np.mean(ttft))
    if blocks:
        out["decode_gap16_p95_ms"] = 1e3 * float(np.percentile(blocks, 95))
    if each:
        out["each_gap_p95_ms"] = 1e3 * float(np.percentile(each, 95))
    return out


# ---------------------------------------------------------------------------
# Correctness: served tokens against the plain reference.
# ---------------------------------------------------------------------------


def batches_for(n: int, slots: int) -> int:
    """Finished batches a sample of ``n`` requests draws from."""
    return -(-n // slots)


def sample(batches: list[Batch], gen_len: int, n: int, seed: int):
    """(prompts, served tokens) of ``n`` finished requests drawn from the
    seed.  Up to a batch's worth, the slots are cut into ``n`` runs of
    neighbours, and from each run one slot of one finished batch is drawn,
    so that every part of the batch is compared; a larger sample, a whole
    number of batches, takes every slot from that many finished batches,
    each a different one."""
    done = [b for b in batches if len(b.tokens) == gen_len]
    if not done:
        raise BenchError("no request finished inside the window")
    slots = done[0].prompts.shape[0]
    per = min(n, slots)
    rounds = n // per
    if n < 1 or n % per or rounds > len(done):
        raise BenchError(f"a sample of {n} from {len(done)} finished "
                         f"batches of {slots} slots")
    rng = np.random.default_rng([seed, 1])
    picks = [(done[b], int(rng.integers(lo, hi)))
             for lo, hi in zip(np.arange(per) * slots // per,
                               np.arange(1, per + 1) * slots // per)
             for b in rng.choice(len(done), rounds, replace=False)]
    prompts = np.stack([b.prompts[s] for b, s in picks])
    served = np.stack([np.stack(b.tokens)[:, s] for b, s in picks])
    return prompts, served


def logit_gaps(ref, tokens):
    """Per position, how far the reference logit of ``tokens`` lies below the
    reference's best; inf for a token outside the vocabulary."""
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens)
    inside = (tokens >= 0) & (tokens < ref.shape[-1])
    got = jnp.take_along_axis(ref, jnp.where(inside, tokens, 0)[..., None],
                              -1)[..., 0]
    return np.asarray(jnp.where(inside, ref.max(-1) - got, jnp.inf))


def compare(cell: Cell, params, prompts, served, *, control: bool = False):
    """Run the reference over each prompt with its served tokens.  Returns,
    per request and served token, the gap of the served token below the
    reference's best and, with ``control``, that of the token the fp8
    reference puts first at the same position."""
    import jax.numpy as jnp

    ref_mod = cell.reference()
    P = prompts.shape[1]
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    kw = dict(prompt_len=P, start=P - 1)
    ref = ref_mod.logits(params, cell.config, tokens, **kw)
    out = {"served": logit_gaps(ref, served)}
    if control:
        low = ref_mod.logits(params, cell.config, tokens, fp8=True, **kw)
        out["control"] = logit_gaps(ref, jnp.argmax(low, -1))
    return out


GAP_STATS = {
    "max_logit_gap": lambda g: float(g.max()),
    "mean_logit_gap": lambda g: float(g.mean()),
}


def gap_stats(gaps) -> dict[str, float]:
    """Every statistic a cell may hold to a limit, over all compared
    tokens: the widest gap and the mean gap."""
    return {name: f(gaps) for name, f in GAP_STATS.items()}


def judge(gaps, limits: dict):
    """(checks, failed): each statistic the cell's ``limits`` name, beside
    its limit, and the compared requests that fail.  A request fails when
    one of its tokens lies further below the reference's best than
    ``max_logit_gap`` allows; a statistic over the whole sample that is
    over its limit fails every compared request."""
    stats = gap_stats(gaps)
    checks = {name: {"value": stats[name], "limit": limit}
              for name, limit in limits.items()}
    if any(c["value"] > c["limit"] for c in checks.values()
           if c is not checks.get("max_logit_gap")):
        return checks, gaps.shape[0]
    if "max_logit_gap" in limits:
        return checks, int((gaps.max(axis=1) > limits["max_logit_gap"]).sum())
    return checks, 0


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric's reader may read."""
    dims: counts.Dims
    peak: dict
    chips: int
    summary: tr.Summary
    prefill_module: str
    decode_module: str
    prefill_hlo: str                      # the compiled prefill's HLO text
    prefills: list[tuple[int, int]]       # (batch, prompt_len) per prefill
    decode_steps: list[tuple[int, int]]   # (batch, kv_len) per decode step
    memory_peak_bytes: int


class CompileCounter:
    """Counts compilations and compile-cache loads while it is armed."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, *args, **kwargs):
        if self.armed and event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax

        self.armed = False
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on)


def memory_peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def per_layer_metrics(cell: Cell, ctx: ReadContext) -> dict[str, dict]:
    """Each of the cell's per-layer metrics, read by its own reader.  The
    cell lists only metrics that its trace holds, so a reader that finds
    nothing is an error: a kernel or program renamed, or counts that no
    longer match the trace."""
    metrics = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(ctx)
        if value is None:
            raise BenchError(f"per-layer metric {m['name']} found nothing "
                             f"to read in this cell's trace")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t0: float) -> dict:
    """One run of one cell; ``t0`` is the host time the process started.
    Returns the result line's object."""
    import jax

    cell = load_cell(root, workload)
    import_program(root)
    devices, report, peak = devices_for(cell, root / BENCH / "peaks.json")
    sess = Session(cell, devices)
    params = sess.weights(seed)
    mix = ClosedBatches(cell.mix, slots=sess.slots,
                        vocab=cell.config["vocab_size"], seed=seed)
    warm_up(sess, params)
    compiles = CompileCounter()

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    compiles.armed = True
    n = cell.sizes["sample_requests"]
    batches, start, end = serve_window(sess, params, mix,
                                       0.0 if trace else seconds,
                                       batches_for(n, sess.slots))
    compiles.close()
    if trace:
        jax.profiler.stop_trace()
    setup_s = start - t0
    if compiles.count:
        print(f"warning: {compiles.count} compilations inside the window",
              file=sys.stderr)
    mem_peak = memory_peak_bytes(devices)
    report["memory_peak_bytes"] = mem_peak

    metrics: dict[str, dict] = {}
    result: dict = {}
    if trace:
        try:
            summary = tr.summarize(tr.load(tr.find_xplane(log_dir)))
        except (ValueError, FileNotFoundError) as e:
            raise BenchError(f"trace: {e}") from e
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        ctx = ReadContext(
            dims=cell.dims, peak=peak, chips=cell.chips, summary=summary,
            prefill_module=sess.prefill_module,
            decode_module=sess.decode_module,
            prefill_hlo=sess.prefill.as_text(),
            prefills=[(sess.slots, sess.prompt_len)] * len(batches),
            decode_steps=[(sess.slots, k) for b in batches for k in b.kv_lens],
            memory_peak_bytes=mem_peak)
        metrics = per_layer_metrics(cell, ctx)
        report["busy_s"] = summary.busy_s
        report["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    else:
        values = end_to_end(batches, sess.slots, start, end)
        values["setup_s"] = setup_s
        if "each_gap_p95_ms" in values:
            print(f"diagnostic each_gap_p95_ms {values['each_gap_p95_ms']!r}",
                  file=sys.stderr)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise BenchError(f"the window gave no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    attempted = sess.slots * len(batches)
    del sess
    prompts, served = sample(batches, mix.gen_len, n, seed)
    gaps = compare(cell, params, prompts, served)["served"]
    checks, failed = judge(gaps, cell.sizes["limits"])
    result = {"correct": failed == 0, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": report,
              **result, "checks": checks}
    return result
