"""Device time by named scope (``scopes.py``) and the readers built on it:
synthetic operations against a hand-written program text, a traced run
of the tiny cell whose trace is made up from its compiled programs, and a
small trace recorded on a TPU v5e by ``record_scoped_trace.py``; and the
comparison of compiled programs but for metadata (``same_program.py``)."""

import gzip
import os
import shutil
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip import same_program
from benchmarks.chip import scopes
from benchmarks.chip import trace as tr

sys.path.insert(0, os.path.dirname(__file__))
import tiny_cell  # noqa: E402

FIXTURES = Path(__file__).parent / "fixtures"
PLANE = "/device:TPU:0"
READERS = ("decode_cache_ms", "decode_attn_ms", "prefill_attn_ms")

# A program in the form of a compiled one's text: a scan over layers whose
# body holds a fusion without metadata (its ROOT's scope, ``mlp``), the
# scan's own slicing (``layers``), a cache write under ``attn_core`` and
# ``kv_write`` (the innermost wins) and attention (``attn_core``).
HLO = """HloModule jit_f, is_scheduled=true, entry_computation_layout={(bf16[4]{0})->bf16[4]{0}}

%fused_computation (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  ROOT %tanh.1 = bf16[4]{0} tanh(%param_0), metadata={op_name="jit(f)/layers/while/body/closed_call/mlp/tanh" stack_frame_id=3}
}

%body (p.1: (s32[], bf16[4])) -> (s32[], bf16[4]) {
  %p.1 = (s32[], bf16[4]{0}) parameter(0)
  %fusion.1 = bf16[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation
  %dynamic-slice.2 = bf16[4]{0} dynamic-slice(%p.1), metadata={op_name="jit(f)/layers/while/body/dynamic_slice"}
  %fusion.2 = bf16[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/layers/while/body/closed_call/attn_core/kv_write/dynamic_update_slice"}
  %custom-call.3 = bf16[4]{0} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/layers/while/body/closed_call/attn_core/checkpoint/pallas_call"}
  ROOT %tuple.4 = (s32[], bf16[4]{0}) tuple(%p.1, %fusion.1)
}

ENTRY %main.5 (a.1: bf16[4]) -> bf16[4] {
  %a.1 = bf16[4]{0} parameter(0), metadata={op_name="a"}
  %gather.6 = bf16[4]{0} gather(%a.1), metadata={op_name="jit(f)/embed/jit(_take)/gather"}
  %while.7 = (s32[], bf16[4]{0}) while(%gather.6), condition=%cond, body=%body, metadata={op_name="jit(f)/layers/while"}
  %copy.8 = bf16[4]{0} copy(%while.7)
  ROOT %fusion.9 = bf16[4]{0} fusion(%copy.8), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/logits/dot_general;jit(f)/norm/mul"}
}
"""

# One execution's operations, (op, start, duration) in ns from its start:
# self times embed 10, layers 15 + 10, mlp 20, kv_write 30, attn_core 15,
# unscoped 5, logits 5: 110 in all, the union of the intervals.
STEP = [("gather.6", 0, 10), ("while.7", 10, 90), ("fusion.1", 20, 20),
        ("dynamic-slice.2", 40, 10), ("fusion.2", 50, 30),
        ("custom-call.3", 80, 15), ("copy.8", 100, 5), ("fusion.9", 105, 5)]
SELF = {"embed": 10, "layers": 25, "mlp": 20, "kv_write": 30,
        "attn_core": 15, "unscoped": 5, "logits": 5}


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def summary(executions: dict[str, int], step=STEP, period=200):
    """A reduced trace of ``executions`` runs of each module, one after
    another, each running ``step``."""
    mods, ops, spans, t = [], [], [], 0
    for module, n in executions.items():
        for _ in range(n):
            mods.append(ev(f"{module}(1)", t, 110))
            ops.extend((module, ev(f"%{op} = x", t + s, d))
                       for op, s, d in step)
            spans.append(ev("bench.decode", t, 1))
            t += period
    return tr.Summary(window_s=t / 1e9, busy_s=0.0, shift_ns=0.0,
                      modules={PLANE: mods}, ops={PLANE: ops},
                      device_ops=[], idle_gaps=[])


def test_a_fusion_without_metadata_takes_its_roots_scope():
    by_op = scopes.op_scopes(HLO)
    assert by_op["fusion.1"] == "mlp"
    assert by_op["fusion.2"] == "kv_write"           # the innermost wins
    assert by_op["custom-call.3"] == "attn_core"
    assert by_op["dynamic-slice.2"] == by_op["while.7"] == "layers"
    assert by_op["gather.6"] == "embed"
    assert by_op["fusion.9"] == "logits"             # the first of merged
    assert by_op["copy.8"] == by_op["a.1"] == scopes.UNSCOPED


def test_self_times_do_not_count_nested_operations_twice():
    s = summary({"jit_f": 2})
    times = scopes.scope_times(s, "jit_f", HLO)
    assert times == {k: pytest.approx(2 * v / 1e9) for k, v in SELF.items()}
    assert sum(times.values()) == pytest.approx(scopes.busy_s(s, "jit_f"))
    assert scopes.scope_times(s, "jit_other", HLO) == {}


def test_self_times_add_up_to_the_union_when_operations_overlap():
    evs = [ev("p", 0, 100), ev("a", 10, 20), ev("b", 20, 20),  # b leaves a
           ev("c", 150, 10), ev("d", 155, 20)]                 # d leaves c
    assert scopes.self_ns(evs) == [70, 10, 20, 5, 20]
    assert sum(scopes.self_ns(evs)) == tr.covered_ns(evs, 0, 1000)


def context(executions, hlo=HLO, prefills=None, decode_steps=None):
    return types.SimpleNamespace(
        summary=summary(executions), prefill_module="jit_prefill",
        decode_module="jit_decode", prefill_hlo=hlo, decode_hlo=hlo,
        prefills=[(16, 1500)] * (prefills if prefills is not None
                                 else executions.get("jit_prefill", 0)),
        decode_steps=[(16, 1500)] * (
            decode_steps if decode_steps is not None
            else executions.get("jit_decode", 0)))


def reader(name):
    return harness.load_cell(tiny_cell.REPO, "sc2-decode").reader(name)


# per execution: kv_write 30 + layers 25; attn_core 15 (ns, read in ms)
VALUES = {"decode_cache_ms": 55e-6, "decode_attn_ms": 15e-6,
          "prefill_attn_ms": 15e-6}


@pytest.mark.parametrize("name", READERS)
def test_reader_value(name):
    ctx = context({"jit_prefill": 2, "jit_decode": 6})
    assert reader(name).read(ctx) == pytest.approx(VALUES[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_its_scopes_are_absent(name):
    absent = {"decode_cache_ms": ("kv_write", "layers"),
              "decode_attn_ms": ("attn_core",),
              "prefill_attn_ms": ("attn_core",)}[name]
    hlo = HLO
    for scope in absent:
        hlo = hlo.replace(f"/{scope}/", "/elsewhere/").replace(
            f"/{scope}\"", "/elsewhere\"")
    assert reader(name).read(context({"jit_prefill": 2,
                                      "jit_decode": 6}, hlo)) is None
    # executions that are not the run's
    assert reader(name).read(context({"jit_prefill": 2, "jit_decode": 6},
                                     prefills=3, decode_steps=5)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_scopes_reads_zero(name):
    hlo = "\n".join(line for line in HLO.split("\n")
                    if "op_name" not in line or "parameter" in line)
    ctx = context({"jit_prefill": 1, "jit_decode": 3}, hlo)
    assert reader(name).read(ctx) == 0.0


def test_every_scope_the_metrics_know_is_the_programs():
    from repro.models.layers import SCOPES

    assert set(scopes.SCOPES) <= set(SCOPES)


def _made_up_trace(sess, batches):
    """A reduced trace of the window that ``batches`` were served in: one
    operation of each scope of each compiled program per execution, 10 ns
    each, one after another."""
    runs = {sess.prefill_module: (sess.prefill, len(batches)),
            sess.decode_module: (sess.decode,
                                 sum(len(b.kv_lens) for b in batches))}
    mods, ops, t = [], [], 0
    for module, (compiled, n) in runs.items():
        one = {}
        for op, scope in sorted(scopes.op_scopes(compiled.as_text()).items()):
            one.setdefault(scope, op)
        for _ in range(n):
            mods.append(ev(f"{module}(1)", t, 10 * len(one)))
            ops.extend((module, ev(f"%{op} = x", t + 10 * i, 10))
                       for i, op in enumerate(one.values()))
            t += 10 * len(one) + 5
    return tr.Summary(window_s=t / 1e9, busy_s=0.0, shift_ns=0.0,
                      modules={PLANE: mods}, ops={PLANE: ops},
                      device_ops=[], idle_gaps=[])


def test_a_traced_run_reads_the_scope_metrics(tmp_path, monkeypatch, capsys):
    """The whole traced path of a run at the tiny cell's size, the profiler
    trace made up from the compiled programs: the readers find the decode
    step's text through the run that reads them."""
    spec = {m["name"]: m for m in harness._load_json(
        tiny_cell.REPO / "BENCHMARK.json")["per_layer"]}
    metrics = [{k: v for k, v in spec[n].items() if k != "workloads"}
               for n in READERS]
    root = tiny_cell.make_root(tmp_path, metrics=metrics)
    for name in READERS:
        shutil.copy(tiny_cell.REPO / tiny_cell.BENCH / "metrics" /
                    f"{name}.py", root / tiny_cell.BENCH / "metrics")
    seen = {}
    serve_window = harness.serve_window

    def window(sess, *args):
        out = serve_window(sess, *args)
        seen["trace"] = _made_up_trace(sess, out[0])
        return out

    monkeypatch.setattr(harness, "require_accelerator", lambda report: None)
    monkeypatch.setattr(harness, "serve_window", window)
    monkeypatch.setattr(harness.tr, "find_xplane", lambda log_dir: log_dir)
    monkeypatch.setattr(harness.tr, "load", lambda path: None)
    monkeypatch.setattr(harness.tr, "summarize", lambda t: seen["trace"])
    result = harness.run(root, "tiny", 5, 0.0, True, time.perf_counter())
    assert result["correct"]
    got = {n: m["value"] for n, m in result["metrics"].items()}
    # one operation of 10 ns a scope: kv_write and layers, attn_core
    assert got == {"decode_cache_ms": pytest.approx(2e-5),
                   "decode_attn_ms": pytest.approx(1e-5),
                   "prefill_attn_ms": pytest.approx(1e-5)}
    lines = [ln for ln in capsys.readouterr().err.split("\n")
             if ln.startswith("diagnostic scopes")]
    assert [ln.split()[2] for ln in lines] == ["jit_prefill",
                                               "jit_decode"]


def test_same_program_tells_metadata_from_a_change(tmp_path):
    def plain(x):
        return jnp.tanh(x @ x)

    def scoped(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x)

    def other(x):
        return jnp.sin(x @ x)

    x = jnp.ones((8, 8), jnp.float32)
    paths = {}
    for fn in (plain, scoped, other):
        paths[fn] = tmp_path / f"{fn.__name__}.txt.gz"
        with gzip.open(paths[fn], "wt") as f:
            f.write(jax.jit(fn).lower(x).compile().as_text())
    assert same_program.diff(paths[plain], paths[scoped]) == 0
    assert same_program.diff(paths[plain], paths[other]) == 1


@pytest.fixture(scope="module")
def scoped():
    """Three steps of a two-layer scan holding the flash kernel under
    ``attn_core`` and a cache write under ``kv_write``, on one v5e
    (``record_scoped_trace.py``)."""
    return (tr.summarize(tr.load(str(FIXTURES / "scoped_trace.xplane.pb"))),
            (FIXTURES / "scoped_trace.hlo.txt").read_text())


def test_recorded_kernel_lands_in_attn_core(scoped):
    s, hlo = scoped
    kernel = tr.mosaic_ops(hlo, "_fa_kernel")
    by_op = scopes.op_scopes(hlo)
    assert kernel and {by_op[op] for op in kernel} == {"attn_core"}
    seconds, events = s.kernel("jit_step", kernel)
    assert events == 6                          # two layers, three steps
    times = scopes.scope_times(s, "jit_step", hlo)
    # read by hand: the kernel 209.716 us, and the layout changes around it
    assert seconds == pytest.approx(0.000209716)
    assert times["attn_core"] == pytest.approx(0.00022927)


def test_recorded_scan_stacking_lands_in_layers(scoped):
    s, hlo = scoped
    by_op = scopes.op_scopes(hlo)
    updates = {scope: {op for op, sc in by_op.items()
                       if sc == scope and "dynamic-update-slice" in op}
               for scope in ("layers", "kv_write")}
    # each layer of each step: the entry written into the layer's cache,
    # and the layer's cache stacked back by the scan
    for ops in updates.values():
        assert ops and s.kernel("jit_step", ops)[1] == 6
    times = scopes.scope_times(s, "jit_step", hlo)
    assert times["layers"] == pytest.approx(3.7662e-05)
    assert 0 < times["kv_write"] < times["layers"] / 100
    assert times["mlp"] > 0


def test_recorded_self_times_match_busy_time(scoped):
    s, hlo = scoped
    times = scopes.scope_times(s, "jit_step", hlo)
    busy = scopes.busy_s(s, "jit_step")
    assert sum(times.values()) == pytest.approx(busy, rel=0.01)
    assert busy == pytest.approx(s.busy_s, rel=0.01)
    # the while op and its body both ran; counted once, not twice
    ops = sum(e.dur_ns for _, e in s.ops[sorted(s.ops)[0]]) / 1e9
    assert ops > 1.5 * busy
