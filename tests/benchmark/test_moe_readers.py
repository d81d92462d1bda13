"""The expert layer's readers (``moe_scopes.py`` and the metrics
``moe_decode_hbm_share``, ``moe_prefill_mfu`` and ``moe_route_ms``):
synthetic operations against a hand-written program text that names the
layer's scopes, the counts at Granite's published widths, and a tiny
Granite cell run through the harness, untraced and with a trace made up
from its compiled programs."""

import os
import shutil
import sys
import time
import types

import pytest

from benchmarks.chip import counts
from benchmarks.chip import harness
from benchmarks.chip import moe_scopes
from benchmarks.chip import scopes
from benchmarks.chip import trace as tr

sys.path.insert(0, os.path.dirname(__file__))
import tiny_cell  # noqa: E402

PLANE = "/device:TPU:0"
READERS = ("moe_decode_hbm_share", "moe_prefill_mfu", "moe_route_ms")

# A scan over layers whose body routes (``moe_route``), runs a grouped
# matmul (``moe_experts``: a fusion without metadata takes its ROOT's, and
# a TPU's kernel carries only the compiler's name for it) from a copy of
# the layer's expert weights (``layers``, by its shape ``moe_weights``) and
# combines (``moe_combine``), beside an operation of ``moe`` alone and one
# of ``attn_core``.
HLO = """HloModule jit_f, is_scheduled=true, entry_computation_layout={(bf16[4]{0})->bf16[4]{0}}

%fused_computation (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  ROOT %tanh.1 = bf16[4]{0} tanh(%param_0), metadata={op_name="jit(f)/layers/while/body/closed_call/moe/moe_experts/tanh"}
}

%body (p.1: (s32[], bf16[4])) -> (s32[], bf16[4]) {
  %p.1 = (s32[], bf16[4]{0}) parameter(0)
  %sort.2 = bf16[4]{0} sort(%p.1), metadata={op_name="jit(f)/layers/while/body/closed_call/moe/moe_route/sort"}
  %fusion.3 = bf16[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation
  %ragged-dot-none.4 = bf16[4]{0} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %gather.5 = bf16[4]{0} gather(%p.1), metadata={op_name="jit(f)/layers/while/body/closed_call/moe/moe_combine/gather"}
  %reduce.6 = bf16[4]{0} reduce(%p.1), metadata={op_name="jit(f)/layers/while/body/closed_call/moe/reduce"}
  %custom-call.7 = bf16[4]{0} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/layers/while/body/closed_call/attn_core/pallas_call"}
  %dynamic-slice_bitcast_fusion.11 = bf16[40,1536,512]{2,1,0:T(8,128)(2,1)S(1)} fusion(%p.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/layers/while/body/squeeze"}
  ROOT %tuple.8 = (s32[], bf16[4]{0}) tuple(%p.1, %fusion.3)
}

ENTRY %main.9 (a.1: bf16[4]) -> bf16[4] {
  %a.1 = bf16[4]{0} parameter(0), metadata={op_name="a"}
  ROOT %while.10 = (s32[], bf16[4]{0}) while(%a.1), condition=%cond, body=%body, metadata={op_name="jit(f)/layers/while"}
}
"""

# One execution, (op, start, duration) in ns: self times layers 15,
# moe_route 10, moe_experts 20 + 30, moe_combine 5, moe 5, attn_core 10,
# moe_weights 5.
STEP = [("while.10", 0, 100), ("sort.2", 10, 10), ("fusion.3", 20, 20),
        ("ragged-dot-none.4", 40, 30), ("gather.5", 70, 5),
        ("reduce.6", 75, 5), ("custom-call.7", 80, 10),
        ("dynamic-slice_bitcast_fusion.11", 90, 5)]
SELF = {"layers": 15, "moe_route": 10, "moe_experts": 50, "moe_combine": 5,
        "moe": 5, "attn_core": 10, "moe_weights": 5}
GRANITE = {
    "num_hidden_layers": 32, "hidden_size": 1536, "num_attention_heads": 24,
    "num_key_value_heads": 8, "head_dim": 64, "intermediate_size": 512,
    "vocab_size": 49155, "num_local_experts": 40, "num_experts_per_tok": 8,
    "mlp": "swiglu", "qkv_bias": False, "norm": "rms_norm",
    "dtype": "bfloat16"}


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def summary(executions: dict[str, int]):
    mods, ops, t = [], [], 0
    for module, n in executions.items():
        for _ in range(n):
            mods.append(ev(f"{module}(1)", t, 100))
            ops.extend((module, ev(f"%{op} = x", t + s, d))
                       for op, s, d in STEP)
            t += 200
    return tr.Summary(window_s=t / 1e9, busy_s=0.0, shift_ns=0.0,
                      modules={PLANE: mods}, ops={PLANE: ops},
                      device_ops=[], idle_gaps=[])


def context(executions, hlo=HLO, prefills=None, decode_steps=None):
    return types.SimpleNamespace(
        dims=counts.Dims.from_config(GRANITE), chips=1,
        peak={"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
        summary=summary(executions), prefill_module="jit_prefill",
        decode_module="jit_decode", prefill_hlo=hlo, decode_hlo=hlo,
        prefills=[(16, 1020)] * (prefills if prefills is not None
                                 else executions.get("jit_prefill", 0)),
        decode_steps=[(16, 1100)] * (
            decode_steps if decode_steps is not None
            else executions.get("jit_decode", 0)))


def reader(name):
    return harness.load_cell(tiny_cell.REPO, "granite-decode").reader(name)


def test_each_operation_takes_its_innermost_expert_scope():
    weights = moe_scopes.weight_shapes(counts.Dims.from_config(GRANITE))
    by_op = moe_scopes.op_labels(HLO, weights)
    assert by_op["dynamic-slice_bitcast_fusion.11"] == "moe_weights"
    assert moe_scopes.op_labels(HLO)[
        "dynamic-slice_bitcast_fusion.11"] == "layers"
    assert by_op["sort.2"] == "moe_route"
    assert by_op["fusion.3"] == by_op["ragged-dot-none.4"] == "moe_experts"
    assert by_op["gather.5"] == "moe_combine"
    assert by_op["reduce.6"] == "moe"
    assert by_op["custom-call.7"] == "attn_core"
    assert by_op["while.10"] == "layers"
    # scopes.py counts them all as the layer they lie in
    assert {scopes.op_scopes(HLO)[op] for op in
            ("sort.2", "fusion.3", "gather.5", "reduce.6")} == {"moe"}


def test_self_times_by_label():
    weights = moe_scopes.weight_shapes(counts.Dims.from_config(GRANITE))
    times = moe_scopes.label_times(summary({"jit_f": 3}), "jit_f", HLO,
                                   weights)
    assert times == {k: pytest.approx(3 * v / 1e9) for k, v in SELF.items()}


def test_counts_at_granite_widths():
    d = counts.Dims.from_config(GRANITE)
    # the 38.9 of 40 experts that 16 tokens touch, 32 layers: 5.87 GB
    assert counts.expected_experts(d, 16) == pytest.approx(38.87, abs=0.01)
    assert moe_scopes.expert_bytes(d, 16) == pytest.approx(5.87e9, rel=1e-3)
    # 16 x 1020 tokens, 8 experts each: 19.7 TFLOP of the prefill's 28.0
    flops = moe_scopes.expert_flops(d, 16, 1020)
    assert flops == 32 * 2 * 16 * 1020 * 8 * 3 * 1536 * 512
    assert flops / counts.prefill_flops(d, 16, 1020) == pytest.approx(
        0.70, abs=0.01)


def test_reader_values():
    ctx = context({"jit_prefill": 2, "jit_decode": 6})
    d = ctx.dims
    # the grouped matmuls: moe_experts 50 and moe_weights 5 ns
    hbm = 100 * moe_scopes.expert_bytes(d, 16) / (55e-9 * 8.19e11)
    mfu = 100 * moe_scopes.expert_flops(d, 16, 1020) / (55e-9 * 1.97e14)
    want = {"moe_decode_hbm_share": hbm, "moe_prefill_mfu": mfu,
            "moe_route_ms": 20e-6}          # moe_route, moe_combine, moe
    for name in READERS:
        assert reader(name).read(ctx) == pytest.approx(want[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_program_without_the_expert_scopes(name):
    hlo = HLO.replace('op_name="ragged-dot-none"', 'op_name="custom-call"')
    for scope in moe_scopes.SUBSCOPES:
        hlo = hlo.replace(f"/{scope}/", "/")
    assert reader(name).read(context({"jit_prefill": 2,
                                      "jit_decode": 6}, hlo)) is None
    # executions that are not the run's
    assert reader(name).read(context({"jit_prefill": 2, "jit_decode": 6},
                                     prefills=3, decode_steps=5)) is None


# A Granite-like cell at a size the CPU holds: the tiny expert
# configuration with the family's multipliers, dropless, and its plain
# reference.  The limit is from CPU readings over seeds 1 to 12, one batch
# of 8 each: the program's mean gap at most 2.5e-5, the fp8 control's at
# least 1.5e-4.
TINY_GRANITE = dict(
    tiny_cell.MOE, name="tiny-granite", capacity_factor=None,
    reference="granite_moe_ref", embedding_multiplier=12.0,
    attention_multiplier=0.05, residual_multiplier=0.22, logits_scaling=6.0,
    program={"arch": "granite_moe_3b_a800m", "overrides": dict(
        tiny_cell.MOE["program"]["overrides"], embedding_multiplier=12.0,
        attention_multiplier=0.05, residual_multiplier=0.22,
        logits_scaling=6.0, capacity_factor=None)})
TINY_LIMITS = {"mean_logit_gap": 1e-4}


def _granite_root(tmp_path, monkeypatch, metrics=(tiny_cell.METRIC,)):
    monkeypatch.setitem(tiny_cell.LIMITS, TINY_GRANITE["name"], TINY_LIMITS)
    root = tiny_cell.make_root(tmp_path, TINY_GRANITE, metrics=metrics)
    bench = tiny_cell.REPO / tiny_cell.BENCH
    shutil.copy(bench / "configs" / "granite_moe_ref.py",
                root / tiny_cell.BENCH / "configs")
    for m in metrics:
        shutil.copy(bench / "metrics" / f"{m['name']}.py",
                    root / tiny_cell.BENCH / "metrics")
    monkeypatch.setattr(harness, "require_accelerator", lambda report: None)
    return root


def test_a_tiny_granite_cell_is_correct(tmp_path, monkeypatch):
    root = _granite_root(tmp_path, monkeypatch)
    result = harness.run(root, "tiny", 11, 0.5, False, time.perf_counter())
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"tokens_per_s", "ttft_mean_ms",
                                      "decode_gap16_p95_ms", "setup_s"}


def _made_up_trace(sess, batches):
    """One operation of each label of each compiled program per
    execution, 10 ns each, one after another."""
    runs = {sess.prefill_module: (sess.prefill, len(batches)),
            sess.decode_module: (sess.decode,
                                 sum(len(b.kv_lens) for b in batches))}
    mods, ops, t = [], [], 0
    for module, (compiled, n) in runs.items():
        one = {}
        for op, lab in sorted(moe_scopes.op_labels(
                compiled.as_text()).items()):
            one.setdefault(lab, op)
        for _ in range(n):
            mods.append(ev(f"{module}(1)", t, 10 * len(one)))
            ops.extend((module, ev(f"%{op} = x", t + 10 * i, 10))
                       for i, op in enumerate(one.values()))
            t += 10 * len(one) + 5
    return tr.Summary(window_s=t / 1e9, busy_s=0.0, shift_ns=0.0,
                      modules={PLANE: mods}, ops={PLANE: ops},
                      device_ops=[], idle_gaps=[])


def test_a_traced_tiny_granite_run_reads_the_expert_metrics(
        tmp_path, monkeypatch, capsys):
    spec = {m["name"]: m for m in harness._load_json(
        tiny_cell.REPO / "BENCHMARK.json")["per_layer"]}
    metrics = [{k: v for k, v in spec[n].items() if k != "workloads"}
               for n in READERS]
    root = _granite_root(tmp_path, monkeypatch, metrics)
    seen = {}
    serve_window = harness.serve_window

    def window(sess, *args):
        out = serve_window(sess, *args)
        seen["trace"] = _made_up_trace(sess, out[0])
        seen["slots"], seen["prompt"] = sess.slots, sess.prompt_len
        return out

    monkeypatch.setattr(harness, "serve_window", window)
    monkeypatch.setattr(harness.tr, "find_xplane", lambda log_dir: log_dir)
    monkeypatch.setattr(harness.tr, "load", lambda path: None)
    monkeypatch.setattr(harness.tr, "summarize", lambda t: seen["trace"])
    result = harness.run(root, "tiny", 5, 0.0, True, time.perf_counter())
    assert result["correct"], result["checks"]
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(got) == set(READERS) and all(v > 0 for v in got.values())
    # one operation of 10 ns each of moe_route, moe_combine (and moe, where
    # the program has one) a decode step
    assert got["moe_route_ms"] in (pytest.approx(2e-5), pytest.approx(3e-5))
    d = counts.Dims.from_config(TINY_GRANITE)
    mfu = 100 * moe_scopes.expert_flops(d, seen["slots"], seen["prompt"]) / (
        10e-9 * 1e12)
    assert got["moe_prefill_mfu"] == pytest.approx(mfu)
    err = capsys.readouterr().err
    lines = [ln.split()[2] for ln in err.split("\n")
             if ln.startswith("diagnostic moe_scopes")]
    assert lines == ["jit_prefill", "jit_decode"]

