"""The harness at a size the CPU holds: a cell built from files found by
name, the comparison that decides ``correct``, its fp8 control, and a run
with the timed path broken underneath.  The look for a chip is skipped
here by replacing ``harness.require_accelerator``; everything after it runs
as on the chip."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.traffic import ClosedBatches

sys.path.insert(0, os.path.dirname(__file__))
import tiny_cell  # noqa: E402

REPO = tiny_cell.REPO
CONFIGS = {"dense": tiny_cell.DENSE, "moe": tiny_cell.MOE}


@pytest.fixture
def no_chip_check(monkeypatch):
    monkeypatch.setattr(harness, "require_accelerator", lambda report: None)


def _run(root, seed=11, seconds=0.5):
    return harness.run(root, "tiny", seed, seconds, False, time.perf_counter())


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_cell_built_from_files_found_by_name(tmp_path, no_chip_check, kind):
    root = tiny_cell.make_root(tmp_path, CONFIGS[kind])
    cell = harness.load_cell(root, "tiny")
    assert [m["name"] for m in cell.per_layer] == ["peak_hbm_gib"]
    result = _run(root)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "ttft_mean_ms",
                                      "decode_gap16_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == jax.devices()[0].platform


def _with_fault(fault):
    """``make_serve_fns`` whose decode step is broken by ``fault``."""
    import repro.serve.engine as engine

    make = engine.make_serve_fns

    def broken(*args, **kwargs):
        prefill_jit, decode_jit = make(*args, **kwargs)

        def decode_broken(cache_like):
            step = decode_jit(cache_like)

            def f(params, cache, kv_len, token):
                logits, new = step(params, cache, kv_len, token)
                return fault(logits, new, cache)

            return jax.jit(f)

        return prefill_jit, decode_broken

    return broken


FAULTS = {
    # the step returns its state (the KV cache) unchanged
    "state_unchanged": lambda lg, new, old: (lg, old),
    # a token altered where it is produced
    "token_altered": lambda lg, new, old: (lg.at[:, 7].add(100.0), new),
    # half of the batch left out: its logits never computed
    "half_batch": lambda lg, new, old: (lg.at[lg.shape[0] // 2:].set(0),
                                        new),
}


STATS = sorted(tiny_cell.LIMITS["tiny-dense"])


@pytest.mark.parametrize("stat", STATS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(tmp_path, no_chip_check,
                                          monkeypatch, fault, stat):
    import repro.serve.engine as engine

    root = tiny_cell.make_root(tmp_path, tiny_cell.DENSE, stats=[stat])
    monkeypatch.setattr(engine, "make_serve_fns", _with_fault(FAULTS[fault]))
    result = _run(root)
    assert not result["correct"]
    assert result["failed"] > 0
    check = result["checks"][stat]
    assert check["value"] > check["limit"]


@pytest.fixture(scope="module")
def control_gaps(tmp_path_factory):
    """Per seed, the gaps of the program's served tokens and of the tokens
    the fp8 control puts first, over one batch served to its last token
    and sampled as a run samples it."""
    root = tiny_cell.make_root(tmp_path_factory.mktemp("control"))
    cell = harness.load_cell(root, "tiny")
    sess = harness.Session(cell, jax.devices()[:1])
    out = []
    for seed in (3, 4, 5):
        params = sess.weights(seed)
        mix = ClosedBatches(cell.mix, slots=sess.slots,
                            vocab=cell.config["vocab_size"], seed=seed)
        batch = harness.serve_batch(sess, params, mix.prompts(0),
                                    deadline=float("inf"))
        prompts, served = harness.sample([batch], mix.gen_len,
                                         cell.sizes["sample_requests"], seed)
        out.append(harness.compare(cell, params, prompts, served,
                                   control=True))
    return out


@pytest.mark.parametrize("stat", STATS)
def test_fp8_control_fails_the_limit(control_gaps, stat):
    limits = {stat: tiny_cell.LIMITS["tiny-dense"][stat]}
    for gaps in control_gaps:
        assert harness.judge(gaps["served"], limits)[1] == 0
        assert harness.judge(gaps["control"], limits)[1] > 0


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "sc2-decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for path in spec["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [*spec["command"], "--workload", spec["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_device_kind_is_an_error(tmp_path, no_chip_check):
    root = tiny_cell.make_root(tmp_path, tiny_cell.DENSE)
    (root / tiny_cell.BENCH / "peaks.json").write_text(
        json.dumps({"TPU v5 lite": {}}))
    cell = harness.load_cell(root, "tiny")
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.devices_for(cell, root / tiny_cell.BENCH / "peaks.json")


@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (REPO / "BENCHMARK.json").read_text())["workloads"]])
def test_every_cell_finds_its_files_and_matches_the_program(cell):
    c = harness.load_cell(REPO, cell)
    assert c.reference().logits
    for m in c.per_layer:
        assert c.reader(m["name"]).read
    harness.program_config(c.config)      # raises where sizes disagree
    assert c.mix["prompt_len"] + c.mix["gen_len"] <= c.sizes["cache_len"]
    assert c.sizes["limits"]
    for name, limit in c.sizes["limits"].items():
        assert name in harness.GAP_STATS and limit > 0


def test_program_config_refuses_a_size_it_would_not_run():
    config = dict(tiny_cell.DENSE, hidden_size=256)
    with pytest.raises(harness.BenchError, match="hidden_size"):
        harness.program_config(config)


def test_prompts_come_from_the_seed():
    mix = {"loop": "closed_batches", "prompt_len": 8, "gen_len": 4}
    big = 2 ** 31 + 12345
    a = ClosedBatches(mix, slots=3, vocab=50, seed=big)
    b = ClosedBatches(mix, slots=3, vocab=50, seed=big)
    c = ClosedBatches(mix, slots=3, vocab=50, seed=big + 1)
    assert np.array_equal(a.prompts(2), b.prompts(2))
    assert not np.array_equal(a.prompts(2), c.prompts(2))
    assert a.prompts(0).shape == c.prompts(0).shape == (3, 8)
    assert harness.seed_key(big).tolist() == [0, 2 ** 31 + 12345]
    assert harness.seed_key(2 ** 33 + 1).tolist() == [2, 1]


def test_end_to_end_metrics_from_host_times():
    slots = 2
    b = harness.Batch(prompts=np.zeros((slots, 4), np.int32), sent=0.0,
                      times=[0.5 + 0.01 * i for i in range(40)],
                      tokens=[], kv_lens=[])
    out = harness.end_to_end([b], slots, start=0.0, end=0.8)
    # tokens up to t = 0.8: 31 steps of 2 slots, over 0.8 s.
    assert out["tokens_per_s"] == pytest.approx(31 * 2 / 0.8)
    assert out["ttft_mean_ms"] == pytest.approx(500.0)
    # 30 gaps of 10 ms: one full block of 16.
    assert out["decode_gap16_p95_ms"] == pytest.approx(10.0)
    assert out["each_gap_p95_ms"] == pytest.approx(10.0)
    # one slow gap (170 ms) lifts its block's mean by a sixteenth of its
    # excess: blocks of 20 and 10 ms, whose 95th percentile is 19.5.
    b.times[5:] = [t + 0.16 for t in b.times[5:]]
    out = harness.end_to_end([b], slots, start=0.0, end=2.0)
    assert out["decode_gap16_p95_ms"] == pytest.approx(19.5)


@pytest.fixture(scope="module")
def tiny_session(tmp_path_factory):
    root = tiny_cell.make_root(tmp_path_factory.mktemp("ahead"))
    cell = harness.load_cell(root, "tiny")
    sess = harness.Session(cell, jax.devices()[:1])
    params = sess.weights(7)
    mix = ClosedBatches(cell.mix, slots=sess.slots,
                        vocab=cell.config["vocab_size"], seed=7)
    return sess, params, mix.prompts(0)


def test_steps_dispatched_ahead_serve_the_same_tokens(tiny_session,
                                                      monkeypatch):
    sess, params, prompts = tiny_session
    served = {}
    for ahead in (0, 1, 5, sess.gen_len):
        monkeypatch.setattr(harness, "AHEAD", ahead)
        b = harness.serve_batch(sess, params, prompts, float("inf"))
        assert len(b.tokens) == len(b.times) == sess.gen_len
        assert b.kv_lens == [sess.prompt_len + i
                             for i in range(sess.gen_len - 1)]
        assert all(t1 >= t0 for t0, t1 in zip(b.times, b.times[1:]))
        served[ahead] = np.stack(b.tokens)
    assert all(np.array_equal(s, served[0]) for s in served.values())


def test_a_passed_deadline_dispatches_nothing_more(tiny_session):
    sess, params, prompts = tiny_session
    b = harness.serve_batch(sess, params, prompts, float("-inf"))
    # the prefill was sent before the deadline was looked at; its token
    # is fetched, and no decode step is dispatched
    assert len(b.tokens) == len(b.times) == 1 and b.kv_lens == []


def test_a_traced_window_serves_the_batches_its_sample_needs(tiny_session):
    sess, params, _ = tiny_session
    mix = ClosedBatches(tiny_cell.MIX, slots=sess.slots, vocab=512, seed=3)
    n = 3 * sess.slots
    batches, start, end = harness.serve_window(
        sess, params, mix, 0.0, harness.batches_for(n, sess.slots))
    assert len(batches) == 3 and end >= batches[-1].times[-1] >= start
    assert all(len(b.tokens) == sess.gen_len for b in batches)
    prompts, served = harness.sample(batches, sess.gen_len, n, seed=3)
    assert served.shape == (n, sess.gen_len)
    # every request compared once: no prompt drawn twice
    assert len({p.tobytes() for p in prompts}) == n


def _finished(batches, slots, gen_len):
    return [harness.Batch(prompts=np.full((slots, 3), 100 * i + np.arange(
        slots)[:, None], np.int32), sent=0.0, times=[0.0] * gen_len,
        tokens=[np.arange(slots, dtype=np.int32)] * gen_len, kv_lens=[])
        for i in range(batches)]


@pytest.mark.parametrize("seed", [1, 2, 3, 2 ** 31 + 7])
def test_sample_compares_every_part_of_the_batch(seed):
    slots, n = 16, 4
    prompts, served = harness.sample(_finished(3, slots, 5), 5, n, seed)
    assert prompts.shape == (n, 3) and served.shape == (n, 5)
    # one request from each run of four neighbouring slots
    assert sorted(served[:, 0] // (slots // n)) == list(range(n))
    assert np.array_equal(prompts[:, 0] % 100, served[:, 0])
    again = harness.sample(_finished(3, slots, 5), 5, n, seed)
    assert np.array_equal(again[0], prompts)
    with pytest.raises(harness.BenchError, match="no request finished"):
        harness.sample(_finished(1, slots, 4), 5, n, seed)
    # two batches' worth: every slot from two different finished batches
    prompts, served = harness.sample(_finished(3, slots, 5), 5, 2 * slots,
                                     seed)
    assert sorted(served[:, 0]) == sorted(2 * list(range(slots)))
    assert len({p.tobytes() for p in prompts}) == 2 * slots
    with pytest.raises(harness.BenchError, match="finished batches"):
        harness.sample(_finished(1, slots, 5), 5, 2 * slots, seed)


def test_a_reader_that_finds_nothing_is_an_error(tmp_path):
    nothing = dict(tiny_cell.METRIC, name="nothing_to_read")
    root = tiny_cell.make_root(tmp_path, metrics=[nothing])
    (root / tiny_cell.BENCH / "metrics" / "nothing_to_read.py").write_text(
        "def read(ctx):\n    return None\n")
    cell = harness.load_cell(root, "tiny")
    with pytest.raises(harness.BenchError, match="nothing_to_read"):
        harness.per_layer_metrics(cell, ctx=None)


def test_a_context_past_the_sliding_window_is_refused(tmp_path):
    config = dict(tiny_cell.DENSE, sliding_window=32)
    root = tiny_cell.make_root(tmp_path, config)
    cell = harness.load_cell(root, "tiny")
    with pytest.raises(harness.BenchError, match="sliding window"):
        harness.Session(cell, jax.devices()[:1])


def test_judge_holds_each_named_statistic_to_its_limit():
    gaps = np.array([[0.0, 0.0, 0.3, 0.0], [0.0, 0.01, 0.0, 0.0]])
    checks, failed = harness.judge(gaps, {"max_logit_gap": 0.1})
    assert checks == {"max_logit_gap": {"value": 0.3, "limit": 0.1}}
    assert failed == 1                   # one request holds the 0.3 token
    checks, failed = harness.judge(gaps, {"mean_logit_gap": 0.02,
                                          "max_logit_gap": 0.5})
    assert checks["mean_logit_gap"]["value"] == pytest.approx(0.31 / 8)
    assert failed == 2                   # a sample statistic fails them all
    assert harness.judge(gaps, {"mean_logit_gap": 0.1})[1] == 0
