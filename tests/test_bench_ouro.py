"""A rehearsal of the benchmark's Ouro-2.6B cell off the chip, at a test
size with files of its own (``benchmark/tests/BENCHMARK.tiny_ouro.json``):
the harness end to end to ``correct``, the counts against a hand count
and the full-size counts against ISSUE 33's, and each new per-layer
metric's reader on a made-up trace, on the recorded one and on a
window."""

import importlib
import json
import math
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "benchmark", "tests")
TINY = os.path.join(TESTS, "BENCHMARK.tiny_ouro.json")
CELL = "ouro_tiny.tiny_train_bs2_seq32"
FULL_CELL = "ouro_2_6b_pp6.train_bs1_seq4096"
FULL = "full_attention"

counts = importlib.import_module("benchmark.counts.ouro_2_6b_pp6")
ref = importlib.import_module("benchmark.reference.ouro_2_6b_pp6")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny():
    return (_load(TESTS, "configs", "ouro_tiny.json"),
            _load(TESTS, "traffic", "tiny_train_bs2_seq32.json"))


def full():
    return (_load(ROOT, "benchmark", "configs", "ouro_2_6b_pp6.json"),
            _load(ROOT, "benchmark", "traffic", "train_bs1_seq4096.json"))


@pytest.fixture(scope="module")
def result():
    from benchmark import run
    return run.run_cell(CELL, 2147483659, 0.5, True, bench_file=TINY,
                        on_chip=False)


def test_cell_end_to_end_is_correct(result):
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    for value, limit in result["compared"].values():
        assert value <= limit
    # one dispatch an application of a layer: R x L = 3 x 2
    assert result["kernel_paths"]["flash_attention"] == {"ref": 6}
    json.dumps(result)


def test_traced_run_reports_the_gates_counter_and_no_device_metric(result):
    # off the TPU the device metrics' readers find nothing and return
    # nothing; the program's own counter is there, strictly inside 1..R
    assert set(result["metrics"]) == {
        "data_wait_ms", "compiles_in_window", "loop_exit_step_mean"}
    assert result["metrics"]["loop_exit_step_mean"]["unit"] == "steps"
    assert 1.0 < result["metrics"]["loop_exit_step_mean"]["value"] < 3.0


@pytest.mark.parametrize("fault", ["control", "half", "half_tokens",
                                   "unchanged", "unchanged_host"])
def test_the_control_and_the_faults_are_not_correct(fault):
    from benchmark import control, run
    from benchmark.probes import (  # noqa: F401 - they register themselves
        half_tokens, unchanged_host)
    got = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                       control=(fault == "control"),
                       tamper=control.FAULTS.get(fault))
    assert got["correct"] is False and got["failed"] == 0
    if fault == "half_tokens":
        assert got["compared"]["grad_diff_median"][0] > 0.5
    if fault.startswith("unchanged"):
        assert got["compared"]["change_median"][0] == pytest.approx(1.0)


def test_counts_against_a_hand_count():
    cfg, mix = tiny()
    # per token and pass, forward MACs, 32 tokens. A layer (4 heads of 16
    # over 4 key-value heads, hidden 64): projections 4 * 64 * 64 = 16384,
    # core 4 heads * 16 keys * 2 * 16 = 2048, SwiGLU 3 * 64 * 80 = 15360.
    # A pass: 2 layers + the head 64 * 96 = 6144 + the gate 64. 3 passes.
    layer = 16384 + 2048 + 15360
    macs = 3 * (2 * layer + 6144 + 64)
    assert macs == 221376
    assert counts.forward_macs_per_token(cfg, 32) == macs
    assert counts.step_flops_per_sample(cfg, mix) == 3 * 2 * macs * 32
    # parameters, one copy of each: the reference's leaves are the count
    hand = 2 * (16384 + 15360 + 4 * 64) + 2 * 96 * 64 + 64 + 64 + 1
    assert counts.param_count(cfg) == hand == 76417 == sum(
        math.prod(shape) for shape, _ in ref.leaves(cfg).values())
    # the cores: R x L = 6 applications, batch 2; q, k, v, o alike
    core = counts.attn_core(cfg, mix, 2, FULL)
    assert core["flops"] == 6 * 3 * 2 * 2 * 32 * 2048
    q = 2 * 4 * 32 * 16 * 2
    assert core["bytes"] == 6 * 12 * q
    assert counts.attn_core(cfg, mix, 2, "sliding_attention") \
        == {"flops": 0.0, "bytes": 0.0}
    # the heads: 3 passes of 64 rows through [64, 96] and the gate; the
    # weight (12,288 bytes) charged once a chunk (64 rows in chunks of 16:
    # 4) a pass in each of the three products, its gradient once; a pass's
    # state read and its gradient written
    head = counts.loop_head(cfg, mix, 2)
    assert head["flops"] == 3 * 2 * 3 * 64 * (6144 + 64)
    assert head["bytes"] == 3 * (3 * 4 * 12288 + 2 * 64 * 64 * 2) + 12288


def test_the_full_size_counts_are_the_issues():
    cfg, mix = full()
    args = cfg["model"]["args"]
    assert counts.param_count(cfg) == 436_277_249 == sum(
        math.prod(shape) for shape, _ in ref.leaves(cfg).values())
    assert 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert counts.param_count(cfg) == 8 * 51_388_416 \
        + 2 * 6144 * 2048 + 2048 + 2049
    # about 48 TFLOP a sample, a least step of 245 ms at 197 TFLOP/s
    flops = counts.step_flops_per_sample(cfg, mix)
    assert round(flops / 1e12, 1) == 48.2
    assert round(1e3 * flops / 197e12) == 245
    core = counts.attn_core(cfg, mix, 1, FULL)
    assert core["flops"] == 32 * 3 * 2 * 4096 * (16 * 2048 * 2 * 128)
    # every published width is in the file as published, at the top and
    # in the builder's arguments alike; the loop and theta too
    for key, value in {"hidden_size": 2048, "intermediate_size": 5632,
                       "head_dim": 128, "num_attention_heads": 16,
                       "num_key_value_heads": 16, "total_ut_steps": 4,
                       "rope_theta": 1000000,
                       "rms_norm_eps": 1e-6}.items():
        assert cfg[key] == value == args[key], key
    assert cfg["num_hidden_layers"] == args["num_hidden_layers"] == 8
    assert cfg["vocab_size"] == args["vocab_size"] == 49152 // 8
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "vocab_size": 49152}
    assert cfg["layer_types"] == [FULL] * 48
    assert cfg["early_exit_threshold"] == 1
    assert set(cfg["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert {"sandwich_norms", "final_norm_in_loop", "gate", "loss",
            "sequence", "optimizer", "weights"} <= set(cfg["assumed"])
    assert cfg["optimizer"]["args"]["learning_rate"] == 1e-5


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench = _load(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[FULL_CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "train_bs1_seq4096"
    # appended, not inserted (PR 37 appended one cell after it)
    assert bench["workloads"][4] == entry
    _, mix = full()
    assert (mix["kind"], mix["batch"], mix["seq_len"], mix["pool"],
            mix["mesh"]) == ("train", 1, 4096, 4, None)
    listed = [m["name"] for m in bench["per_layer"]
              if FULL_CELL in m.get("workloads", [])]
    # PR 35 added four that time parts of a layer (``attn_proj_ms``,
    # ``attn_rope_ms``, ``attn_out_ms``, ``recompute_ms``) on this cell too
    assert len(listed) == 18
    assert {"attn_full_core_roofline", "step_mfu_pct", "hbm_peak_gib",
            "device_idle_pct"} <= set(listed)
    own = [m for m in bench["per_layer"] if m["workloads"] == [FULL_CELL]]
    assert [m["name"] for m in own] == ["loop_head_ms",
                                        "loop_exit_step_mean"]
    for m in own:
        assert m["layer"] == "model step"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
    assert not {"mla_core_roofline", "moe_ffn_ms", "swa_tile_waste",
                "attn_window_core_roofline"} & set(listed)
    # the Laguna cell still reports its full cores through the same reader
    full_core = {m["name"]: m for m in bench["per_layer"]}[
        "attn_full_core_roofline"]
    assert full_core["workloads"][:2] == [
        "laguna_xs2_ep32.train_bs1_seq8192", FULL_CELL]
    limits = _load(ROOT, "benchmark", "cells", FULL_CELL + ".json")["limits"]
    assert limits["nonfinite_costs"] == 0 and "grad_diff" in limits


class _Window:
    steps = 10
    at_open = {"steps": 6, "loop_exit_step_mean": 6 * 1.9}
    at_close = {"steps": 16, "loop_exit_step_mean": 6 * 1.9 + 10 * 1.75}


def _context(ops):
    """A made-up traced context: device 0 ran ``ops`` (scope, seconds)
    back to back."""
    from benchmark import peaks, trace_reduce
    cfg, mix = tiny()
    at, made = 0.0, []
    for i, (scope, seconds) in enumerate(ops):
        made.append(trace_reduce.Op(f"fusion.{i}", scope, at, at + seconds))
        at += seconds
    return {"trace": trace_reduce.Reduced(
                [trace_reduce.Device(0, made)], {}, at),
            "counts": counts, "cfg": cfg, "mix": mix, "chips": 1,
            "window": _Window(), "peak": peaks.load("TPU v5 lite")}


STEP = "jit(step)/jit(main)/"
OPS = [
    (STEP + "jvp(ut0_blk0_attn)/attn_core/pallas_call", 0.010),
    (STEP + "transpose(jvp(ut2_blk1_attn))/checkpoint/attn_core/pallas_call",
     0.030),
    (STEP + "jvp(ut1_blk0_attn)/dot_general", 0.5),       # a projection
    (STEP + "jvp(ut1_blk0_mlp)/dot_general", 0.7),
    (STEP + "jvp(out_head)/while/body/dot_general", 0.004),
    (STEP + "jvp(out_head)/loop_gate/logistic", 0.001),
    (STEP + "transpose(jvp(out_head))/while/body/dot_general", 0.009),
    (STEP + "jvp(ut3_out_norm)/mul", 0.002),
    (STEP + "jvp(output)/dot_general", 0.1),     # the inference head: no
]


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}").read


def test_each_new_metrics_reader_on_a_made_up_context(capsys):
    from benchmark import peaks
    ctx = _context(OPS)
    # the cost's layer, forward and backward with the gate's inner scope;
    # not the norms, not the inference head
    assert reader("loop_head_ms")(ctx) == pytest.approx(1e3 * 0.014 / 10)
    said = capsys.readouterr().err
    work = counts.loop_head(ctx["cfg"], ctx["mix"], 2)
    least, bound = peaks.least_seconds(work["flops"], work["bytes"],
                                       ctx["peak"])
    assert f"least {1e3 * least:.3f} ms" in said and bound in said
    assert reader("loop_exit_step_mean")(ctx) == pytest.approx(1.75)
    # the accepted full-core reader finds this model's 'ut<t>_blk<i>_attn'
    work = counts.attn_core(ctx["cfg"], ctx["mix"], 2, FULL)
    least, _ = peaks.least_seconds(work["flops"], work["bytes"],
                                   ctx["peak"])
    assert reader("attn_full_core_roofline")(ctx) == pytest.approx(
        100.0 * least * 10 / 0.040)
    # the other kernels' readers find nothing of theirs here
    for name in ("attn_window_core_roofline", "mla_core_roofline",
                 "moe_experts_roofline", "moe_ffn_ms", "swa_tile_waste",
                 "moe_load_max_over_mean"):
        assert reader(name)(ctx) is None, name


def test_the_readers_are_silent_where_there_is_nothing_to_read():
    """A program without the layer or the counter (the parent commit,
    another model: the recorded LSTM trace), counts without
    ``loop_head`` (another configuration's), or no chip: nothing to
    read, nothing raised."""
    from benchmark import trace_reduce
    from benchmark.window import Window
    ctx = _context(OPS)
    recorded = trace_reduce.reduce_file(
        os.path.join(TESTS, "tiny.xplane.pb"), chips=1,
        scopes=_load(TESTS, "tiny.scopes.json"))
    assert recorded.scope_seconds(r"jvp\(lstm\d+\)") > 0
    assert reader("loop_head_ms")(dict(ctx, trace=recorded)) is None
    laguna = importlib.import_module("benchmark.counts.laguna_xs2_ep32")
    assert reader("loop_head_ms")(dict(ctx, counts=laguna)) is None
    assert reader("loop_head_ms")(dict(ctx, trace=None, peak=None)) is None
    win = Window.__new__(Window)
    win.at_open = {"steps": 0, "data_wait": 0.0}
    win.at_close = {"steps": 4, "data_wait": 0.25}
    assert reader("loop_exit_step_mean")({"window": win}) is None
    win.at_close = {"steps": 4, "loop_exit_step_mean": 7.0}
    assert reader("loop_exit_step_mean")({"window": win}) == 1.75
    win.at_open = win.at_close
    assert reader("loop_exit_step_mean")({"window": win}) is None
