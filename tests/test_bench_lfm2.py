"""A rehearsal of the benchmark's LFM2-24B-A2B cell off the chip, at a
test size with files of its own (``benchmark/tests/BENCHMARK.tiny_lfm2
.json``): the harness end to end to ``correct``, the counts against a
hand count and the full-size counts against ISSUE 37's, and each new
per-layer metric's reader on a made-up trace and on the recorded one."""

import importlib
import json
import math
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "benchmark", "tests")
TINY = os.path.join(TESTS, "BENCHMARK.tiny_lfm2.json")
CELL = "lfm2_tiny.tiny_train_bs2_seq32"
FULL_CELL = "lfm2_24b_a2b_ep8.train_bs1_seq8192"
CONV, FULL = "conv", "full_attention"

counts = importlib.import_module("benchmark.counts.lfm2_24b_a2b_ep8")
ref = importlib.import_module("benchmark.reference.lfm2_24b_a2b_ep8")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny():
    return (_load(TESTS, "configs", "lfm2_tiny.json"),
            _load(TESTS, "traffic", "tiny_train_bs2_seq32.json"))


def full():
    return (_load(ROOT, "benchmark", "configs", "lfm2_24b_a2b_ep8.json"),
            _load(ROOT, "benchmark", "traffic", "train_bs1_seq8192.json"))


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}").read


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
    # one attention layer of three, two expert layers
    assert result["kernel_paths"]["flash_attention"] == {"ref": 1}
    assert "moe_grouped_matmul" in result["kernel_paths"]
    json.dumps(result)


def test_traced_run_reports_the_programs_counters_and_no_device_metric(
        result):
    # off the TPU the device metrics' readers find nothing and return
    # nothing; the expert layers' own counters are there
    assert set(result["metrics"]) == {
        "data_wait_ms", "compiles_in_window", "moe_load_max_over_mean"}
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0


@pytest.mark.parametrize("fault", ["control", "half", "half_tokens"])
def test_the_control_and_the_faults_are_not_correct(fault):
    """The program's bfloat16 path in the float32 test cell's place, half
    of a batch's rows left out, and half of every row's positions (the
    fault for a cell whose batch is one row)."""
    from benchmark import control, run
    from benchmark.probes import half_tokens  # noqa: F401 - registers it
    out = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                       control=(fault == "control"),
                       tamper=control.FAULTS.get(fault))
    assert out["correct"] is False and out["failed"] == 0


def test_counts_against_a_hand_count():
    cfg, mix = tiny()
    # per token, forward MACs, 32 tokens, hidden 64. A conv operator: W_in
    # 64*192 + W_out 64*64 = 16384, the three taps 3*64 = 192. The
    # attention layer (4 heads of 16 over 2 key-value heads): projections
    # 2*64*64 + 2*64*32 = 12288, core 4 heads * 16 keys * 2*16 = 2048.
    # Dense SwiGLU 3*64*96 = 18432. Expert layers (2): router 64*8 = 512,
    # routed 2 * 4/8 = 1 expert = 3*64*48 = 9216. The tied head 64*96.
    macs = (2 * (16384 + 192) + 12288 + 2048 + 18432
            + 2 * (512 + 9216) + 6144)
    assert macs == 91520
    assert counts.forward_macs_per_token(cfg, 32) == macs
    assert counts.step_flops_per_sample(cfg, mix) == 3 * 2 * macs * 32
    # parameters: the reference's trained leaves are the count; the tied
    # table once
    assert counts.param_count(cfg) == sum(
        math.prod(shape) for shape, kind in ref.leaves(cfg).values()
        if kind != "static")
    assert counts.param_count(cfg) == (
        96 * 64 + 64 + 3 * 2 * 64 + 2 * (16384 + 192) + 12288 + 2 * 16
        + 18432 + 2 * (512 + 4 * 9216))
    # the conv operators, batch 2 (64 tokens), two layers: the products'
    # FLOPs times three; bytes: 15 T d + 12 d^2 elements of 2 bytes
    conv = counts.short_conv(cfg, mix, 2)
    assert conv["flops"] == 2 * 3 * 2 * 64 * 16384
    assert conv["bytes"] == 2 * 2 * (15 * 64 * 64 + 12 * 64 * 64)
    # the one full core: q, o, dO, dq at 4 heads, k, v, dk, dv at 2
    core = counts.attn_core(cfg, mix, 2, FULL)
    assert core["flops"] == 3 * 2 * 2 * 32 * 2048
    q, k = 2 * 32 * 64 * 2, 2 * 32 * 2 * 16 * 2
    assert core["bytes"] == 6 * q + 6 * k
    assert counts.attn_core(cfg, mix, 2, "sliding_attention") == {
        "flops": 0.0, "bytes": 0.0}
    experts = counts.moe_experts(cfg, mix, 2)
    rows = 2 * 32 * 2 * 4 / 8
    assert experts["flops"] == 3 * 2 * 2 * rows * 9216
    assert experts["bytes"] == 2 * (3 * 4 * 9216 * 2
                                    + 3 * rows * (128 + 144) * 2)


def test_the_full_size_counts_are_the_issues():
    cfg, mix = full()
    assert counts.param_count(cfg) == 469_284_992
    macs = counts.forward_macs_per_token(cfg, 8192)
    assert macs == 202_924_032
    assert round(counts.step_flops_per_sample(cfg, mix) / 1e12, 2) == 9.97
    # a conv layer's products, forward and backward: about 825 GFLOP,
    # 4.2 ms at the peak, bound by FLOPs (the bytes are 0.7 ms a layer)
    conv = counts.short_conv(cfg, mix, 1)
    assert round(conv["flops"] / 4 / 1e9) == 825
    assert round(1e3 * conv["flops"] / 4 / 197e12, 1) == 4.2
    assert conv["bytes"] / 819e9 < conv["flops"] / 197e12 / 5
    # the core at a head of 64: 0.82 TFLOP forward and backward
    core = counts.attn_core(cfg, mix, 1, FULL)
    assert round(core["flops"] / 1e12, 2) == 0.82
    # a held expert sees 512 rows a step under a uniform router
    args = cfg["model"]["args"]
    assert 8192 * args["num_experts_per_tok"] / args["num_experts"] == 512
    # every published width is in the file as published, top level and
    # the builder's arguments alike
    for key, value in {"hidden_size": 2048, "intermediate_size": 11776,
                       "moe_intermediate_size": 1536,
                       "num_attention_heads": 32, "num_key_value_heads": 8,
                       "num_experts_per_tok": 4, "conv_L_cache": 3,
                       "norm_eps": 1e-5, "routed_scaling_factor": 1,
                       "conv_bias": False, "norm_topk_prob": True,
                       "use_expert_bias": True}.items():
        assert cfg[key] == value == args[key], key
    assert cfg["rope_parameters"] == args["rope_parameters"] \
        == {"rope_theta": 1000000, "rope_type": "default"}
    assert args["num_experts"] == 64 == cfg["published"]["num_experts"]
    assert cfg["num_experts"] == args["experts_held"] == 8
    assert args["expert_offset"] == 8
    assert cfg["vocab_size"] == args["vocab_size"] == 65536 // 8
    assert cfg["num_dense_layers"] == args["num_dense_layers"] == 1
    # layer_types stays whole at the top; the builder gets one leading
    # dense layer and published layers 2-5, one whole period
    assert len(cfg["layer_types"]) == 40 and cfg["num_hidden_layers"] == 5
    assert cfg["layer_types"].count(CONV) == 30
    assert args["layer_types"] == [cfg["layer_types"][0]] \
        + cfg["layer_types"][2:6] == [CONV, FULL, CONV, CONV, CONV]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size", "num_dense_layers"}
    assert next(iter(cfg["assumed"])) == "tied_head"
    assert {"conv_thirds", "qk_norm", "router", "final_norm", "sequence",
            "optimizer", "weights"} <= set(cfg["assumed"])


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench = _load(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[FULL_CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "train_bs1_seq8192"
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    assert set(config["reduced"]) == set(full()[0]["reduced"])
    listed = {m["name"] for m in bench["per_layer"]
              if FULL_CELL in m.get("workloads", [])}
    assert len(listed) == 25
    assert {"short_conv_roofline", "short_conv_core_ms", "attn_qk_norm_ms",
            "attn_full_core_roofline", "attn_proj_ms", "attn_rope_ms",
            "attn_out_ms", "recompute_ms", "moe_experts_roofline",
            "moe_ffn_ms", "moe_load_max_over_mean", "moe_dispatch_ms",
            "moe_combine_ms", "step_mfu_pct", "device_idle_pct",
            "moe_buffer_fill"} <= listed
    assert not {"mla_core_roofline", "attn_window_core_roofline",
                "swa_tile_waste", "lstm_seq_roofline"} & listed
    for name in ("short_conv_roofline", "short_conv_core_ms",
                 "attn_qk_norm_ms"):
        m = {m["name"]: m for m in bench["per_layer"]}[name]
        # the Mellum2 cell has q/k norms too
        assert m["workloads"] == [FULL_CELL] + (
            ["mellum2_12b_a2b5_ep8.train_bs2_seq8192"]
            if name == "attn_qk_norm_ms" else [])
        assert m["moves"] == "samples_per_s"
        assert m["source"] == "device_trace"
    limits = _load(ROOT, "benchmark", "cells", FULL_CELL + ".json")["limits"]
    assert limits["nonfinite_costs"] == 0 and "grad_diff" in limits


class _Window:
    steps = 10
    at_open = {"steps": 6, "moe_rows_max": 100.0, "moe_rows_mean": 80.0,
               "moe_experts_active": 24.0}
    at_close = {"steps": 16, "moe_rows_max": 3100.0,
                "moe_rows_mean": 2580.0, "moe_experts_active": 64.0}


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
AGAIN = "/checkpoint/rematted_computation"
OPS = [
    (STEP + "jvp(blk0_sconv)/sconv_in/dot_general", 0.010),
    (STEP + "jvp(blk0_sconv)/sconv_core/mul", 0.002),
    (STEP + "jvp(blk2_sconv)/sconv_out/dot_general", 0.008),
    (STEP + "transpose(jvp(blk2_sconv))/jvp(blk2_sconv)" + AGAIN
     + "/sconv_core/mul", 0.003),
    (STEP + "transpose(jvp(blk0_sconv))/jvp(blk0_sconv)/checkpoint"
     "/sconv_core/mul", 0.007),
    (STEP + "transpose(jvp(blk0_sconv))/jvp(blk0_sconv)/checkpoint"
     "/sconv_in/dot_general", 0.020),
    (STEP + "jvp(blk1_attn)/attn_qk_norm/rsqrt", 0.004),
    (STEP + "transpose(jvp(blk1_attn))/jvp(blk1_attn)" + AGAIN
     + "/attn_qk_norm/mul", 0.005),
    (STEP + "jvp(blk1_attn)/attn_core/pallas_call", 0.010),
    (STEP + "transpose(jvp(blk1_attn))/checkpoint/attn_core/pallas_call",
     0.030),
    (STEP + "jvp(blk1_attn)/attn_qkv/dot_general", 0.5),
    (STEP + "jvp(blk1_moe)/moe_experts/pallas_call", 0.004),
    (STEP + "jvp(res2a_conv)/conv_general_dilated", 0.9),   # a ResNet's
    (STEP + "jvp(blk0_mlp)/dot_general", 0.7),
]


def test_each_new_metrics_reader_on_a_made_up_trace():
    from benchmark import peaks
    ctx = _context(OPS)

    def share(work, seconds):
        least, _ = peaks.least_seconds(work["flops"], work["bytes"],
                                       ctx["peak"])
        return 100.0 * least * _Window.steps / seconds

    # the whole layer's time, forward, again and backward, and no other
    # layer's: a ResNet's `conv` is not an `sconv`
    assert reader("short_conv_roofline")(ctx) == pytest.approx(
        share(counts.short_conv(ctx["cfg"], ctx["mix"], 2), 0.050))
    assert reader("short_conv_core_ms")(ctx) == pytest.approx(
        1e3 * 0.012 / 10)
    assert reader("attn_qk_norm_ms")(ctx) == pytest.approx(1e3 * 0.009 / 10)
    # the accepted readers find this model's attention and expert layers
    assert reader("attn_full_core_roofline")(ctx) == pytest.approx(
        share(counts.attn_core(ctx["cfg"], ctx["mix"], 2, FULL), 0.040))
    assert reader("attn_proj_ms")(ctx) == pytest.approx(1e3 * 0.5 / 10)
    assert reader("recompute_ms")(ctx) == pytest.approx(1e3 * 0.008 / 10)
    assert reader("moe_experts_roofline")(ctx) == pytest.approx(
        share(counts.moe_experts(ctx["cfg"], ctx["mix"], 2, rows=250 * 4,
                                 active=4), 0.004))
    assert reader("moe_load_max_over_mean")(ctx) == pytest.approx(1.2)
    assert reader("mla_core_roofline")(ctx) is None
    assert reader("attn_window_core_roofline")(ctx) is None


def test_the_readers_are_silent_where_there_is_nothing_to_read():
    """A program without the layer or the scope (the parent commit,
    another model: the recorded LSTM trace), counts without
    ``short_conv`` (another configuration's), or no chip: nothing to
    read, nothing raised."""
    from benchmark import trace_reduce
    ctx = _context(OPS)
    recorded = trace_reduce.reduce_file(
        os.path.join(TESTS, "tiny.xplane.pb"), chips=1,
        scopes=_load(TESTS, "tiny.scopes.json"))
    assert recorded.scope_seconds(r"jvp\(lstm\d+\)") > 0
    laguna = importlib.import_module("benchmark.counts.laguna_xs2_ep32")
    for name in ("short_conv_roofline", "short_conv_core_ms",
                 "attn_qk_norm_ms"):
        assert reader(name)(dict(ctx, trace=recorded)) is None, name
        assert reader(name)(dict(ctx, trace=None, peak=None)) is None, name
    assert reader("short_conv_roofline")(dict(ctx, counts=laguna)) is None
    # a Laguna layer has no q/k normalisation: its trace has no such scope
    bare = _context([(STEP + "jvp(blk1_swa)/attn_rope/mul", 1.0)])
    assert reader("attn_qk_norm_ms")(bare) is None
