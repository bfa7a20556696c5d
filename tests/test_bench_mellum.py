"""A rehearsal of the benchmark's Mellum2-12B-A2.5B cell off the chip, at
a test size with files of its own (``benchmark/tests/BENCHMARK.tiny_
mellum2.json``): the harness end to end to ``correct``, the counts
against a hand count and the full-size counts against the cut's table,
and the two new per-layer metrics' readers on a made-up trace and on the
recorded one."""

import importlib
import json
import math
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "benchmark", "tests")
TINY = os.path.join(TESTS, "BENCHMARK.tiny_mellum2.json")
CELL = "mellum2_tiny.tiny_train_bs2_seq32"
FULL_CELL = "mellum2_12b_a2b5_ep8.train_bs2_seq8192"
SLIDING, FULL = "sliding_attention", "full_attention"

counts = importlib.import_module("benchmark.counts.mellum2_12b_a2b5_ep8")
ref = importlib.import_module("benchmark.reference.mellum2_12b_a2b5_ep8")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny():
    return (_load(TESTS, "configs", "mellum2_tiny.json"),
            _load(TESTS, "traffic", "tiny_train_bs2_seq32.json"))


def full():
    return (_load(ROOT, "benchmark", "configs", "mellum2_12b_a2b5_ep8.json"),
            _load(ROOT, "benchmark", "traffic", "train_bs2_seq8192.json"))


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
    # four attention layers through the one entry, four expert layers
    assert sum(result["kernel_paths"]["flash_attention"].values()) == 4
    assert "moe_grouped_matmul" in result["kernel_paths"]
    json.dumps(result)


def test_traced_run_reports_the_programs_counters_and_no_device_metric(
        result):
    """Off the TPU the device metrics' readers find nothing and return
    nothing; the program's own counters are there: the balancing term
    near 1 at a fresh router, and the window's tiling (32 tokens, a
    window of 8 in tiles of 8: 7 tiles a head, 36 + 24 * 8 visible
    pairs)."""
    assert set(result["metrics"]) == {
        "data_wait_ms", "compiles_in_window", "moe_load_max_over_mean",
        "swa_tile_waste", "moe_balance_ratio"}
    assert 1.0 <= result["metrics"]["moe_balance_ratio"]["value"] < 1.5
    assert result["metrics"]["swa_tile_waste"]["value"] == pytest.approx(
        7 * 64 / (36 + 24 * 8))


@pytest.mark.parametrize("fault", ["control", "half", "half_tokens"])
def test_the_control_and_the_faults_are_not_correct(fault):
    """The program's bfloat16 path in the float32 test cell's place, half
    of a batch's rows left out, and half of every row's positions."""
    from benchmark import control, run
    from benchmark.probes import half_tokens  # noqa: F401 - registers it
    out = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                       control=(fault == "control"),
                       tamper=control.FAULTS.get(fault))
    assert out["correct"] is False and out["failed"] == 0


def test_counts_against_a_hand_count():
    cfg, mix = tiny()
    # per token, forward MACs, 32 tokens, hidden 64, 8 heads of 16 over
    # one key-value head. Projections 2*64*128 + 2*64*16 = 18432 a layer;
    # cores: the full layer 8 heads * 16 keys * 2*16 = 4096, a sliding
    # layer 8 * (8 - 64/64) * 32 = 1792. Expert layers (4): router 64*8 =
    # 512, routed 4 * 4/8 = 2 experts * 3*64*24 = 9216. The head 64*96.
    macs = (4 * 18432 + 4096 + 3 * 1792 + 4 * (512 + 9216) + 6144)
    assert macs == 128256
    assert counts.forward_macs_per_token(cfg, 32) == macs
    assert counts.step_flops_per_sample(cfg, mix) == 3 * 2 * macs * 32
    # parameters: the reference's trained leaves; the q/k scales 2 x 16
    assert counts.param_count(cfg) == sum(
        math.prod(shape) for shape, kind in ref.leaves(cfg).values()
        if kind != "static")
    assert counts.param_count(cfg) == (
        2 * 96 * 64 + 64 + 4 * (18432 + 2 * 64 + 2 * 16 + 512 + 4 * 4608))
    # the cores, batch 2: q, o, dO, dq at 8 heads, k, v, dk, dv at one
    q, k = 2 * 8 * 32 * 16 * 2, 2 * 1 * 32 * 16 * 2
    full_core = counts.attn_core(cfg, mix, 2, FULL)
    assert full_core == {"flops": 3 * 2 * 2 * 32 * 4096.0,
                         "bytes": 6.0 * q + 6 * k}
    window = counts.attn_core(cfg, mix, 2, SLIDING)
    assert window == {"flops": 3 * 3 * 2 * 2 * 32 * 1792.0,
                      "bytes": 3 * (6.0 * q + 6 * k)}
    experts = counts.moe_experts(cfg, mix, 2)
    rows = 2 * 32 * 4 * 4 / 8
    assert experts["flops"] == 4 * 3 * 2 * rows * 4608
    assert experts["bytes"] == 4 * (3 * 4 * 4608 * 2
                                    + 3 * rows * (128 + 72) * 2)


def test_the_full_size_counts_are_the_cuts_table():
    cfg, mix = full()
    assert counts.param_count(cfg) == 340_350_208
    assert round(counts.step_flops_per_sample(cfg, mix) / 8192 / 1e9,
                 2) == 1.17
    assert round(2 * counts.step_flops_per_sample(cfg, mix) / 1e12,
                 1) == 19.2
    # a full core sees 4.3 times a sliding core's pairs a head
    full_core = counts.attn_core(cfg, mix, 2, FULL)
    window = counts.attn_core(cfg, mix, 2, SLIDING)
    assert round(full_core["flops"] / (window["flops"] / 3), 1) == 4.3
    # a held expert sees 2,048 rows a step under a uniform router
    args = cfg["model"]["args"]
    assert 2 * 8192 * args["num_experts_per_tok"] \
        / args["num_experts"] == 2048
    # every published width in the file as published, top level and the
    # builder's arguments alike
    for key, value in {"hidden_size": 2304, "moe_intermediate_size": 896,
                       "num_attention_heads": 32, "num_key_value_heads": 4,
                       "head_dim": 128, "num_experts_per_tok": 8,
                       "sliding_window": 1024, "rms_norm_eps": 1e-6,
                       "norm_topk_prob": True}.items():
        assert cfg[key] == value == args[key], key
    assert cfg["rope_parameters"] == args["rope_parameters"]
    yarn = cfg["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"],
            yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"]) == ("yarn", 16, 8192, 32, 1)
    assert yarn["attention_factor"] == pytest.approx(1 + 0.1 * math.log(16))
    assert cfg["rope_parameters"]["sliding_attention"]["rope_theta"] \
        == yarn["rope_theta"] == 500000
    assert args["num_experts"] == 64 == cfg["published"]["num_experts"]
    assert cfg["num_experts"] == args["experts_held"] == 8
    assert args["expert_offset"] == 8
    assert cfg["vocab_size"] == args["vocab_size"] == 98304 // 8
    # layer_types stays whole at the top; the builder gets one period
    assert len(cfg["layer_types"]) == 28 and cfg["num_hidden_layers"] == 4
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    assert args["layer_types"] == cfg["layer_types"][:4] \
        == [SLIDING, SLIDING, SLIDING, FULL]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    assert list(cfg["assumed"])[:3] == ["router", "balance_loss", "qk_norm"]
    assert {"window", "mtp", "final_norm", "selection_bias", "sequence",
            "optimizer", "weights", "ids"} <= set(cfg["assumed"])
    assert "deployment" in cfg


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench = _load(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[FULL_CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "train_bs2_seq8192"
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    assert set(config["reduced"]) == set(full()[0]["reduced"])
    listed = {m["name"] for m in bench["per_layer"]
              if FULL_CELL in m.get("workloads", [])}
    assert len(listed) == 26
    assert {"attn_full_core_roofline", "attn_window_core_roofline",
            "swa_tile_waste", "attn_proj_ms", "attn_rope_ms", "attn_out_ms",
            "attn_qk_norm_ms", "recompute_ms", "moe_experts_roofline",
            "moe_ffn_ms", "moe_load_max_over_mean", "moe_dispatch_ms",
            "moe_combine_ms", "step_mfu_pct", "device_idle_pct",
            "moe_balance_ms", "moe_balance_ratio"} <= listed
    assert not {"mla_core_roofline", "short_conv_roofline",
                "lstm_seq_roofline", "loop_head_ms"} & listed
    for name, source in (("moe_balance_ms", "device_trace"),
                         ("moe_balance_ratio", "program_counter")):
        m = {m["name"]: m for m in bench["per_layer"]}[name]
        assert m["workloads"] == [FULL_CELL]
        assert m["moves"] == "samples_per_s" and m["source"] == source
        assert m["layer"] == "model step"
    limits = _load(ROOT, "benchmark", "cells", FULL_CELL + ".json")["limits"]
    assert limits["nonfinite_costs"] == 0 and "grad_diff" in limits


class _Window:
    steps = 10
    at_open = {"steps": 6, "moe_balance": 6.6}
    at_close = {"steps": 16, "moe_balance": 18.1}


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
    (STEP + "jvp(blk0_moe)/moe_balance/reduce_sum", 0.002),
    (STEP + "transpose(jvp(blk0_moe))/moe_balance/mul", 0.003),
    (STEP + "jvp(moe_balance)/moe_balance/mul", 0.001),
    (STEP + "transpose(jvp(moe_balance))/moe_balance/mul", 0.004),
    (STEP + "jvp(blk0_moe)/moe_route/top_k", 0.5),
    (STEP + "jvp(blk3_attn)/attn_core/pallas_call", 0.7),
]


def test_each_new_metrics_reader_on_a_made_up_trace():
    ctx = _context(OPS)
    # the expert layers' statistics and the term, forward and backward
    assert reader("moe_balance_ms")(ctx) == pytest.approx(1e3 * 0.010 / 10)
    assert reader("moe_balance_ratio")(ctx) == pytest.approx(1.15)


def test_the_readers_are_silent_where_there_is_nothing_to_read():
    """A program without the term (the parent commit, another model: the
    recorded LSTM trace, a window with no such counter) or no chip:
    nothing to read, nothing raised."""
    from benchmark import trace_reduce
    ctx = _context(OPS)
    recorded = trace_reduce.reduce_file(
        os.path.join(TESTS, "tiny.xplane.pb"), chips=1,
        scopes=_load(TESTS, "tiny.scopes.json"))
    assert recorded.scope_seconds(r"jvp\(lstm\d+\)") > 0
    assert reader("moe_balance_ms")(dict(ctx, trace=recorded)) is None
    assert reader("moe_balance_ms")(dict(ctx, trace=None, peak=None)) \
        is None

    class Bare(_Window):
        at_open = {"steps": 6, "moe_rows_mean": 1.0}
        at_close = {"steps": 16, "moe_rows_mean": 2.0}

    assert reader("moe_balance_ratio")(dict(ctx, window=Bare())) is None
