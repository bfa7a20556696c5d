"""A rehearsal of the benchmark's Laguna-XS.2 cell off the chip, at a
test size with files of its own (``benchmark/tests/BENCHMARK.tiny_laguna
.json``): the harness end to end to ``correct``, the counts against a
hand count and the full-size counts against ISSUE 31's, and each new
per-layer metric's reader on a made-up context."""

import importlib
import json
import math
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "benchmark", "tests")
TINY = os.path.join(TESTS, "BENCHMARK.tiny_laguna.json")
CELL = "laguna_tiny.tiny_train_bs2_seq64"
FULL, SLIDING = "full_attention", "sliding_attention"

counts = importlib.import_module("benchmark.counts.laguna_xs2_ep32")
ref = importlib.import_module("benchmark.reference.laguna_xs2_ep32")


def tiny():
    with open(os.path.join(TESTS, "configs", "laguna_tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TESTS, "traffic",
                           "tiny_train_bs2_seq64.json")) as f:
        return cfg, json.load(f)


def full():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_xs2_ep32.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "train_bs1_seq8192.json")) as f:
        return cfg, json.load(f)


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
    assert set(result["kernel_paths"]) >= {"flash_attention",
                                           "moe_grouped_matmul"}
    # one dispatch a layer, both kinds through the one entry
    assert sum(result["kernel_paths"]["flash_attention"].values()) == 4
    json.dumps(result)


def test_traced_run_reports_the_programs_counters_and_no_device_metric(
        result):
    # off the TPU the device metrics' readers find nothing and return
    # nothing; the program's own counters are there. 64 tokens, a
    # window of 16 in tiles of 16: 7 of 16 tiles a head, 136 + 48 * 16
    # visible pairs
    assert set(result["metrics"]) == {
        "data_wait_ms", "compiles_in_window", "moe_load_max_over_mean",
        "swa_tile_waste"}
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert result["metrics"]["swa_tile_waste"]["value"] == pytest.approx(
        7 * 256 / (136 + 48 * 16))


def test_the_control_and_half_a_batch_are_not_correct():
    from benchmark import control, run
    low = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                       control=True)
    assert low["correct"] is False
    half = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                        tamper=control.FAULTS["half"])
    assert half["correct"] is False


def test_half_of_a_rows_positions_left_out_is_not_correct():
    """The fault for a cell whose batch is one row
    (``benchmark/probes/half_tokens.py``): the step sees the first half
    of every row's positions."""
    from benchmark import control, run
    from benchmark.probes import half_tokens  # noqa: F401 - registers it
    half = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                        tamper=control.FAULTS["half_tokens"])
    assert half["correct"] is False and half["failed"] == 0
    assert half["compared"]["grad_diff_median"][0] > 0.5


def test_counts_against_a_hand_count():
    cfg, mix = tiny()
    # per token, forward MACs, 64 tokens, a window of 16. A full layer (6
    # heads of 8 over 2 key-value heads): projections 32*48 + 2*32*16 +
    # 48*32 + the gate's 32*6 = 4288, core 6 heads * 32 keys * 16 = 3072.
    # A sliding layer (8 heads): projections 32*64 + 1024 + 64*32 + 32*8 =
    # 5376, core 8 * (16 - 256/128 = 14 keys) * 16 = 1792. Dense FFN
    # 3*32*48 = 4608. Expert layers (3): router 32*16 = 512, shared
    # 3*32*16 = 1536, routed 4 * 4/16 = 1 expert = 1536. Head 32*64.
    macs = (2 * (4288 + 3072) + 2 * (5376 + 1792) + 4608
            + 3 * (512 + 1536 + 1536) + 2048)
    assert macs == 46464
    assert counts.forward_macs_per_token(cfg, 64) == macs
    assert counts.step_flops_per_sample(cfg, mix) == 3 * 2 * macs * 64
    # parameters: the reference's leaves are the count
    assert counts.param_count(cfg) == 52944 == sum(
        math.prod(shape) for shape, _ in ref.leaves(cfg).values())
    # the cores by kind, batch 2: FLOPs of the visible pairs; q, o, dO, dq
    # at the query heads and k, v, dk, dv at the 2 key-value heads
    core = counts.attn_core(cfg, mix, 2, FULL)
    assert core["flops"] == 3 * 2 * 2 * 64 * 2 * 3072
    q, k = 2 * 6 * 64 * 8 * 2, 2 * 2 * 64 * 8 * 2
    assert core["bytes"] == 2 * (6 * q + 6 * k)
    core = counts.attn_core(cfg, mix, 2, SLIDING)
    assert core["flops"] == 3 * 2 * 2 * 64 * 2 * 1792
    q = 2 * 8 * 64 * 8 * 2
    assert core["bytes"] == 2 * (6 * q + 6 * k)
    # a window as long as the sequence is the causal triangle
    wide = json.loads(json.dumps(cfg))
    wide["model"]["args"]["sliding_window"] = 64
    assert counts.attn_core(wide, mix, 2, SLIDING)["flops"] \
        == 3 * 2 * 2 * 64 * 2 * (8 * 32 * 16)
    experts = counts.moe_experts(cfg, mix, 2)
    rows = 2 * 64 * 4 * 4 / 16
    assert experts["flops"] == 3 * 2 * 3 * rows * 1536
    assert experts["bytes"] == 3 * (3 * 4 * 1536 * 2
                                    + 3 * rows * (64 + 48) * 2)


def test_the_full_size_counts_are_the_issues():
    cfg, mix = full()
    assert counts.param_count(cfg) == 389_635_072 == sum(
        math.prod(shape) for shape, _ in ref.leaves(cfg).values())
    macs = counts.forward_macs_per_token(cfg, 8192)
    assert macs == 391_446_528
    args = cfg["model"]["args"]
    # the issue's parts, in millions of multiply-accumulates a token
    proj = sum(counts._attn_proj_macs(args, h)
               for h in args["num_attention_heads_per_layer"])
    cores = sum(counts._core_macs_per_token(args, i, 8192) for i in range(5))
    assert round(proj / 1e6, 1) == 172.6
    assert round(cores / 1e6, 1) == 125.0
    assert round((macs - proj - cores) / 1e6, 1) == round(
        50.3 + 17.8 + 25.7, 1)
    assert round(counts.step_flops_per_sample(cfg, mix) / 1e12, 1) == 19.2
    # a full layer's core sees 8.3 times the pairs of a sliding one a head
    assert round(counts._keys_seen(args, FULL, 8192)
                 / counts._keys_seen(args, SLIDING, 8192), 1) == 8.3
    # every published width is in the file as published, top level and
    # the builder's arguments alike
    for key, value in {"hidden_size": 2048, "intermediate_size": 8192,
                       "moe_intermediate_size": 512,
                       "shared_expert_intermediate_size": 512,
                       "head_dim": 128, "num_key_value_heads": 8,
                       "sliding_window": 512, "num_experts_per_tok": 8,
                       "moe_routed_scaling_factor": 2.5}.items():
        assert cfg[key] == value == args[key], key
    assert cfg["num_attention_heads"] == 48
    assert cfg["rope_parameters"] == args["rope_parameters"]
    assert args["num_experts"] == 256 == cfg["published"]["num_experts"]
    assert cfg["num_experts"] == args["experts_held"] == 8
    assert cfg["vocab_size"] == args["vocab_size"] == 100352 // 8
    # the per-layer lists stay whole at the top; the builder gets the
    # leading dense layer and one whole period
    assert len(cfg["layer_types"]) == 40 and cfg["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert args[key] == cfg[key][:5], key
    assert args["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert args["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    assert {"gating", "router", "qk_norm"} <= set(cfg["assumed"])


def test_the_benchmark_names_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "laguna_xs2_ep32.train_bs1_seq8192"
    entry = {w["name"]: w for w in bench["workloads"]}[cell]
    assert entry["chips"] == 1 and entry["traffic"] == "train_bs1_seq8192"
    _, mix = full()
    assert (mix["kind"], mix["batch"], mix["seq_len"], mix["pool"],
            mix["mesh"]) == ("train", 1, 8192, 4, None)
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", [])}
    assert {"attn_full_core_roofline", "attn_window_core_roofline",
            "swa_tile_waste", "moe_experts_roofline", "moe_ffn_ms",
            "moe_load_max_over_mean", "step_mfu_pct"} <= listed
    assert not {"mla_core_roofline", "lstm_seq_roofline"} & listed
    with open(os.path.join(ROOT, "benchmark", "cells", cell + ".json")) as f:
        limits = json.load(f)["limits"]
    assert limits["nonfinite_costs"] == 0 and "grad_diff" in limits


class _Window:
    steps = 10
    at_open = {"steps": 6, "moe_rows_max": 100.0, "moe_rows_mean": 80.0,
               "moe_experts_active": 24.0, "swa_pairs_visited": 6 * 1792.0,
               "swa_pairs_visible": 6 * 904.0}
    at_close = {"steps": 16, "moe_rows_max": 3100.0,
                "moe_rows_mean": 2580.0, "moe_experts_active": 64.0,
                "swa_pairs_visited": 16 * 1792.0,
                "swa_pairs_visible": 16 * 904.0}


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
    (STEP + "jvp(blk0_attn)/attn_core/pallas_call", 0.010),
    (STEP + "transpose(jvp(blk3_attn))/checkpoint/attn_core/pallas_call",
     0.030),
    (STEP + "jvp(blk1_swa)/attn_core/pallas_call", 0.002),
    (STEP + "transpose(jvp(blk2_swa))/checkpoint/attn_core/pallas_call",
     0.006),
    (STEP + "jvp(blk1_swa)/dot_general", 0.5),           # a projection
    (STEP + "jvp(blk0_attn)/dot_general", 0.5),
    (STEP + "jvp(blk1_moe)/moe_experts/pallas_call", 0.004),
    (STEP + "transpose(jvp(blk1_moe))/moe_experts/pallas_call", 0.006),
    (STEP + "jvp(blk2_moe)/moe_route/top_k", 0.020),
    (STEP + "jvp(blk0_mlp)/dot_general", 0.7),
]


def test_each_new_metrics_reader_on_a_made_up_context():
    from benchmark import peaks
    ctx = _context(OPS)

    def reader(name):
        return importlib.import_module(f"benchmark.metrics.{name}").read

    def share(work, seconds):
        least, _ = peaks.least_seconds(work["flops"], work["bytes"],
                                       ctx["peak"])
        return 100.0 * least * _Window.steps / seconds

    # each kind's cores under its own layers' scope, and not the other's
    assert reader("attn_full_core_roofline")(ctx) == pytest.approx(
        share(counts.attn_core(ctx["cfg"], ctx["mix"], 2, FULL), 0.040))
    assert reader("attn_window_core_roofline")(ctx) == pytest.approx(
        share(counts.attn_core(ctx["cfg"], ctx["mix"], 2, SLIDING), 0.008))
    assert reader("swa_tile_waste")(ctx) == pytest.approx(1792 / 904)
    # the accepted readers find this model's expert layers too
    assert reader("moe_experts_roofline")(ctx) == pytest.approx(
        share(counts.moe_experts(ctx["cfg"], ctx["mix"], 2, rows=250 * 4,
                                 active=4), 0.010))
    assert reader("moe_ffn_ms")(ctx) == pytest.approx(1e3 * 0.030 / 10)
    assert reader("moe_load_max_over_mean")(ctx) == pytest.approx(1.2)
    # the latent cores' reader finds no `mla_core` here, and says nothing
    assert reader("mla_core_roofline")(ctx) is None
    # a program without the scopes or the counters (the parent commit),
    # or counts without `attn_core` (another configuration's): nothing to
    # read, nothing raised
    bare = _context([(STEP + "jvp(lstm0)/while", 1.0)])
    bare["window"] = type("W", (), {"steps": 10, "at_open": {"wall": 0.0},
                                    "at_close": {"wall": 1.0}})()
    for name in ("attn_full_core_roofline", "attn_window_core_roofline",
                 "swa_tile_waste"):
        assert reader(name)(bare) is None
    joyai = importlib.import_module("benchmark.counts.joyai_llm_flash_ep32")
    for name in ("attn_full_core_roofline", "attn_window_core_roofline"):
        assert reader(name)(dict(ctx, counts=joyai)) is None
        # and off the chip (no trace, no peak) they are silent
        assert reader(name)(dict(ctx, trace=None, peak=None)) is None
