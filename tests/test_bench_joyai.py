"""A rehearsal of the benchmark's JoyAI-LLM-Flash cell off the chip, at
a test size with files of its own (``benchmark/tests/BENCHMARK.tiny_joyai
.json``): the harness end to end to ``correct``, the counts against a
hand count, and each new per-layer metric's reader on a made-up
context."""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "benchmark", "tests")
TINY = os.path.join(TESTS, "BENCHMARK.tiny_joyai.json")
CELL = "joyai_tiny.tiny_train_bs2_seq32"

counts = importlib.import_module("benchmark.counts.joyai_llm_flash_ep32")


def tiny():
    with open(os.path.join(TESTS, "configs", "joyai_tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(TESTS, "traffic",
                           "tiny_train_bs2_seq32.json")) as f:
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
    json.dumps(result)


def test_traced_run_reports_the_programs_counter_and_no_device_metric(
        result):
    # off the TPU the device metrics' readers find nothing and return
    # nothing; the program's own counter is there
    assert set(result["metrics"]) == {"data_wait_ms", "compiles_in_window",
                                      "moe_load_max_over_mean"}
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0


def test_the_control_and_half_a_batch_are_not_correct():
    from benchmark import control, run
    low = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                       control=True)
    assert low["correct"] is False
    half = run.run_cell(CELL, 7, 0.2, False, bench_file=TINY, on_chip=False,
                        tamper=control.FAULTS["half"])
    assert half["correct"] is False


def test_counts_against_a_hand_count():
    cfg, mix = tiny()
    # per token, forward MACs. Attention (4 layers with the module's):
    # projections 32*24 + 24*2*12 + 32*20 + 16*2*16 + 16*32 = 3008, core
    # 2 heads * 32/2 keys * (12 + 8) = 640. Dense FFN 3*32*48 = 4608.
    # Expert layers (3): router 32*16 = 512, shared 3*32*16 = 1536,
    # routed 4 * 4/16 = 1 expert = 1536. Module's projection 2*32*32,
    # two heads 2*32*64.
    macs = 4 * (3008 + 640) + 4608 + 3 * (512 + 1536 + 1536) + 2048 + 4096
    assert macs == 36096
    assert counts.forward_macs_per_token(cfg, 32) == macs
    assert counts.step_flops_per_sample(cfg, mix) == 3 * 2 * macs * 32
    # parameters: the reference's leaves are the count
    ref = importlib.import_module("benchmark.reference.joyai_llm_flash_ep32")
    import math
    assert counts.param_count(cfg) == 47952 == sum(
        math.prod(shape) for shape, _ in ref.leaves(cfg).values())
    core = counts.mla_core(cfg, mix, 2)
    assert core["flops"] == 3 * 2 * 4 * 2 * 32 * 640
    # q, k of 12 and v, o of 8, bfloat16; backward moves 2q+2k+... as told
    qk, v = 2 * 2 * 32 * 12 * 2, 2 * 2 * 32 * 8 * 2
    assert core["bytes"] == 4 * ((2 * qk + 2 * v) + (4 * qk + 4 * v))
    experts = counts.moe_experts(cfg, mix, 2)
    rows = 2 * 32 * 4 * 4 / 16
    assert experts["flops"] == 3 * 2 * 3 * rows * 1536
    assert experts["bytes"] == 3 * (3 * 4 * 1536 * 2
                                    + 3 * rows * (64 + 48) * 2)


def test_the_full_size_counts_are_the_issues():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai_llm_flash_ep32.json")) as f:
        cfg = json.load(f)
    assert counts.param_count(cfg) == 491_697_408
    assert counts.forward_macs_per_token(cfg, 4096) == 434_634_752
    # every published width is in the file as published
    for key, value in {"hidden_size": 2048, "intermediate_size": 7168,
                       "moe_intermediate_size": 768, "q_lora_rank": 1536,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                       "qk_rope_head_dim": 64, "v_head_dim": 128,
                       "num_attention_heads": 32, "num_experts_per_tok": 8,
                       "n_shared_experts": 1}.items():
        assert cfg[key] == value == cfg["model"]["args"][key], key
    assert cfg["model"]["args"]["n_routed_experts"] == 256
    assert cfg["n_routed_experts"] == cfg["model"]["args"]["experts_held"]


class _Window:
    steps = 10
    at_open = {"steps": 6, "moe_rows_max": 100.0, "moe_rows_mean": 80.0,
               "moe_experts_active": 24.0}
    at_close = {"steps": 16, "moe_rows_max": 3100.0, "moe_rows_mean": 2580.0,
                "moe_experts_active": 64.0}


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
    (STEP + "jvp(blk1_attn)/mla_core/pallas_call", 0.010),
    (STEP + "transpose(jvp(blk1_attn))/checkpoint/mla_core/pallas_call",
     0.030),
    (STEP + "jvp(blk1_attn)/dot_general", 0.5),          # a projection
    (STEP + "jvp(mtp_moe)/moe_experts/pallas_call", 0.004),
    (STEP + "transpose(jvp(mtp_moe))/moe_experts/pallas_call", 0.006),
    (STEP + "jvp(blk2_moe)/moe_route/top_k", 0.020),
    (STEP + "jvp(blk0_mlp)/dot_general", 0.7),
    (STEP + "jvp(mtp_proj)/dot_general", 0.9),
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

    assert reader("mla_core_roofline")(ctx) == pytest.approx(
        share(counts.mla_core(ctx["cfg"], ctx["mix"], 2), 0.040))
    assert reader("moe_experts_roofline")(ctx) == pytest.approx(
        share(counts.moe_experts(ctx["cfg"], ctx["mix"], 2, rows=250 * 4,
                                 active=4), 0.010))
    # the program's counts: 250 rows a held expert, all 4 held got some
    assert reader("moe_ffn_ms")(ctx) == pytest.approx(1e3 * 0.030 / 10)
    assert reader("moe_load_max_over_mean")(ctx) == pytest.approx(1.2)
    # a program without the scopes or the counters (the parent commit):
    # nothing to read, nothing raised
    bare = _context([(STEP + "jvp(lstm0)/while", 1.0)])
    bare["window"] = type("W", (), {"steps": 10, "at_open": {"wall": 0.0},
                                    "at_close": {"wall": 1.0}})()
    for name in ("mla_core_roofline", "moe_experts_roofline", "moe_ffn_ms",
                 "moe_load_max_over_mean"):
        assert reader(name)(bare) is None
    # and off the chip (no trace, no peak) the device metrics are silent
    for name in ("mla_core_roofline", "moe_experts_roofline", "moe_ffn_ms"):
        assert reader(name)(dict(ctx, trace=None, peak=None)) is None


def test_a_collapsed_load_keeps_the_experts_share_at_or_under_100():
    """Every routed row of a step on ONE of the 4 held experts, on a chip
    that takes exactly the least time for what such a step has to move
    (one expert's weights three times, the rows' activations three
    times): the share reads 100, not more. Charged the weights of all
    four held experts, the same run would read over 100."""
    from benchmark import peaks
    read = importlib.import_module(
        "benchmark.metrics.moe_experts_roofline").read
    cfg, mix = tiny()
    rows, layers, expert = 48, 3, 3 * 32 * 16       # MACs of one expert
    work = {"flops": 3 * 2 * layers * rows * expert,
            "bytes": layers * (3 * 1 * expert * 2
                               + 3 * rows * (2 * 32 + 3 * 16) * 2)}
    peak = peaks.load("TPU v5 lite")
    least, _ = peaks.least_seconds(work["flops"], work["bytes"], peak)
    ctx = _context([(STEP + "jvp(blk1_moe)/moe_experts/pallas_call",
                     10 * least)])
    ctx["window"] = type("W", (), {
        "steps": 10, "at_open": {"steps": 0},
        "at_close": {"steps": 10, "moe_rows_max": 10.0 * rows,
                     "moe_rows_mean": 10.0 * rows / 4,
                     "moe_experts_active": 10.0}})()
    assert read(ctx) == pytest.approx(100.0)
    assert counts.moe_experts(cfg, mix, 2, rows=rows, active=1) == work
    every = counts.moe_experts(cfg, mix, 2, rows=rows)   # all four charged
    assert every["bytes"] > work["bytes"]
    assert peaks.least_seconds(every["flops"], every["bytes"],
                               peak)[0] > least
    # an expert needs a row: 2 rows cannot wake more than 2 experts, and
    # a program that reports no ``moe_experts_active`` is bounded so too
    assert counts.moe_experts(cfg, mix, 2, rows=2)["bytes"] == layers * (
        3 * 2 * expert * 2 + 3 * 2 * (2 * 32 + 3 * 16) * 2)
    assert counts.moe_experts(cfg, mix, 2, rows=0) == {"flops": 0.0,
                                                      "bytes": 0.0}
