"""The six readers that time a part of a layer from a device trace
(``attn_proj_ms``, ``attn_rope_ms``, ``attn_out_ms``, ``recompute_ms``,
``moe_dispatch_ms``, ``moe_combine_ms``: ``benchmark/metrics/``, over
``scope_ms.read``) on made-up traces whose scope paths are the ones the
compiled layers carry (``tests/test_tpu_compile.py`` pins those), and
their entries in ``BENCHMARK.json``."""

import importlib
import json
import os

import pytest

from benchmark import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JOYAI = "joyai_llm_flash_ep32.train_bs2_seq4096"
LAGUNA = "laguna_xs2_ep32.train_bs1_seq8192"
OURO = "ouro_2_6b_pp6.train_bs1_seq4096"
LFM2 = "lfm2_24b_a2b_ep8.train_bs1_seq8192"      # appended by PR 37
MELLUM = "mellum2_12b_a2b5_ep8.train_bs2_seq8192"

STEP = "jit(step)/jit(main)/"
FWD = STEP + "jvp({0})/"
BWD = STEP + "transpose(jvp({0}))/jvp({0})/checkpoint/"
AGAIN = BWD + "rematted_computation/"
MOE_BWD = STEP + "transpose(jvp({0}))/while/body/"

# (scope path, start, end) in seconds: what ran on device 0 over 10 steps
OPS = [
    (FWD.format("blk1_attn") + "attn_qkv/dot_general", 0.0, 0.020),
    (AGAIN.format("blk1_attn") + "attn_qkv/dot_general", 0.1, 0.120),
    (BWD.format("blk1_attn") + "attn_qkv/transpose", 0.2, 0.240),
    (FWD.format("blk2_swa") + "attn_qkv/dot_general", 0.3, 0.310),
    (FWD.format("mtp_attn") + "attn_rope/concatenate", 0.4, 0.405),
    (AGAIN.format("ut3_blk7_attn") + "attn_rope/cos", 0.5, 0.502),
    (BWD.format("blk2_swa") + "attn_rope/add_any", 0.6, 0.603),
    (FWD.format("blk2_swa") + "attn_out/logistic", 0.7, 0.701),
    (AGAIN.format("blk2_swa") + "attn_out/dot_general", 0.8, 0.803),
    (BWD.format("blk1_attn") + "attn_out/dot_general", 0.9, 0.906),
    # the cores, a recomputed one among them, and a SwiGLU layer's
    (FWD.format("blk1_attn") + "mla_core/pallas_call", 1.0, 1.3),
    (AGAIN.format("blk2_swa") + "attn_core/slice", 1.3, 1.304),
    (FWD.format("blk0_mlp") + "dot_general", 1.4, 1.5),
    (AGAIN.format("blk0_mlp") + "dot_general", 1.5, 1.550),
    (BWD.format("blk0_mlp") + "dot_general", 1.6, 1.8),
    # an expert layer: a `while` under a scope encloses its body's
    # operations (a sort's loop), so the part is a union, not a sum
    (FWD.format("blk3_moe") + "moe_route/top_k", 2.0, 2.004),
    (FWD.format("blk3_moe") + "moe_dispatch/sort/while", 2.1, 2.130),
    (FWD.format("blk3_moe") + "moe_dispatch/sort/while/body/gt", 2.1, 2.115),
    (FWD.format("blk3_moe") + "moe_dispatch/sort/while/body/select_n",
     2.115, 2.130),
    (FWD.format("blk3_moe") + "while", 2.2, 2.407),      # the chunks' loop
    (FWD.format("blk3_moe") + "while/body/moe_dispatch/gather", 2.2, 2.206),
    (FWD.format("blk3_moe") + "while/body/moe_experts/jit(gmm)/pallas_call",
     2.206, 2.4),
    (FWD.format("blk3_moe") + "while/body/moe_combine/scatter-add",
     2.4, 2.407),
    (FWD.format("blk3_moe") + "moe_shared/dot_general", 2.5, 2.6),
    (MOE_BWD.format("mtp_moe") + "moe_dispatch/add", 2.7, 2.702),
    (MOE_BWD.format("mtp_moe")
     + "transpose(jvp(moe_combine))/mul", 2.8, 2.809),
    (MOE_BWD.format("mtp_moe") + "jvp(moe_experts)/jit(gmm)/pallas_call",
     2.9, 3.0),
    (STEP + "transpose(jvp(mtp_moe))/add_any", 3.0, 3.001),
    (STEP + "mul", 3.1, 3.2),                   # the update: no layer
]

# milliseconds a step over the window's 10 steps
EXPECTED = {
    "attn_proj_ms": 1e3 * (0.020 + 0.020 + 0.040 + 0.010) / 10,
    "attn_rope_ms": 1e3 * (0.005 + 0.002 + 0.003) / 10,
    "attn_out_ms": 1e3 * (0.001 + 0.003 + 0.006) / 10,
    "recompute_ms": 1e3 * (0.020 + 0.002 + 0.003 + 0.004 + 0.050) / 10,
    "moe_dispatch_ms": 1e3 * (0.004 + 0.030 + 0.006 + 0.002) / 10,
    "moe_combine_ms": 1e3 * (0.007 + 0.009) / 10,
}
CELLS = {name: [JOYAI, LAGUNA, OURO, LFM2, MELLUM]
         if not name.startswith("moe") else [JOYAI, LAGUNA, LFM2, MELLUM]
         for name in EXPECTED}

# a model with none of these layers: the LSTM cell's scopes
OTHER = [(STEP + "jvp(lstm0)/while/body/dot_general", 0.0, 0.5),
         (STEP + "transpose(jvp(lstm0))/while/body/mul", 0.5, 1.0),
         (STEP + "jvp(lstm0_proj)/dot_general", 1.0, 1.1)]


class _Window:
    steps = 10


def _context(ops):
    made = [trace_reduce.Op(f"fusion.{i}", scope, start, end)
            for i, (scope, start, end) in enumerate(ops)]
    return {"trace": trace_reduce.Reduced([trace_reduce.Device(0, made)],
                                          {}, 4.0),
            "window": _Window(), "peak": {"bf16_flops": 197e12}}


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}").read


def tool(name):
    """A script of ``tools/`` as a module."""
    import sys
    path = os.path.join(ROOT, "tools")
    if path not in sys.path:
        sys.path.insert(0, path)
    return importlib.import_module(name)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_parts_reader(name):
    """The milliseconds expected (forward, recomputed and backward of
    every layer of the kind, a union where a ``while`` encloses its
    body); nothing without a trace, off the chip, over no steps, or on
    a cell whose program opens no such scope (the parent's too)."""
    read = reader(name)
    ctx = _context(OPS)
    assert read(ctx) == pytest.approx(EXPECTED[name])
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, peak=None)) is None
    assert read(_context(OTHER)) is None
    still = _Window()
    still.steps = 0
    assert read(dict(ctx, window=still)) is None
    # the readers these stand beside read what they read before
    assert reader("moe_ffn_ms")(ctx) == pytest.approx(
        1e3 * (0.004 + 0.030 + 0.207 + 0.1 + 0.002 + 0.009 + 0.1 + 0.001)
        / 10)


def test_the_parts_of_a_layer_add_up_to_it():
    """Every operation of the made-up attention and expert layers lies
    under one part, so the parts' times add up to the layer's (on the
    chip: but what ``PERF.md`` section 5 lists under no part)."""
    trace = _context(OPS)["trace"]
    from benchmark.metrics import scope_ms
    attention = sum(trace.scope_seconds(scope_ms.ATTENTION + rf".*\b{p}\b")
                    for p in ("attn_qkv", "attn_rope", "attn_out",
                              "mla_core", "attn_core"))
    assert attention == pytest.approx(
        trace.scope_seconds(scope_ms.ATTENTION))
    experts = sum(trace.scope_seconds(scope_ms.EXPERTS + rf".*\b{p}\b")
                  for p in ("moe_(?:route|dispatch)", "moe_experts",
                            "moe_combine", "moe_shared"))
    assert experts == pytest.approx(
        trace.scope_seconds(scope_ms.EXPERTS) - 0.001)  # the bare add_any


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_benchmark_lists_a_parts_metric(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "model step",
                     "moves": "samples_per_s", "workloads": CELLS[name]}
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                       name + ".py"))
    # appended after every entry the benchmark had, then the short
    # convolution's and the q/k norm's three, then the balancing term's
    # two, then the buffer's fill
    assert bench["per_layer"][-1]["name"] == "moe_buffer_fill"
    assert [m["name"] for m in bench["per_layer"][-12:-1]] == [
        "attn_proj_ms", "attn_rope_ms", "attn_out_ms", "recompute_ms",
        "moe_dispatch_ms", "moe_combine_ms", "short_conv_roofline",
        "short_conv_core_ms", "attn_qk_norm_ms", "moe_balance_ms",
        "moe_balance_ratio"]


def test_the_split_of_a_kept_trace_by_part_and_direction():
    """``tools/trace_layer_split.py`` on the made-up operations: a row a
    part with forward | again | backward, the layer whole, and what lies
    under no part."""
    trace_layer_split = tool("trace_layer_split")
    device = _context(OPS)["trace"].devices[0]
    table = {(r["layer"], r["part"]): r
             for r in trace_layer_split.rows(device, 10)}
    qkv = table["attn", "attn_qkv"]
    assert (qkv["fwd"], qkv["again"], qkv["bwd"]) == pytest.approx(
        (2.0, 2.0, 4.0))
    assert table["swa", "attn_qkv"]["ms_a_step"] == pytest.approx(1.0)
    assert table["swa", "attn_core"]["again"] == pytest.approx(0.4)
    for kind in ("attn", "swa"):
        assert table[kind, "no part"]["ms_a_step"] == 0.0
        assert table[kind, "the layer"]["ms_a_step"] == pytest.approx(sum(
            r["ms_a_step"] for (k, p), r in table.items()
            if k == kind and p not in ("the layer", "no part")))
    assert table["moe", "moe_dispatch"]["fwd"] == pytest.approx(3.6)
    # the bare add_any, and what the chunks' loop spends between its
    # body's operations
    assert table["moe", "no part"]["ms_a_step"] == pytest.approx(0.1)
    assert table["moe", "the layer"]["ms_a_step"] == pytest.approx(sum(
        r["ms_a_step"] for (k, p), r in table.items()
        if k == "moe" and p != "the layer"))
    assert ("moe", "attn_qkv") not in table
    # another model's trace has no such layer: no row
    assert list(trace_layer_split.rows(
        _context(OTHER)["trace"].devices[0], 10)) == []


def test_step_text_compares_two_compiled_texts_without_their_metadata():
    """``tools/step_text.py same``: an instruction's metadata and the
    tables it points to are left out of the comparison, anything else
    is not."""
    step_text = tool("step_text")
    a = ('HloModule jit_step\n\nFileNames\n1 "/a/attention.py"\n\n'
         'StackFrames\n1 {file_location_id=1 parent_frame_id=1}\n\n'
         'ENTRY %main {\n  %dot.1 = f32[8,8]{1,0} dot(%x, %y), '
         'metadata={op_name="jit(step)/jvp(blk0_attn)/dot_general" '
         'stack_frame_id=1}\n}\n')
    b = a.replace("/a/attention.py", "/b/attention.py").replace(
        "jvp(blk0_attn)/dot_general", "jvp(blk0_attn)/attn_qkv/dot_general")
    assert a != b and step_text.bare(a) == step_text.bare(b)
    assert "metadata" not in step_text.bare(a)
    assert "%dot.1 = f32[8,8]{1,0} dot(%x, %y)\n" in step_text.bare(a)
    assert step_text.bare(a) != step_text.bare(a.replace("f32[8,8]",
                                                         "f32[8,16]"))
