"""CPU rehearsal of the harness: each kind of cell end to end at a test
size (kernels on their reference path), a ``chips: 4`` cell on a
data-parallel mesh of four virtual devices, the result line's keys as
the contract fixes them, and the refusal to measure off the TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "BENCHMARK.tiny.json")

CELLS = ["lstm_tiny.tiny_train_bs8", "resnet_tiny.tiny_train_bs8",
         "lstm_tiny.tiny_train_dp4_bs16"]


def _check_line(result, names):
    assert list(result)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    assert set(result["metrics"]) == set(names)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(result["device"])
    for value, limit in result["compared"].values():
        assert value <= limit
    json.dumps(result)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(cell):
    result = run.run_cell(cell, 2147483659, 0.5, False, bench_file=TINY,
                          on_chip=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    _check_line(result, ["samples_per_s", "step_p95_ms", "setup_s"])
    assert result["device"]["count"] == 4     # as JAX reports it


def test_traced_run_reports_host_counters_and_no_device_metric():
    result = run.run_cell(CELLS[0], 5, 0.5, True, bench_file=TINY,
                          on_chip=False)
    assert result["correct"] is True
    # off the TPU the readers of device metrics find nothing to read and
    # return nothing: no roofline, mfu, idle share or memory is printed
    _check_line(result, ["data_wait_ms", "host_step_overhead_ms",
                         "compiles_in_window"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" not in result["device"]
    assert result["device"]["memory_peak_bytes"] is None


def test_same_seed_same_inputs():
    from benchmark import traffic
    with open(os.path.join(HERE, "configs", "lstm_tiny.json")) as f:
        cfg = json.load(f)
    mix = traffic.load("tiny_train_bs8", HERE)
    a = traffic.Batches(cfg["inputs"], mix, 2 ** 31 + 11)
    b = traffic.Batches(cfg["inputs"], mix, 2 ** 31 + 11)
    c = traffic.Batches(cfg["inputs"], mix, 2 ** 31 + 12)
    assert all((a.at(3)[k] == b.at(3)[k]).all() for k in a.at(3))
    assert any((a.at(3)[k] != c.at(3)[k]).any() for k in a.at(3))
    rows = a.at(0)["words"]
    assert len({r.tobytes() for r in rows}) == len(rows)   # all differ


def test_the_command_refuses_to_run_off_the_tpu():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        proc = subprocess.run(
            bench["command"] + ["--workload", cell["name"], "--seed", "1",
                                "--seconds", "1", "--trace", "0"],
            cwd=ROOT, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "needs a TPU" in proc.stderr


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cfg in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, cfg["file"]))
    for cell in bench["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
        from benchmark import check
        assert check.limits(cell["name"])["nonfinite_costs"] == 0
    for kind in ("traffic", "cells", "configs"):
        # test sizes live under benchmark/tests/, never beside the cells'
        assert not [f for f in os.listdir(os.path.join(
            ROOT, "benchmark", kind)) if "tiny" in f]
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_the_tail_is_over_every_step_of_the_window():
    from benchmark.window import Window
    win = Window.__new__(Window)
    win.opened, win.ends = 1.0, [1.1, 1.2, 1.6, 1.7]
    assert win.step_times() == pytest.approx([0.1, 0.1, 0.4, 0.1])
    # one stalled step in a hundred is beyond the 95th, six are not
    assert run.p95([0.1] * 99 + [0.5]) == pytest.approx(0.1)
    assert run.p95([0.1] * 94 + [0.5] * 6) > 0.1
    assert run.p95([0.2]) == 0.2


def test_a_large_batch_comes_from_a_pool_and_a_small_one_fresh():
    from benchmark import traffic
    mix = {"batch": 4, "seq_len": 0, "pool": 2}
    big = traffic.Batches({"x": {"type": "dense_vector", "dim": 70000}},
                          mix, 5)
    assert big.at(0)["x"].nbytes >= traffic.POOLED_FROM_BYTES
    assert big.at(2) is big.at(0) and big.at(3) is big.at(1)
    small = traffic.Batches({"x": {"type": "dense_vector", "dim": 7}}, mix, 5)
    assert small.pool is None
    assert (small.at(2)["x"] != small.at(0)["x"]).any()
