"""The per-layer metrics that read the program's finer counters
(``StepBreakdown`` ``dispatch``, ``device_wait`` and the prefetch
thread's keys): a traced rehearsal of the test-size LSTM cell reports
all five, and every metric ``BENCHMARK.json`` names has its reader."""

import json
import math
import os

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "BENCHMARK.tiny.json")
CELL = "lstm_tiny.tiny_train_bs8"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(TINY) as f:
    ACCEPTED = {m["name"] for m in json.load(f)["per_layer"]}
NEW = [m for m in BENCH["per_layer"] if m["name"] not in ACCEPTED]


def test_the_five_counters_are_the_entries_appended_last():
    assert [m["name"] for m in NEW] == [
        "step_dispatch_ms", "device_wait_ms", "prefetch_decode_ms",
        "prefetch_h2d_ms", "prefetch_put_wait_ms"]
    assert BENCH["per_layer"][-len(NEW):] == NEW
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in NEW:
        assert m["source"] == "program_counter"
        assert m["moves"] == "samples_per_s" and m["workloads"] == cells
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))


def test_a_traced_rehearsal_reports_all_five(tmp_path):
    with open(TINY) as f:
        bench = json.load(f)
    for cfg in bench["configs"]:
        cfg["file"] = os.path.join(HERE, cfg["file"])
    bench["per_layer"] += [dict(m, workloads=[CELL]) for m in NEW]
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    result = run.run_cell(CELL, 2147483777, 0.5, True,
                          bench_file=str(bench_file), on_chip=False)
    assert result["correct"] is True
    for m in NEW:
        got = result["metrics"][m["name"]]
        assert got["unit"] == "ms"
        assert math.isfinite(got["value"]) and got["value"] >= 0
    # the loop is serial: the trainer's thread waits for every step
    assert result["metrics"]["device_wait_ms"]["value"] > 0
    assert result["metrics"]["step_dispatch_ms"]["value"] > 0


def test_a_program_without_the_counter_reads_as_nothing():
    """The parent commit's breakdown has no such key: the reader returns
    nothing, and the line leaves the metric out."""
    import importlib
    from benchmark.window import Window
    win = Window.__new__(Window)
    win.at_open = {"steps": 0, "data_wait": 0.0}
    win.at_close = {"steps": 4, "data_wait": 0.25}
    for m in NEW:
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        assert reader.read({"window": win}) is None
    from benchmark.metrics import data_wait_ms
    assert data_wait_ms.read({"window": win}) == 62.5
