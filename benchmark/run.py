"""One run of one cell of ``BENCHMARK.json``.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s``): weights and traffic from the seed, the
trainer the configuration names, then ``SGD.train`` over the prefetch
thread for the warm steps; the first three of those are what the check
of outputs reads. The same call goes on into the timed window. Once it
has closed: the device's memory peak is read, the trainer's state is
freed, the plain reference follows the first three steps and ``correct``
is decided. The last line of standard output is the result.

With ``--trace 1`` the window is ``TRACE_SECONDS`` (at most
``--seconds``), all of it under the profiler, and the metrics are the
cell's per-layer ones, each from its reader in ``benchmark/metrics/``.

A cell's data files are found by name beside the directory that holds
its configuration: ``traffic/<traffic>.json`` and ``cells/<cell>.json``
(the tests' tiny cells keep theirs under ``benchmark/tests/``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


WARM_STEPS = 6          # the check of outputs reads the first three
TRACE_SECONDS = 4.0     # a traced window: traces are large


def p95(values) -> float:
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[18]


def say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def load_cell(bench_file: str, workload: str):
    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {bench_file} "
                         f"(it has {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    file = os.path.join(os.path.dirname(os.path.abspath(bench_file)),
                        entry["file"])
    with open(file) as f:
        cfg = json.load(f)
    return bench, cell, cfg, os.path.dirname(os.path.dirname(file))


def metrics_of(bench: dict, group: str, workload: str) -> list:
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


def memory_peak(devices) -> Optional[int]:
    """The peak on the fullest chip. Probe M (PERF.md): on this runtime
    ``peak_bytes_in_use`` holds arguments and outputs only; the compiled
    step's temporaries are ``peak_bytes_reserved`` (it matched
    ``memory_analysis().temp_size_in_bytes`` to 0.4%), so the peak is
    their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use") is not None:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved") or 0))
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_file: str = os.path.join(ROOT, "BENCHMARK.json"),
             on_chip: bool = True, tamper=None,
             control: bool = False,
             keep_trace: Optional[str] = None) -> dict:
    """Everything a run does but the look at its arguments. ``on_chip``
    False (the tests' rehearsal) skips the refusal to run off the TPU;
    such a result carries no device metric. ``tamper(program)`` lets a
    test break the timed path underneath. ``control`` puts the
    configuration's lower-precision control in the program's place
    (``benchmark/control.py``): it has to come out as not correct.
    ``keep_trace`` names a file to copy the run's ``.xplane.pb`` to."""
    bench, cell, cfg, data_root = load_cell(bench_file, workload)

    from benchmark import check, peaks, program, traffic
    from benchmark.reference import plain
    from benchmark.window import Window
    import jax

    phases = [("imports", time.perf_counter() - T_START)]

    def phase(name):
        phases.append((name, time.perf_counter() - T_START))

    device = program.start(require_tpu=on_chip)
    phase("device")
    mix = traffic.load(cell["traffic"], data_root)
    chips = int(cell["chips"])
    if device["count"] < chips:
        raise SystemExit(f"{workload} needs {chips} chips, JAX has "
                         f"{device['count']} ({device})")
    mesh_size = 1
    for v in (mix.get("mesh") or {}).values():
        mesh_size *= int(v)
    if mesh_size != chips:
        raise SystemExit(f"{workload}: the traffic's mesh spans {mesh_size} "
                         f"devices, the cell asks for {chips}")
    devices = jax.devices()[:chips]
    peak = peaks.load(device["kind"]) if on_chip else None
    say("device", json.dumps(device), "cell", json.dumps(cell))

    ref_name = cfg.get("reference", cfg["name"])
    leaves = check.reference_module(ref_name).leaves(cfg)
    names = plain.trained(leaves)

    def weights():
        return plain.make_weights(leaves, seed)

    batches = traffic.Batches(cfg["inputs"], mix, seed)
    phase("traffic")
    ctl = cfg["precision"]["control"] if control else {}
    prog = program.Program(
        cfg, mix, weights(),
        compute_dtype=ctl["compute_dtype"] if ctl.get("kind") == "program"
        else "config")
    phase("trainer")
    if set(prog.params()) != set(leaves):
        raise SystemExit("the reference's leaves are not the program's: "
                         f"{sorted(set(prog.params()) ^ set(leaves))[:8]}")
    if tamper is not None:
        tamper(prog)
    recorder = check.Recorder(cfg, names, weights, prog.slot, prog.params)

    trace_dir = None
    window_seconds = float(seconds)
    if trace:
        window_seconds = min(window_seconds, TRACE_SECONDS)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")

    def open_trace():
        if trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # spans, not every call
            jax.profiler.start_trace(trace_dir, profiler_options=options)

    def close_trace():
        if trace_dir is not None:
            jax.profiler.stop_trace()

    win = Window(prog, batches, seconds=window_seconds,
                 warm_steps=WARM_STEPS, recorder=recorder,
                 on_open=open_trace, on_close=close_trace)
    try:
        with program.kernel_tally() as tally:
            win.run()
        setup_s = win.opened - T_START
        phases.append(("first_step", win.first_end - T_START))
        phases.append(("window_open", setup_s))
        say("set-up, seconds since start:", json.dumps(
            {k: round(v, 2) for k, v in phases}))
        compiles = prog.compiles()
        say("kernel paths", json.dumps(tally), "train-step compiles",
            compiles, "steps in the window", win.steps)
        memory = memory_peak(devices)
        context = {
            "cell": cell, "cfg": cfg, "mix": mix, "window": win,
            "device": device, "chips": chips, "peak": peak,
            "memory_peak_bytes": memory,
            "counts": importlib.import_module(
                f"benchmark.counts.{ref_name}"),
            "trace": None,
        }
        if trace and on_chip:
            # off the chip (the tests' rehearsal) the profiler is driven
            # but its trace holds no device plane: nothing is reduced
            from benchmark import trace_reduce
            scopes = trace_reduce.scopes_from_hlo(
                prog.step_hlo(batches.at(0)))
            if keep_trace:
                shutil.copy(trace_reduce.trace_file(trace_dir), keep_trace)
                with open(keep_trace + ".scopes.json", "w") as f:
                    json.dump(scopes, f)
            context["trace"] = trace_reduce.reduce_dir(
                trace_dir, chips=chips, window_s=win.window_s,
                scopes=scopes)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    result_metrics = {}
    if trace:
        for m in metrics_of(bench, "per_layer", workload):
            reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
            value = reader.read(context)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    else:
        times = win.step_times()
        values = {
            "samples_per_s": win.steps * batches.batch / win.window_s,
            "step_p95_ms": 1e3 * p95(times),
            "setup_s": setup_s,
        }
        say("steps", win.steps, "median step ms",
            1e3 * statistics.median(times), "p95 ms", values["step_p95_ms"],
            "longest ms", 1e3 * max(times))
        for m in metrics_of(bench, "end_to_end", workload):
            result_metrics[m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}

    # the check of outputs, once the window has closed and the memory
    # peak is read: the reference gets the chip to itself
    got = recorder.readings()
    attempted = win.steps
    nonfinite = sum(1 for c in win.costs
                    if c != c or abs(c) == float("inf"))
    prog.free()
    del prog, win, recorder, context["window"]
    gc.collect()
    t0 = time.perf_counter()
    if ctl.get("kind") == "reference":
        got = check.follow(ref_name, cfg, weights(), batches.at,
                           plain.Arith(operand=ctl["operand"],
                                       store=ctl.get("store")),
                           keep_first_grad=True)
    ref = check.follow(ref_name, cfg, weights(), batches.at,
                       check.stated_arith(cfg),
                       against=got.pop("first_grad"))
    numbers = check.compare(got, ref)
    numbers["nonfinite_costs"] = float(nonfinite)
    verdict = check.verdict(numbers, check.limits(workload, data_root))
    say(f"reference followed {check.STEPS} steps in "
        f"{time.perf_counter() - t0:.1f}s; worst at",
        json.dumps({k: numbers[k] for k in numbers if k.endswith("_at")}),
        "left out of the change", numbers["left_out"])

    reduced = context["trace"]
    device_block = dict(device, memory_peak_bytes=memory)
    if reduced is not None:
        device_block.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": nonfinite,
              "metrics": result_metrics, "device": device_block}
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["kernel_paths"] = tally
    result["numbers"] = {k: v for k, v in numbers.items()
                         if isinstance(v, float)}
    result["compared"] = verdict["compared"]
    for name, (value, limit) in verdict["compared"].items():
        say(f"compared {name} {value:.6g} limit {limit:.6g}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="FILE", help="copy the traced "
                    "run's .xplane.pb here, to look at it by hand "
                    "(python3 -m benchmark.trace_reduce FILE)")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), keep_trace=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
