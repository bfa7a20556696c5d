"""How the readings behind a cell's limits are taken (PERF.md, Probes M
and P), kept so that a later issue can take them again for a new cell.
Not part of a run of the benchmark. Many seeds in one process:

    chiprun -- python3 -m benchmark.probes.probe <workload> --seeds 11,2147483659 \\
        [--program config,bf16,highest] [--arith carry,store,fp8+store] [--faults] \\
        [--against stated,highest] [--elements] [--memory] [--set optimizer.args.l2_rate=0]

For every seed the plain reference follows the first three steps in each
arithmetic of ``--against`` (``stated``: what the configuration states;
``highest``: float32 at ``highest``, the second witness). Against each:

- ``--program``: the program's own first steps through the window's
  call, as configured (``config``), on its bfloat16 path (``bf16``, the
  LSTM's control) or traced under ``jax.default_matmul_precision
  ("highest")`` (``highest``: does the program side with the float32
  reference once its products are float32?);
- ``--arith``: the reference put in the program's place in a lower
  precision (``ARITHS``; ``carry`` rounds only what the recurrences
  carry, ``store`` what every layer hands on, on top of what is stated);
- ``--faults``: half of every batch left out, the mean over the rest.

``--elements`` adds, per leaf, the share of the first gradient's
elements whose sign differs from the reference's and the share of the
gradient's squared norm they hold. ``--memory`` is Probe M:
``memory_stats()`` beside ``memory_analysis()`` of the compiled step.
Every reading goes to ``chiprun_out/probe_<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import time

import jax
import numpy as np

from benchmark import check, program, traffic
from benchmark.reference import plain
from benchmark.run import ROOT, WARM_STEPS, load_cell
from benchmark.window import Window

FP8 = "float8_e4m3fn"
ARITHS = {"carry": {"carry": "bfloat16"}, "store": {"store": "bfloat16"},
          "bf16": {"operand": "bfloat16"},
          "bf16+store": {"operand": "bfloat16", "store": "bfloat16"},
          "fp8": {"operand": FP8}, "fp8+store": {"operand": FP8, "store": FP8}}
NUMBERS = ("loss_first", "loss", "grad_median", "grad", "grad_diff_median",
           "grad_diff", "change_median", "change")


def say(*a):
    print("[probe]", *a, flush=True)


def drive(cfg, mix, leaves, seed, how, memory):
    """The program's first steps through the window's own call."""
    batches = traffic.Batches(cfg["inputs"], mix, seed)
    scope = jax.default_matmul_precision("highest") if how == "highest" \
        else contextlib.nullcontext()
    with scope:
        prog = program.Program(
            cfg, mix, plain.make_weights(leaves, seed),
            compute_dtype="bfloat16" if how == "bf16" else "config")
        rec = check.Recorder(cfg, plain.trained(leaves),
                             lambda: plain.make_weights(leaves, seed),
                             prog.slot, prog.params)
        win = Window(prog, batches, seconds=0.0,
                     warm_steps=10 if memory else WARM_STEPS, recorder=rec)
        t0 = time.perf_counter()
        with program.kernel_tally() as tally:
            win.run()
    say(f"  program {how}: {len(win.costs)} steps in "
        f"{time.perf_counter() - t0:.1f}s; paths {json.dumps(tally)}; "
        f"costs {win.costs[:3]}")
    if memory:
        stats = jax.local_devices()[0].memory_stats() or {}
        say("  M memory_stats", {k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "bytes_limit")})
        ma = prog.step_memory(batches.at(0))
        say("  M memory_analysis", {k: getattr(ma, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")})
    got = rec.readings()
    prog.free()
    del prog, win, rec
    gc.collect()
    return got


def elements(got: dict, ref: dict) -> dict:
    """Per leaf: the share of elements (of those the reference moves)
    whose first gradient has the other sign, and the share of the
    reference gradient's squared norm in them."""
    out = {}
    for name, r in ref.items():
        g = np.asarray(got[name])
        r = np.asarray(r)
        live = r != 0
        flipped = live & (np.sign(g) != np.sign(r))
        out[name] = [float(flipped.sum() / max(live.sum(), 1)),
                     float(np.square(r[flipped]).sum()
                           / max(np.square(r).sum(), 1e-300))]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", default="config")
    ap.add_argument("--arith", default="")
    ap.add_argument("--against", default="stated")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--elements", action="store_true")
    ap.add_argument("--memory", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="dotted.path=json over the configuration")
    ap.add_argument("--benchmark-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    say("device", program.start(require_tpu=not args.cpu))
    _bench, cell, cfg, data_root = load_cell(args.benchmark_file,
                                             args.workload)
    for item in args.set:
        path, value = item.split("=")
        *parents, key = path.split(".")
        node = cfg
        for part in parents:
            node = node[part]
        node[key] = json.loads(value)
    mix = traffic.load(cell["traffic"], data_root)
    ref_name = cfg.get("reference", cfg["name"])
    leaves = check.reference_module(ref_name).leaves(cfg)
    stated = cfg["precision"].get("reference", {})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    log = open(os.path.join(out, f"probe_{args.workload}.jsonl"), "a")

    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        batches = traffic.Batches(cfg["inputs"], mix, seed)
        labels = [int(np.sum(batches.at(i)["label"] == 1)) for i in range(3)]
        say(f"== {args.workload} seed {seed} set {args.set} rows with "
            f"label 1 in the first three batches {labels}")

        def weights():
            return plain.make_weights(leaves, seed)

        def follow(arith, **kw):
            return check.follow(ref_name, cfg, weights(), batches.at,
                                plain.Arith(**arith), **kw)

        refs = {}
        for name in args.against.split(","):
            refs[name] = follow(stated if name == "stated" else {},
                                keep_first_grad=True)
            say(f" reference {name}: losses {refs[name]['loss']}")

        def show(tag, got):
            first = got.pop("first_grad")
            for name, ref in refs.items():
                # the difference of the two first gradients, leaf by leaf
                diff = {n: float(np.linalg.norm(
                    np.asarray(first[n], np.float64)
                    - np.asarray(ref["first_grad"][n], np.float64)))
                    for n in ref["grad"]}
                c = check.compare(got, dict(ref, grad_diff=diff))
                row = {"seed": seed, "set": args.set, "what": tag,
                       "against": name, "labels": labels,
                       "numbers": {n: c[n] for n in c if n != "left_out"}}
                if args.elements:
                    row["elements"] = elements(first, ref["first_grad"])
                log.write(json.dumps(row) + "\n")
                log.flush()
                say(f"  {tag:26s} vs {name:8s}", " ".join(
                    f"{n} {c[n]:.2e}" for n in NUMBERS), "worst at",
                    c["grad_at"], c["grad_diff_at"], c["change_at"])
                if args.elements:
                    say("     sign differs, share of elements / of the "
                        "gradient's square:", " ".join(
                            f"{n} {a:.3f}/{b:.1e}" for n, (a, b)
                            in row["elements"].items()))

        for how in filter(None, args.program.split(",")):
            show("program " + how, drive(cfg, mix, leaves, seed, how,
                                         memory=args.memory and k == 0))
        if args.faults:
            got = check.follow(
                ref_name, cfg, weights(),
                lambda i: {n: v[:len(v) // 2]
                           for n, v in batches.at(i).items()},
                plain.Arith(**stated), keep_first_grad=True)
            show("half of the batch left out", got)
        for tag in filter(None, args.arith.split(",")):
            show("reference as " + tag,
                 follow({**stated, **ARITHS[tag]}, keep_first_grad=True))
        del refs
        gc.collect()


if __name__ == "__main__":
    main()
