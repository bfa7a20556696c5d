r"""How long a cell's train step takes to trace, lower and compile for a
described ``v5e:2x2``, with no chip, and how many Mosaic calls the
lowered module and the compiled program hold: what a warm run's
``setup_s`` still pays when the persistent compilation cache serves the
compile (its key is the lowered module, so tracing and lowering run
every time).

    python3 tools/step_setup_times.py <workload>     from a checkout's root

The trainer is built as ``python3 -m benchmark.rehearse`` builds it, on
a virtual CPU device, and its step is traced, lowered and compiled for
the described chip with the kernels on their compiled path. Mosaic
calls are counted by kernel: in the lowered text by the kernel's name
(a ``pallas_call`` emitted once serves every site that calls its jitted
entry), in the compiled text by the entry's ``jit(...)`` in the call's
``op_name`` (every site, after XLA inlines the entries). Times are the
sandbox's CPU's and vary from run to run; they say which phase grows,
not what the chip's host takes.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys
import time


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__)
        return 2
    workload = argv[0]
    sys.path.insert(0, os.getcwd())
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import check, program, traffic
    from benchmark.reference import plain
    from benchmark.run import load_cell
    from paddle_tpu.ops import common

    jax.config.update("jax_enable_compilation_cache", False)
    _bench, cell, cfg, data_root = load_cell("BENCHMARK.json", workload)
    if int(cell["chips"]) != 1:
        print(f"{workload}: {cell['chips']} chips; one-chip cells only")
        return 2
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    mix = traffic.load(cell["traffic"], data_root)
    leaves = check.reference_module(cfg.get("reference", cfg["name"])).leaves(
        cfg)
    prog = program.Program(cfg, mix, plain.make_weights(leaves, 0))
    tr = prog.trainer
    batches = traffic.Batches(cfg["inputs"], dict(mix, pool=1), 0)
    feed = prog.feeder(prog.rows(batches.at(0)))

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    args = jax.tree_util.tree_map(shape, (tr.params, tr.opt_state, feed))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    npass = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    with common.force_mode("pallas"):
        t0 = time.perf_counter()
        traced = tr._train_step.trace(*args, key, npass, None)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
    low = collections.Counter(re.findall(
        r'stablehlo\.custom_call @tpu_custom_call\(.*?kernel_name = "(\w+)"',
        lowered.as_text()))
    comp = collections.Counter(
        re.search(r"jit\((\w+)\)/pallas_call", line).group(1)
        if re.search(r"jit\((\w+)\)/pallas_call", line) else "other"
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    print(json.dumps({
        "workload": workload, "trace_s": round(t1 - t0, 3),
        "lower_s": round(t2 - t1, 3), "compile_s": round(t3 - t2, 3),
        "lowered_mosaic": dict(low), "lowered_mosaic_total": sum(low.values()),
        "compiled_mosaic": dict(comp),
        "compiled_mosaic_total": sum(comp.values())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
