"""Compile a cell's train step for a described ``v5e:2x2`` here in the
sandbox, with no chip: ``memory_analysis()`` per device, the Mosaic
custom calls and the collectives, so that a later issue can reckon a
cell's bytes before it spends chip time.

    python3 -m benchmark.rehearse <workload> [--kernels pallas|ref]
    python3 -m benchmark.rehearse --config benchmark/configs/lstm_imdb_h1280.json \\
        --traffic <mix> --chips 4    (a cell not registered yet: the mix is
                                      traffic/<mix>.json beside configs/)

Nothing runs and nothing here is a measurement. The trainer is built on
virtual CPU devices (as many as the cell has chips), then its step is
lowered for the described TPU devices with the same shardings. Off the
TPU the program's kernels take their ``ref`` path; ``--kernels pallas``
(the default) steers them onto the compiled path from here, as the chip
would, without a new option in the program.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload", nargs="?")
    ap.add_argument("--config", help="a configuration file, for a cell "
                    "that BENCHMARK.json does not hold yet")
    ap.add_argument("--traffic")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--kernels", default="pallas", choices=("pallas", "ref"))
    ap.add_argument("--benchmark-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)

    if args.workload:
        from benchmark.run import load_cell
        _bench, cell, cfg, data_root = load_cell(args.benchmark_file,
                                                 args.workload)
    else:
        import json
        with open(args.config) as f:
            cfg = json.load(f)
        data_root = os.path.dirname(os.path.dirname(
            os.path.abspath(args.config)))
        cell = {"traffic": args.traffic, "chips": args.chips}
        args.workload = f"{cfg['name']}.{args.traffic}"
    chips = int(cell["chips"])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}").strip()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    from benchmark import check, program, traffic
    from benchmark.reference import plain
    from paddle_tpu.ops import common
    from paddle_tpu.parallel import mesh as mesh_lib

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mix = traffic.load(cell["traffic"], data_root)
    ref_name = cfg.get("reference", cfg["name"])
    leaves = check.reference_module(ref_name).leaves(cfg)
    prog = program.Program(cfg, mix, plain.make_weights(leaves, 0))
    tr = prog.trainer
    batches = traffic.Batches(cfg["inputs"], dict(mix, pool=1), 0)
    feed = prog.feeder(prog.rows(batches.at(0)))
    if prog.mesh is not None:
        feed = mesh_lib.shard_batch(feed, prog.mesh)
        described = Mesh(
            np.array(topo.devices[:chips]).reshape(prog.mesh.devices.shape),
            prog.mesh.axis_names)

        def place(x):
            return NamedSharding(described, x.sharding.spec)
    else:
        described = None
        one = SingleDeviceSharding(topo.devices[0])

        def place(x):
            return one

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place(x))

    params, opt_state, feed = jax.tree_util.tree_map(
        shape, (tr.params, tr.opt_state, feed))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=place(None)
                               if described is None else
                               NamedSharding(described,
                                             jax.sharding.PartitionSpec()))
    npass = jax.ShapeDtypeStruct((), jnp.int32, sharding=key.sharding)
    tr.mesh = described                 # the step mesh read at trace time
    with common.force_mode(args.kernels), \
            common.record_dispatch() as tally:
        compiled = tr._train_step.lower(
            params, opt_state, feed, key, npass, None).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    limit = 16_909_336_064              # bytes_limit of a v5e (PERF.md)
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(f"{args.workload}: compiled for {chips} of v5e:2x2, kernels "
          f"{args.kernels}, paths {tally}")
    print(f"  per device: arguments {ma.argument_size_in_bytes:,} outputs "
          f"{ma.output_size_in_bytes:,} aliased {ma.alias_size_in_bytes:,} "
          f"temporaries {ma.temp_size_in_bytes:,} -> {per_device:,} bytes "
          f"= {100 * per_device / limit:.1f}% of {limit:,}")
    print(f"  tpu_custom_call: "
          f"{len(re.findall('custom_call_target=.tpu_custom_call', text))}")
    for kind in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                 "collective-permute"):
        n = len(re.findall(rf" {kind}(?:-start)?\(", text))
        if n:
            print(f"  {kind}: {n}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
