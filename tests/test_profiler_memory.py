"""``utils/profiler`` memory accounting: the documented
``memory_stats`` return schema (graftlint PT605 reconciles the
compiled per-device manifest against exactly this accounting), the
activations / temp-estimator hooks, and ``device_peak_bytes``'s
None-means-unmeasured contract on CPU.
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import create_mesh
from paddle_tpu.utils.profiler import (device_peak_bytes, memory_stats,
                                       tree_device_bytes)


def test_device_peak_bytes_is_none_not_zero_on_cpu():
    """XLA:CPU exposes no peak-allocation counter: the result is None
    ("unmeasured"), NEVER 0 — a caller that treated it as 0 would let
    any admission budget pass on a CPU dryrun. memory_stats
    omits the key entirely in that case."""
    peak = device_peak_bytes()
    assert peak is None or (isinstance(peak, int) and peak > 0)
    stats = memory_stats({"w": jnp.ones((4, 4))})
    if peak is None:  # the CPU container path — always taken in CI
        assert "device_peak_bytes" not in stats
        assert stats.get("device_peak_bytes") != 0


def test_memory_stats_schema_and_hooks():
    """The documented return schema: params always, slots/avg from
    opt_state, act bytes from the activations hook, temp bytes from
    the estimator hook (silent when the estimator reports None)."""
    mesh = create_mesh(n_data=8)
    params = {"w": jax.device_put(jnp.ones((128, 16)),
                                  NamedSharding(mesh, P()))}
    opt = {"slots": {"w": {"m": jax.device_put(
        jnp.ones((128, 16)), NamedSharding(mesh, P("data", None)))}},
        "avg": {"w": jnp.ones((128, 16))}}
    batch = {"x": jax.device_put(jnp.ones((8, 128)),
                                 NamedSharding(mesh, P("data", None)))}
    stats = memory_stats(params, opt, activations=batch,
                         temp_estimator=lambda: 12345)
    assert stats["param_bytes_per_device"] == 128 * 16 * 4  # replicated
    assert stats["slot_bytes_per_device"] == 128 * 16 * 4 // 8  # 1/N
    assert stats["avg_bytes_per_device"] == 128 * 16 * 4
    assert stats["act_bytes_per_device"] == 8 * 128 * 4 // 8
    assert stats["temp_bytes_per_device"] == 12345
    # hooks absent -> keys absent (schema is explicit about presence)
    bare = memory_stats(params)
    assert set(bare) <= {"param_bytes_per_device", "device_peak_bytes"}
    # an estimator that cannot measure reports None -> key omitted,
    # same None-not-0 discipline as device_peak_bytes
    stats = memory_stats(params, temp_estimator=lambda: None)
    assert "temp_bytes_per_device" not in stats


def test_memory_stats_temp_estimator_accepts_compiled_executable():
    """The documented estimator shape: lambda over a compiled
    executable's memory_analysis() — the pass-5 manifest's temp figure
    and the profiler's then agree by construction."""
    compiled = jax.jit(lambda x: jnp.sort(x)).lower(
        jnp.ones((256,))).compile()
    stats = memory_stats(
        {}, temp_estimator=lambda: compiled.memory_analysis()
        .temp_size_in_bytes)
    assert stats["temp_bytes_per_device"] == int(
        compiled.memory_analysis().temp_size_in_bytes)


def test_tree_device_bytes_counts_shard_not_global():
    mesh = create_mesh(n_data=8)
    sharded = jax.device_put(jnp.ones((64, 4)),
                             NamedSharding(mesh, P("data", None)))
    assert tree_device_bytes([sharded]) == 64 * 4 * 4 // 8


def test_memory_stats_reports_fsdp_packed_param_bytes():
    """FSDP param accounting needs no special case: the packed (N,
    chunk) leaves carry their P(fsdp) sharding, so memory_stats reads
    the 1/N per-device bytes straight from the REAL shardings — the
    figure --show_step_breakdown logs and PT605 reconciles against
    the compiled fsdp_train manifest."""
    mesh = create_mesh(n_fsdp=8)
    packed = jax.device_put(jnp.ones((8, 16)),
                            NamedSharding(mesh, P("fsdp", None)))
    stats = memory_stats({"w": packed})
    assert stats["param_bytes_per_device"] == 8 * 16 * 4 // 8


def test_memory_stats_reports_gathered_buffer_peak():
    """The r18 overlap plane: memory_stats surfaces the TRANSIENT
    gathered-buffer peak the fsdp updater computes (two layers live
    under double-buffering, one under the sync spelling) as its own
    key — it is temp memory, not resident params, so it must not fold
    into param_bytes_per_device."""
    stats = memory_stats({}, gather_peak=4096)
    assert stats["gathered_peak_bytes_per_device"] == 4096
    assert "gathered_peak_bytes_per_device" not in memory_stats({})
    # and the human-readable status line renders it like any other
    # *_bytes_per_device figure
    from paddle_tpu.utils.profiler import memory_status
    assert "gathered_peak" in memory_status({}, gather_peak=4096)


def test_fsdp_overlap_stats_exposed_comm_split():
    from paddle_tpu.utils.profiler import fsdp_overlap_stats

    sync = fsdp_overlap_stats(6, False)
    assert sync["fsdp_exposed_collectives"] == 12  # every gather+reduce
    assert sync["fsdp_exposed_comm_frac"] == 1.0
    over = fsdp_overlap_stats(6, True)
    assert over["fsdp_exposed_collectives"] == 2  # first gather+last reduce
    assert abs(over["fsdp_exposed_comm_frac"] - 2 / 12) < 1e-12
    assert fsdp_overlap_stats(0, True)["fsdp_exposed_collectives"] == 0


def test_gather_peak_is_adjacent_pair_under_overlap():
    """FsdpUpdater.gather_peak_bytes: largest single gathered layer
    under the sync spelling, largest ADJACENT PAIR in prefetch-schedule
    order under the overlap chain (exactly two buffers ever live)."""
    from paddle_tpu.optim.zero1 import FsdpUpdater, overlap_spelling
    from paddle_tpu.optim import Adam

    mesh = create_mesh(n_fsdp=8)
    params = {"a": jnp.ones((8, 16)), "b": jnp.ones((24, 16)),
              "c": jnp.ones((16, 16))}
    upd = FsdpUpdater(Adam(learning_rate=0.1), mesh, params)
    assert len(upd.plan) == 3
    sizes = {n: 8 * upd.plan[n][2] * 4 for n in upd.plan}
    order = upd.schedule
    with overlap_spelling("off"):
        assert upd.gather_peak_bytes() == max(sizes.values())
    with overlap_spelling("force"):
        want = max(sizes[a] + sizes[b]
                   for a, b in zip(order, order[1:]))
        assert upd.gather_peak_bytes() == want
