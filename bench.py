"""Benchmark harness.

``python bench.py`` (default path) is ONE process on the chip: it runs
the two measurements that have history — the IMDB-style LSTM train step
(batch 64, hidden 256, seqlen 100, dict 30k; the reference's headline
RNN benchmark, ``benchmark/paddle/rnn/rnn.py``) and the ResNet-50 train
step (fp32, batch 64; imgs/sec/chip and MFU with FLOPs from XLA's cost
analysis of the compiled step) — and prints one JSON row per
measurement, each naming its device. It exits non-zero when the backend
is not a TPU, when the device kind has no entry in ``PEAK_FLOPS``, or
when any phase raises. No probe, no retry, no fallback to an older
capture; the last capture on record is ``BENCH_LIVE_r03.json`` (history).

The ``--<mode>`` mains (``--zero1``, ``--serving``, ...) are CPU-side
structure benches: their counts are facts, their timings are not speed
claims (ROADMAP D2).
"""

from __future__ import annotations

import json
import os
import sys
import time

BATCH, HIDDEN, SEQLEN, VOCAB = 64, 256, 100, 30000
ITERS = int(os.environ.get("BENCH_ITERS", "100"))
RESNET_BATCH = int(os.environ.get("BENCH_RESNET_BATCH", "64"))
RESNET_ITERS = int(os.environ.get("BENCH_RESNET_ITERS", "30"))

# bf16 peak FLOP/s per chip, keyed by ``device_kind`` as JAX reports it
# (Google Cloud TPU documentation, per-generation system architecture
# pages; v5e: 197 TFLOP/s bf16). The MFU denominator — a device that is
# not here is an error, never a default.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS:
        raise SystemExit(
            f"bench: no peak FLOP/s on record for device kind "
            f"{device_kind!r}; add it to PEAK_FLOPS with its source")
    return PEAK_FLOPS[device_kind]


def _timed_chain(run_steps, fetch, n_long, n_short):
    """Steady-state seconds/step: chain n steps device-side (dispatch is
    asynchronous), close the window with ``fetch`` (a device→host read of
    one scalar, which waits for everything queued before it), and take
    the difference quotient of a long and a short chain so the constant
    dispatch + fetch latency cancels. Best of two per chain length."""

    def once(n):
        t0 = time.perf_counter()
        run_steps(n)
        fetch()
        return time.perf_counter() - t0

    n_short = min(n_short, n_long - 1)  # keep the quotient well-defined
    t_short = min(once(n_short) for _ in range(2)) if n_short else 0.0
    t_long = min(once(n_long) for _ in range(2))
    return max(t_long - t_short, 1e-9) / (n_long - n_short)


def bench_lstm(compute_dtype=None):
    import jax
    import numpy as np
    from paddle_tpu.config import dsl
    from paddle_tpu.data import (DataFeeder, integer_value,
                                 integer_value_sequence)
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGD

    dsl.reset()
    cost, out, _ = lstm_text_classifier(
        vocab_size=VOCAB, embed_dim=128, hidden=HIDDEN, num_layers=2,
        classes=2)
    trainer = SGD(cost=cost, update_equation=Adam(learning_rate=2e-3),
                  compute_dtype=compute_dtype)

    rng = np.random.RandomState(0)
    feeder = DataFeeder({"words": integer_value_sequence(VOCAB),
                         "label": integer_value(2)}, pad_multiple=SEQLEN)
    batch = [(list(rng.randint(0, VOCAB, size=SEQLEN)),
              int(rng.randint(0, 2))) for _ in range(BATCH)]
    feed = feeder(batch)

    rng_key = jax.random.PRNGKey(0)
    state = {"m": None}

    def run_steps(n):
        nonlocal rng_key
        for _ in range(n):
            rng_key, step_key = jax.random.split(rng_key)
            trainer.params, trainer.opt_state, metrics = trainer._train_step(
                trainer.params, trainer.opt_state, feed, step_key, 0)
            state["m"] = metrics

    def fetch():
        return float(state["m"]["cost"])

    run_steps(3)  # warmup / compile
    fetch()
    return _timed_chain(run_steps, fetch, ITERS, max(ITERS // 10, 1)) * 1e3


def bench_resnet50(compute_dtype=None, batch=None):
    """ResNet-50 train step: imgs/sec/chip and MFU (flops from XLA cost
    analysis / wall time / device peak). ``compute_dtype="bfloat16"`` runs
    mixed precision: f32 master params, bf16 forward/backward feeding the
    MXU at twice the f32 rate. ``batch`` overrides RESNET_BATCH (the bf16
    run uses 256 per the round-3 verdict: small batches under-fill the
    MXU)."""
    batch = batch or RESNET_BATCH
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.models import resnet
    from paddle_tpu.optim import Momentum
    from paddle_tpu.trainer import SGD

    dsl.reset()
    cost, out, _ = resnet(depth=50, classes=1000, image_size=224)
    trainer = SGD(cost=cost,
                  update_equation=Momentum(learning_rate=0.1, momentum=0.9),
                  compute_dtype=compute_dtype)

    rng = np.random.RandomState(0)
    feed = {
        "image": Argument(value=jnp.asarray(
            rng.rand(batch, 224 * 224 * 3), jnp.float32)),
        "label": Argument(value=jnp.asarray(
            rng.randint(0, 1000, size=batch), jnp.int32)),
    }

    key = jax.random.PRNGKey(0)
    lowered = jax.jit(
        lambda p, o, f, k: trainer._train_step(p, o, f, k, 0)).lower(
            trainer.params, trainer.opt_state, feed, key)
    compiled = lowered.compile()
    flops_per_step = float(compiled.cost_analysis().get("flops", 0.0))

    state = {"params": trainer.params, "opt": trainer.opt_state, "m": None}

    def run_steps(n):
        for _ in range(n):
            state["params"], state["opt"], state["m"] = compiled(
                state["params"], state["opt"], feed, key)

    def fetch():
        return float(state["m"]["cost"])

    run_steps(2)  # warmup
    fetch()
    sec_per_step = _timed_chain(run_steps, fetch, RESNET_ITERS,
                                max(RESNET_ITERS // 10, 1))

    kind = jax.devices()[0].device_kind
    peak = peak_flops(kind)
    mfu = (flops_per_step / sec_per_step / peak) if flops_per_step else None
    tag = "resnet50_bf16" if compute_dtype else "resnet50"
    return {
        f"{tag}_imgs_per_sec_per_chip": round(batch / sec_per_step, 1),
        f"{tag}_step_ms": round(sec_per_step * 1000.0, 2),
        f"{tag}_batch": batch,
        f"{tag}_mfu": round(mfu, 4) if mfu is not None else None,
        f"{tag}_flops_per_step": flops_per_step or None,
        "device_kind": kind,
    }


def bench_input_pipeline(decode_ms=None, batches=None, batch_size=24):
    """Input-pipeline A/B (CPU-side): steps/s and host-blocked fraction
    for the SAME provider-fed LSTM config with the async prefetch
    pipeline off vs on, under a synthetic per-batch host decode cost
    (default 5 ms — the acceptance shape of ISSUE r06). CPU-runnable
    (``python bench.py --input-pipeline`` wrote BENCH_r06.json).
    ``data_wait_frac`` = fraction of step wall time the
    trainer thread is blocked on data (data-wait + host h2d/decode) —
    the quantity prefetch exists to drive to zero."""
    import numpy as np
    from paddle_tpu.config import dsl
    from paddle_tpu.data import (DataFeeder, integer_value,
                                 integer_value_sequence)
    from paddle_tpu.data.provider import provider
    from paddle_tpu.data.reader import batch as batch_reader
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGD

    decode_ms = float(os.environ.get("BENCH_IP_DECODE_MS", "5.0")
                      if decode_ms is None else decode_ms)
    batches = int(os.environ.get("BENCH_IP_BATCHES", "30")
                  if batches is None else batches)
    vocab, seqlen = 1000, 32
    dsl.reset()
    cost, out, _ = lstm_text_classifier(
        vocab_size=vocab, embed_dim=32, hidden=48, num_layers=1, classes=2)
    trainer = SGD(cost=cost, update_equation=Adam(learning_rate=1e-3))

    types = {"words": integer_value_sequence(vocab), "label": integer_value(2)}

    @provider(input_types=types, should_shuffle=False)
    def corpus(settings):
        rng = np.random.RandomState(0)
        for _ in range(batches * batch_size):
            yield (list(rng.randint(0, vocab, size=seqlen)),
                   int(rng.randint(0, 2)))

    base_feeder = DataFeeder(types, pad_multiple=seqlen)

    def slow_feeder(b):
        time.sleep(decode_ms / 1e3)  # synthetic decode cost
        return base_feeder(b)

    import itertools
    reader = batch_reader(corpus.as_reader(), batch_size, drop_last=True)
    # compile outside the measured passes (same shapes throughout:
    # fixed batch, pad_multiple = seqlen)
    trainer.train(lambda: itertools.islice(reader(), 2),
                  feeder=base_feeder, num_passes=1)

    def measure(async_on):
        trainer.train(reader, feeder=slow_feeder, num_passes=1,
                      async_load_data=async_on)
        s = trainer.step_breakdown()
        return (s["steps_per_sec"],
                s["data_wait_frac"] + s["h2d_frac"], s["steps"])

    sync_sps, sync_wait, n1 = measure(False)
    async_sps, async_wait, n2 = measure(True)
    return {
        "input_pipeline_steps_per_sec": round(async_sps, 3),
        "input_pipeline_steps_per_sec_sync": round(sync_sps, 3),
        "input_pipeline_speedup": round(async_sps / sync_sps, 3)
        if sync_sps else None,
        "data_wait_frac": round(async_wait, 4),
        "data_wait_frac_sync": round(sync_wait, 4),
        "input_pipeline_decode_ms": decode_ms,
        "input_pipeline_batches": min(n1, n2),
        "input_pipeline_batch_size": batch_size,
        "input_pipeline_recompiles": trainer.recompile_guard.count,
    }


def bench_zero1(batches=None, batch_size=64):
    """ZeRO-1 A/B: the SAME LSTM-classifier config trained over the full
    device mesh with the replicated optimizer update vs the sharded one
    (``--use_zero1``), reporting steps/s and the per-device
    param/optimizer-slot byte split from ``utils/profiler.memory_stats``.
    CPU-runnable (``python bench.py --zero1`` forces the
    8-virtual-device CPU mesh and writes BENCH_r07.json). Adam (2 slots) is the
    headline shape: slot bytes per device should drop ~N× on an N-way
    data axis."""
    import jax
    import numpy as np
    from paddle_tpu.config import dsl
    from paddle_tpu.data import (DataFeeder, integer_value,
                                 integer_value_sequence)
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.optim import Adam
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.trainer import SGD
    from paddle_tpu.utils.profiler import memory_stats

    batches = int(os.environ.get("BENCH_Z1_BATCHES", "20")
                  if batches is None else batches)
    vocab, seqlen = 5000, 32
    n_dev = len(jax.devices())
    mesh = create_mesh(n_data=n_dev)

    types = {"words": integer_value_sequence(vocab),
             "label": integer_value(2)}
    rng = np.random.RandomState(0)
    data = [(list(rng.randint(0, vocab, size=seqlen)),
             int(rng.randint(0, 2))) for _ in range(batch_size)]
    feeder = DataFeeder(types, pad_multiple=seqlen)

    def reader():
        for _ in range(batches):
            yield data

    def build(zero1):
        dsl.reset()
        cost, out, _ = lstm_text_classifier(
            vocab_size=vocab, embed_dim=64, hidden=96, num_layers=1,
            classes=2)
        tr = SGD(cost=cost, update_equation=Adam(learning_rate=1e-3),
                 mesh=mesh, seed=0)
        # compile + zero1 conversion outside the measured passes
        tr.train(lambda: iter([data, data]), feeder=feeder, num_passes=1,
                 zero1=zero1)
        return tr

    trainers = {False: build(False), True: build(True)}
    best = {False: 0.0, True: 0.0}
    # interleaved best-of-R passes: this host's throughput drifts by tens
    # of percent on the scale of one pass (shared box, one core), so a
    # single A/B pair is meaningless — like _timed_chain's min-of-runs,
    # each mode keeps its best pass and the modes alternate so drift
    # hits both equally
    for _ in range(int(os.environ.get("BENCH_Z1_ROUNDS", "3"))):
        for zero1, tr in trainers.items():
            tr.train(reader, feeder=feeder, num_passes=1, zero1=zero1)
            best[zero1] = max(best[zero1],
                              tr.step_breakdown()["steps_per_sec"])
    rep_sps, z_sps = best[False], best[True]
    rep_mem = memory_stats(trainers[False].params, trainers[False].opt_state)
    z_mem = memory_stats(trainers[True].params, trainers[True].opt_state)
    out = {
        "zero1_devices": n_dev,
        "zero1_optimizer": "adam",
        "zero1_steps_per_sec": round(z_sps, 3),
        "replicated_steps_per_sec": round(rep_sps, 3),
        "zero1_vs_replicated_steps": (round(z_sps / rep_sps, 3)
                                      if rep_sps else None),
        "replicated_slot_bytes_per_device": rep_mem["slot_bytes_per_device"],
        "zero1_slot_bytes_per_device": z_mem["slot_bytes_per_device"],
        "zero1_slot_bytes_reduction": round(
            rep_mem["slot_bytes_per_device"]
            / max(z_mem["slot_bytes_per_device"], 1), 2),
        "param_bytes_per_device": z_mem["param_bytes_per_device"],
        "zero1_batches": batches,
        "zero1_batch_size": batch_size,
    }
    for tag, mem in (("replicated", rep_mem), ("zero1", z_mem)):
        if "device_peak_bytes" in mem:
            out[f"{tag}_device_peak_bytes"] = mem["device_peak_bytes"]
    return out


def bench_fsdp(batches=None, batch_size=64):
    """Full-FSDP A/B: the SAME LSTM-classifier config trained at the
    same data-parallel degree with replicated parameters (the whole
    device set on the ``data`` axis) vs flat-packed 1/N parameters
    (the whole set on the ``fsdp`` axis, ``--fsdp``), reporting
    steps/s and the per-device param/slot byte split from
    ``utils/profiler.memory_stats``. The param-bytes ratio is ASSERTED
    ~N× in-bench (the ISSUE 15 acceptance claim, the same figure the
    PT602 law pins on the audited fsdp_train program); the step-time
    ratio is recorded honestly — on the 1-core virtual mesh the
    per-layer gathers are pure dispatch overhead with no memory to
    save, so expect <1×; on a real TPU the gathers ride ICI and the
    ratio is the number to watch. CPU-runnable
    (``python bench.py --fsdp`` writes BENCH_r17.json)."""
    import jax
    import numpy as np
    from paddle_tpu.config import dsl
    from paddle_tpu.data import (DataFeeder, integer_value,
                                 integer_value_sequence)
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.optim import Adam
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.trainer import SGD
    from paddle_tpu.utils.profiler import memory_stats

    batches = int(os.environ.get("BENCH_FSDP_BATCHES", "20")
                  if batches is None else batches)
    vocab, seqlen = 5000, 32
    n_dev = len(jax.devices())
    meshes = {False: create_mesh(n_data=n_dev),
              True: create_mesh(n_fsdp=n_dev)}

    types = {"words": integer_value_sequence(vocab),
             "label": integer_value(2)}
    rng = np.random.RandomState(0)
    data = [(list(rng.randint(0, vocab, size=seqlen)),
             int(rng.randint(0, 2))) for _ in range(batch_size)]
    feeder = DataFeeder(types, pad_multiple=seqlen)

    def reader():
        for _ in range(batches):
            yield data

    def build(fsdp):
        dsl.reset()
        cost, out, _ = lstm_text_classifier(
            vocab_size=vocab, embed_dim=64, hidden=96, num_layers=1,
            classes=2)
        tr = SGD(cost=cost, update_equation=Adam(learning_rate=1e-3),
                 mesh=meshes[fsdp], seed=0)
        # compile + packing conversion outside the measured passes
        tr.train(lambda: iter([data, data]), feeder=feeder, num_passes=1,
                 fsdp=fsdp)
        return tr

    trainers = {False: build(False), True: build(True)}
    assert trainers[True]._fsdp is not None, "fsdp stood down in-bench"
    best = {False: 0.0, True: 0.0}
    # interleaved best-of-R passes (the host-drift rule: each mode
    # keeps its best pass, modes alternate so drift hits both equally)
    for _ in range(int(os.environ.get("BENCH_FSDP_ROUNDS", "3"))):
        for fsdp, tr in trainers.items():
            tr.train(reader, feeder=feeder, num_passes=1, fsdp=fsdp)
            best[fsdp] = max(best[fsdp],
                             tr.step_breakdown()["steps_per_sec"])
    rep_sps, f_sps = best[False], best[True]
    rep_mem = memory_stats(trainers[False].params,
                           trainers[False].opt_state)
    f_mem = memory_stats(trainers[True].params, trainers[True].opt_state)
    # the honest replicated denominator is the FULL model from shapes:
    # a trained run's placed bytes can be understated when XLA's output
    # propagation opportunistically shards a param output over data
    rep_mem["param_bytes_per_device"] = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize
        for v in trainers[False]._params_for_save().values())
    p_ratio = (rep_mem["param_bytes_per_device"]
               / max(f_mem["param_bytes_per_device"], 1))
    # the acceptance claim is a correctness property, not a perf
    # number: assert it in-bench so a drifted artifact can't hide it.
    # The bar scales with the REAL mesh (a four-chip host has 4
    # devices, where ~4x is perfect and 6.0 would always fail)
    assert p_ratio > 0.75 * n_dev, (
        f"fsdp param bytes/device only dropped {p_ratio:.2f}x on the "
        f"{n_dev}-way fsdp axis (want ~{n_dev}x)")
    out = {
        "fsdp_devices": n_dev,
        "fsdp_optimizer": "adam",
        "fsdp_steps_per_sec": round(f_sps, 3),
        "replicated_steps_per_sec": round(rep_sps, 3),
        "fsdp_vs_replicated_steps": (round(f_sps / rep_sps, 3)
                                     if rep_sps else None),
        "replicated_param_bytes_per_device":
            rep_mem["param_bytes_per_device"],
        "fsdp_param_bytes_per_device": f_mem["param_bytes_per_device"],
        "fsdp_param_bytes_reduction": round(p_ratio, 2),
        "replicated_slot_bytes_per_device":
            rep_mem["slot_bytes_per_device"],
        "fsdp_slot_bytes_per_device": f_mem["slot_bytes_per_device"],
        "fsdp_slot_bytes_reduction": round(
            rep_mem["slot_bytes_per_device"]
            / max(f_mem["slot_bytes_per_device"], 1), 2),
        "fsdp_batches": batches,
        "fsdp_batch_size": batch_size,
    }
    for tag, mem in (("replicated", rep_mem), ("fsdp", f_mem)):
        if "device_peak_bytes" in mem:
            out[f"{tag}_device_peak_bytes"] = mem["device_peak_bytes"]
    return out


def bench_overlap(batches=None, batch_size=64):
    """FSDP gather-overlap x fused-kernel 2x2 A/B (r18): the SAME
    LSTM-classifier config trained on the fsdp mesh under every
    combination of {sync, overlap-forced} gather spelling x {inline,
    fused} LSTM-cell + optimizer kernels. Reports each arm's best-of
    steps/s (interleaved rounds, the host-drift rule) plus the
    exposed-collective split from ``StepBreakdown``: the sync spelling
    exposes every gather + reduce (2 per layer), the double-buffered
    chain exposes only the first gather and last reduce — the
    ``fsdp_exposed_*`` keys are the structural claim a 1-core CPU
    can certify even though its step-time ratio is dispatch-bound
    (on ICI the step time is where the overlap pays). All four arms'
    final params are ASSERTED bitwise identical in-bench — the
    overlap chain is an ``optimization_barrier`` (identity on
    values) and the fused kernels' fallback spelling IS the inline
    math, so a nonzero diff is a correctness bug, not noise.
    CPU-runnable (``python bench.py --overlap`` writes
    BENCH_r18.json)."""
    import jax
    import numpy as np
    from paddle_tpu import kernels
    from paddle_tpu.config import dsl
    from paddle_tpu.data import (DataFeeder, integer_value,
                                 integer_value_sequence)
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.optim import Adam
    from paddle_tpu.optim import zero1
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.trainer import SGD

    batches = int(os.environ.get("BENCH_OVERLAP_BATCHES", "12")
                  if batches is None else batches)
    vocab, seqlen = 5000, 32
    n_dev = len(jax.devices())
    mesh = create_mesh(n_fsdp=n_dev)

    types = {"words": integer_value_sequence(vocab),
             "label": integer_value(2)}
    rng = np.random.RandomState(0)
    data = [(list(rng.randint(0, vocab, size=seqlen)),
             int(rng.randint(0, 2))) for _ in range(batch_size)]
    feeder = DataFeeder(types, pad_multiple=seqlen)

    def reader():
        for _ in range(batches):
            yield data

    def arm_ctx(overlap, fused):
        """The trace-time switches an arm runs under — held for BOTH
        the compiling warmup and the timed passes ("force"/"off"
        rather than auto so the A/B is honest on CPU too)."""
        import contextlib
        st = contextlib.ExitStack()
        st.enter_context(
            zero1.overlap_spelling("force" if overlap else "off"))
        st.enter_context(kernels.fused_rnn(fused))
        st.enter_context(kernels.fused_optimizer(fused))
        return st

    def build(overlap, fused):
        dsl.reset()
        cost, out, _ = lstm_text_classifier(
            vocab_size=vocab, embed_dim=64, hidden=96, num_layers=1,
            classes=2)
        tr = SGD(cost=cost, update_equation=Adam(learning_rate=1e-3),
                 mesh=mesh, seed=0)
        with arm_ctx(overlap, fused):
            # compile + packing conversion outside the measured passes
            tr.train(lambda: iter([data, data]), feeder=feeder,
                     num_passes=1, fsdp=True, fsdp_overlap=overlap)
        return tr

    arms = [(False, False), (True, False), (False, True), (True, True)]
    trainers = {a: build(*a) for a in arms}
    best = {a: 0.0 for a in arms}
    for _ in range(int(os.environ.get("BENCH_OVERLAP_ROUNDS", "2"))):
        for a, tr in trainers.items():
            with arm_ctx(*a):
                tr.train(reader, feeder=feeder, num_passes=1)
            best[a] = max(best[a],
                          tr.step_breakdown()["steps_per_sec"])
    # the acceptance claim is bitwise neutrality of BOTH planes:
    # every arm must land on the baseline's exact trajectory
    base = {k: np.asarray(jax.device_get(v)) for k, v in
            trainers[(False, False)]._params_for_save().items()}
    for a in arms[1:]:
        for k, v in trainers[a]._params_for_save().items():
            assert np.array_equal(base[k], np.asarray(jax.device_get(v))), \
                f"arm overlap={a[0]} fused={a[1]} diverged at {k}"
    sb_off = trainers[(False, False)].step_breakdown()
    sb_on = trainers[(True, False)].step_breakdown()
    with arm_ctx(True, False):
        peak_overlap = trainers[(True, False)]._gather_peak()
    with arm_ctx(False, False):
        peak_sync = trainers[(False, False)]._gather_peak()
    return {
        "overlap_devices": n_dev,
        "overlap_off_steps_per_sec": round(best[(False, False)], 3),
        "overlap_on_steps_per_sec": round(best[(True, False)], 3),
        "overlap_vs_sync_steps": (
            round(best[(True, False)] / best[(False, False)], 3)
            if best[(False, False)] else None),
        "fused_steps_per_sec": round(best[(False, True)], 3),
        "overlap_fused_steps_per_sec": round(best[(True, True)], 3),
        "exposed_collectives_overlap_off":
            int(sb_off["fsdp_exposed_collectives"]),
        "exposed_collectives_overlap_on":
            int(sb_on["fsdp_exposed_collectives"]),
        "exposed_comm_frac_overlap_off":
            round(sb_off["fsdp_exposed_comm_frac"], 4),
        "exposed_comm_frac_overlap_on":
            round(sb_on["fsdp_exposed_comm_frac"], 4),
        "overlap_gathers_per_step": int(sb_on["fsdp_gathers_per_step"]),
        "overlap_gather_peak_bytes": int(peak_overlap or 0),
        "sync_gather_peak_bytes": int(peak_sync or 0),
        "overlap_bitwise_identical": True,
        "overlap_batches": batches,
        "overlap_batch_size": batch_size,
    }


def bench_pipeline(batches=None, batch_size=64, hidden=256, n_stages=4,
                   layers_per_stage=4, microbatches=None):
    """Pipeline-parallel A/B: the SAME deep-MLP config (per-layer device
    attrs, `n_stages` stages x `layers_per_stage` fc layers) trained
    unpipelined over a pure-DP mesh vs pipelined over a (data, pipe)
    mesh with the GPipe schedule (`--parallel_nn`), interleaved best-of-R
    per the host-drift rules (CLAUDE.md). Reports steps/s both modes, the
    bubble-fraction estimate from `utils/profiler.pipeline_bubble_stats`,
    and the per-device body-parameter bytes (the stage-stacked layout
    holds 1/S per device). CPU-runnable
    (``python bench.py --pipeline`` -> BENCH_r08.json); on real ICI the
    ppermute hand-off overlaps compute — on the 1-core virtual mesh the
    schedule's win cannot show, so the honest headline here is
    correctness + bubble accounting, with steps/s recorded for drift
    context."""
    import jax
    import numpy as np
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.optim import Adam
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.trainer import SGD
    from paddle_tpu.utils.profiler import memory_stats

    batches = int(os.environ.get("BENCH_PIPE_BATCHES", "12")
                  if batches is None else batches)
    n_dev = len(jax.devices())
    S = min(n_stages, n_dev)
    n_data = max(n_dev // S, 1)
    M = microbatches or int(os.environ.get("BENCH_PIPE_MICROBATCHES", "8"))

    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    X = rng.randn(batch_size, hidden).astype(np.float32)
    Y = rng.randint(0, 10, size=batch_size).astype(np.int32)
    feed = {"x": Argument(value=jnp.asarray(X)),
            "label": Argument(value=jnp.asarray(Y))}

    def reader():
        for _ in range(batches):
            yield feed

    def build(pipelined):
        dsl.reset()
        x = dsl.data(name="x", size=hidden)
        lbl = dsl.data(name="label", size=10)
        h = x
        for s in range(S):
            for j in range(layers_per_stage):
                h = dsl.fc(input=h, size=hidden, act="tanh",
                           name=f"blk{s}_{j}", layer_attr={"device": s})
        out = dsl.fc(input=h, size=10, act="softmax", name="out")
        cost = dsl.classification_cost(input=out, label=lbl)
        mesh = (create_mesh(n_data=n_data, n_pipe=S) if pipelined
                else create_mesh(n_data=n_dev, n_model=1))
        tr = SGD(cost=cost, update_equation=Adam(learning_rate=1e-3),
                 mesh=mesh, seed=0)
        # compile outside the measured passes
        tr.train(lambda: iter([feed, feed]), num_passes=1,
                 pipeline={"microbatches": M} if pipelined else None)
        return tr

    trainers = {False: build(False), True: build(True)}
    best = {False: 0.0, True: 0.0}
    for _ in range(int(os.environ.get("BENCH_PIPE_ROUNDS", "3"))):
        for pipelined, tr in trainers.items():
            tr.train(reader, num_passes=1)
            best[pipelined] = max(best[pipelined],
                                  tr.step_breakdown()["steps_per_sec"])
    pipe_tr = trainers[True]
    s = pipe_tr.step_breakdown()
    body_keys = pipe_tr._pipe.stacked_keys() if pipe_tr._pipe else []
    pipe_body = memory_stats({k: pipe_tr.params[k] for k in body_keys})
    flat = trainers[False]
    flat_body = memory_stats({k: v for k, v in flat.params.items()
                              if k.startswith("_blk")})
    return {
        "pipeline_devices": n_dev,
        "pipeline_stages": s.get("pipeline_stages", S),
        "pipeline_microbatches": s.get("pipeline_microbatches", M),
        "pipeline_bubble_frac": round(s.get("pipeline_bubble_frac", 0.0),
                                      4),
        "pipeline_bubble_frac_per_stage": [
            round(v, 4) for v in s.get("pipeline_bubble_frac_per_stage",
                                       [])],
        "pipeline_steps_per_sec": round(best[True], 3),
        "unpipelined_steps_per_sec": round(best[False], 3),
        "pipeline_vs_unpipelined_steps": (
            round(best[True] / best[False], 3) if best[False] else None),
        "pipeline_body_param_bytes_per_device":
            pipe_body["param_bytes_per_device"],
        "unpipelined_body_param_bytes_per_device":
            flat_body["param_bytes_per_device"],
        "pipeline_body_param_bytes_reduction": round(
            flat_body["param_bytes_per_device"]
            / max(pipe_body["param_bytes_per_device"], 1), 2),
        "pipeline_batches": batches,
        "pipeline_batch_size": batch_size,
        "pipeline_hidden": hidden,
        "pipeline_layers_per_stage": layers_per_stage,
    }


def bench_serving(n_requests=None, rounds=None):
    """Serving A/B: the SAME LSTM-classifier deploy model behind the
    dynamic micro-batching engine (max_batch=8, small coalesce window)
    vs batch-size-1 serving (max_batch=1 — every request its own device
    launch), under an identical synthetic OPEN-LOOP load (arrivals on a
    fixed clock, independent of completions — the regime where queueing
    either explodes or doesn't). Interleaved best-of-R per CLAUDE.md's
    host-drift rule. Reports completed-requests/s and the p50/p99 total
    latency from the serving metrics plane, plus batch occupancy and the
    guard-asserted compile count. The offered rate is calibrated to ~2x
    the measured single-request service rate, so the unbatched mode MUST
    queue: batching's win is throughput at *bounded* p99, not a faster
    single request. CPU-runnable (``python bench.py --serving`` ->
    BENCH_r09.json)."""
    import numpy as np
    from paddle_tpu.config import dsl
    from paddle_tpu.data import integer_value, integer_value_sequence
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.serving import ServingEngine, ServingPredictor
    from paddle_tpu.trainer.trainer import Topology

    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "64")
                     if n_requests is None else n_requests)
    rounds = int(os.environ.get("BENCH_SERVE_ROUNDS", "3")
                 if rounds is None else rounds)
    vocab, seqlen = 1000, 32
    dsl.reset()
    cost, out, _ = lstm_text_classifier(
        vocab_size=vocab, embed_dim=32, hidden=48, num_layers=1, classes=2)
    topo = Topology(cost)
    import jax
    net = topo.network
    params = net.init_params(jax.random.PRNGKey(0))
    feeding = {"words": integer_value_sequence(vocab),
               "label": integer_value(2)}
    rng = np.random.RandomState(0)

    def mk_sample():
        return (list(rng.randint(0, vocab, size=seqlen)),
                int(rng.randint(0, 2)))

    samples = [mk_sample() for _ in range(n_requests)]

    def build(max_batch):
        pred = ServingPredictor(
            topo.graph, params, [out.name], feeding,
            batch_buckets=[b for b in (1, 2, 4, 8) if b <= max_batch],
            length_buckets=[seqlen])
        eng = ServingEngine(pred, max_batch=max_batch,
                            batch_timeout_ms=2.0,
                            queue_depth=n_requests + 8)
        eng.start(warmup=True)
        return eng

    engines = {"batched": build(8), "unbatched": build(1)}

    # calibrate the open-loop rate off the UNBATCHED engine's sequential
    # service time (max_batch=1 dispatches immediately, so this is the
    # true per-request cost with no coalescing window in it); offer ~2x
    # that rate to both modes — the regime where batch-size-1 serving
    # must queue and dynamic batching must absorb
    t0 = time.perf_counter()
    for _ in range(10):
        engines["unbatched"].infer(samples[0])
    single_ms = (time.perf_counter() - t0) / 10 * 1e3
    interval = single_ms / 1e3 / 2.0
    # fresh metrics for BOTH modes so the published p50/p99/occupancy
    # reflect only the measured open-loop rounds (the 10 zero-queue
    # calibration requests would otherwise skew the unbatched reservoir)
    from paddle_tpu.serving import ServingMetrics
    for eng in engines.values():
        eng.metrics = ServingMetrics()

    def run(eng):
        from paddle_tpu.serving import ServingError
        reqs = []
        t_start = time.perf_counter()
        for i, s in enumerate(samples):
            target = t_start + i * interval
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            try:
                reqs.append(eng.submit(s))
            except ServingError:
                # shed / dead worker: not-ok, but the A/B must still
                # finish and report (a dead engine reads as ~zero
                # throughput + its fatal in hot_path_recompiles)
                pass
        answered = [r.event.wait(120.0) for r in reqs]
        done = time.perf_counter()
        # only requests that were actually ANSWERED cleanly count — a
        # hung/dead engine must read as zero throughput, not success
        ok = sum(1 for got, r in zip(answered, reqs)
                 if got and r.error is None)
        return ok / (done - t_start)

    best = {}
    for _ in range(rounds):
        for mode, eng in engines.items():
            tput = run(eng)
            best[mode] = max(best.get(mode, 0.0), tput)
    res = {"serving_requests": n_requests,
           "serving_open_loop_interval_ms": round(interval * 1e3, 3),
           "serving_batched_rps": round(best["batched"], 2),
           "serving_unbatched_rps": round(best["unbatched"], 2),
           "serving_batched_vs_unbatched_rps": round(
               best["batched"] / max(best["unbatched"], 1e-9), 3)}
    for mode, eng in engines.items():
        snap = eng.metrics.snapshot()
        lat = snap["latency_ms"]["total"]
        res[f"serving_{mode}_p50_ms"] = lat["p50_ms"]
        res[f"serving_{mode}_p99_ms"] = lat["p99_ms"]
        res[f"serving_{mode}_queue_wait_p99_ms"] = (
            snap["latency_ms"]["queue_wait"]["p99_ms"])
        res[f"serving_{mode}_occupancy"] = snap["batch_occupancy"]["mean"]
        res[f"serving_{mode}_batches"] = snap["batches_total"]
        # the hardened guard raises (killing the worker) on any hot-path
        # compile — a clean worker proves zero; a dead one is recorded
        res[f"serving_{mode}_hot_path_recompiles"] = (
            0 if eng.fatal is None else repr(eng.fatal)[:120])
        eng.shutdown()
    return res


def bench_serving_quant(rounds=None, calls=None):
    """Quantized-serving three-way A/B: the SAME LSTM-classifier deploy
    model merged fp32 / ``--quantize=bf16`` / ``--quantize=int8``, each
    artifact loaded by the serving predictor exactly as deploy would
    (storage-dtype leaves + fused dequant view) and WARMED THROUGH THE
    ACCURACY GATE in-bench — a drifted quantized artifact aborts the
    bench instead of publishing a speedup for a model that answers
    wrong. Interleaved best-of-R per CLAUDE.md's host-drift rule: the
    three precision tiers alternate within every round and each
    reports its best per-round median batch-predict latency. The gate
    deltas and verdict ride the artifact (PT401's ``serving_quant``
    schema refuses the speedup without them). CPU-runnable
    (``python bench.py --quant`` -> BENCH_r19.json)."""
    import shutil
    import tempfile

    import numpy as np

    import jax
    from paddle_tpu import quant as quant_lib
    from paddle_tpu.config import dsl
    from paddle_tpu.data import integer_value, integer_value_sequence
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.serving import ServingPredictor
    from paddle_tpu.trainer.merge_model import merge_model
    from paddle_tpu.trainer.trainer import Topology

    rounds = int(os.environ.get("BENCH_QUANT_ROUNDS", "3")
                 if rounds is None else rounds)
    calls = int(os.environ.get("BENCH_QUANT_CALLS", "12")
                if calls is None else calls)
    vocab, seqlen = 1000, 32
    dsl.reset()
    cost, out, _ = lstm_text_classifier(
        vocab_size=vocab, embed_dim=32, hidden=48, num_layers=1,
        classes=2)
    topo = Topology(cost)
    params = topo.network.init_params(jax.random.PRNGKey(0))
    params = {k: np.asarray(v) for k, v in params.items()}
    feeding = {"words": integer_value_sequence(vocab),
               "label": integer_value(2)}
    golden = quant_lib.golden_section(topo.graph, params, [out.name],
                                      feeding)
    rng = np.random.RandomState(0)
    rows = [(list(rng.randint(0, vocab, size=seqlen)),
             int(rng.randint(0, 2))) for _ in range(8)]

    preds = {}
    versions = {}
    tmp = tempfile.mkdtemp(prefix="bench_quant_")
    try:
        for dt in ("fp32", "bf16", "int8"):
            path = os.path.join(tmp, f"{dt}.ptmodel")
            if dt == "fp32":
                merge_model(path, topo.graph, params,
                            outputs=[out.name])
            else:
                q, meta = quant_lib.quantize_params(params, dt,
                                                    sparse_names=set())
                merge_model(path, topo.graph, q, outputs=[out.name],
                            quant=meta, golden=golden)
            pred = ServingPredictor.from_merged(
                path, feeding, batch_buckets=[8],
                length_buckets=[seqlen])
            # warmup REPLAYS THE GOLDEN GATE for the quantized tiers:
            # a drifted artifact raises QuantGateError right here
            pred.warmup()
            preds[dt] = pred
            versions[dt] = pred.model_version

        def one_call(pred):
            t0 = time.perf_counter()
            pred.predict_rows(rows)
            return (time.perf_counter() - t0) * 1e3

        best = {}
        for _ in range(rounds):
            for dt, pred in preds.items():  # interleaved within round
                ms = sorted(one_call(pred) for _ in range(calls))
                med = ms[len(ms) // 2]
                best[dt] = min(best.get(dt, float("inf")), med)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    assert len(set(versions.values())) == 3, (
        f"precision tiers must publish distinct versions: {versions}")
    res = {"quant_calls": calls, "quant_rows_per_call": len(rows),
           "quant_model_versions": versions}
    for dt in ("fp32", "bf16", "int8"):
        res[f"quant_{dt}_p50_ms"] = round(best[dt], 3)
    res["quant_bf16_vs_fp32"] = round(best["bf16"] / best["fp32"], 3)
    res["quant_int8_vs_fp32"] = round(best["int8"] / best["fp32"], 3)
    gates = {dt: preds[dt].quant_gate for dt in ("bf16", "int8")}
    for dt, g in gates.items():
        res[f"quant_gate_delta_{dt}"] = g["max_delta"]
        res[f"quant_gate_tol_{dt}"] = g["tol"]
    res["quant_gate_passed"] = all(g["passed"] for g in gates.values())
    return res


def bench_decode(rounds=None, calls=None):
    """Decode A/B (two axes, interleaved best-of-R per CLAUDE.md's
    host-drift rule):

    1. **Early-exit chunked search vs full scan** — the same beam search
       over a short-output workload (every request finishes in <= 2
       steps, max_length 64): the chunked ``lax.while_loop`` search
       exits at the first chunk boundary where every beam finished, so
       it pays ~chunk steps where the full scan pays 64. Tokens/scores
       are asserted byte-identical between modes (the exactness claim of
       ``docs/generation.md``), and steps-executed are reported.
    2. **Continuous batching vs convoy batching** — the same serving
       engine over a mixed burst (mostly-short + a long tail): convoy
       mode holds every coalesced batch until its slowest lane's search
       returns; continuous mode retires finished lanes and admits queued
       requests at every chunk boundary. Completed-requests/s, plus lane
       occupancy / mid-decode admissions / steps saved from the metrics
       plane, and the hardened-guard recompile assertion for both.

    The decode model is length-controlled by construction (EOS logit =
    3 * sum(memory), memory boots from tanh(2*src)): positive src
    finishes in <= 2 steps, negative src never emits EOS and runs the
    full max_length — a deterministic convoy workload with margins too
    fat for cross-batch-width numeric drift to flip a token. CPU-runnable
    (``python bench.py --decode`` -> BENCH_r10.json)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.config import dsl
    from paddle_tpu.core.generation import SequenceGenerator
    from paddle_tpu.core.network import Network
    from paddle_tpu.core.registry import get_layer_impl
    from paddle_tpu.data import dense_vector
    from paddle_tpu.serving import ServingEngine, ServingPredictor

    rounds = int(os.environ.get("BENCH_DECODE_ROUNDS", "3")
                 if rounds is None else rounds)
    calls = int(os.environ.get("BENCH_DECODE_CALLS", "4")
                if calls is None else calls)
    # sized so step compute (not per-chunk host dispatch) dominates on
    # the 1-core host — the regime a real accelerator is always in
    V, E, H, K, L, CHUNK, B = 2048, 64, 256, 4, 64, 8, 8

    dsl.reset()
    src = dsl.data("src", size=H)
    boot = dsl.fc(src, size=H, act="tanh", name="boot", bias_attr=False)

    def step(prev_emb):
        m = dsl.memory(name="h", size=H, boot_layer=boot)
        h = dsl.fc([prev_emb, m], size=H, act="tanh", name="h",
                   bias_attr=False)
        return dsl.fc(h, size=V, act="softmax", name="prob",
                      bias_attr=False)

    dsl.beam_search(
        step, [dsl.GeneratedInput(size=V, embedding_name="gen_emb",
                                  embedding_size=E)],
        bos_id=0, eos_id=1, beam_size=K, max_length=L, name="gen")
    graph = dsl.current_graph()
    net = Network(graph, outputs=["boot"])
    params = dict(net.init_params(jax.random.PRNGKey(0)))
    boot_key = next(k for k in params if "boot" in k)
    params[boot_key] = jnp.asarray(2.0 * np.eye(H, dtype=np.float32))
    for _, spec in get_layer_impl("beam_search_group").params(
            graph.layers["gen"], []).items():
        params[spec.absolute_name] = jnp.zeros(spec.shape, jnp.float32)
    params["_h.w1"] = jnp.asarray(np.eye(H, dtype=np.float32))
    u = np.zeros((H, V), np.float32)
    u[:, 1] = 3.0
    params["_prob.w0"] = jnp.asarray(u)
    params["gen_emb"] = jnp.zeros((V, E), jnp.float32)

    res = {"decode_max_length": L, "decode_chunk": CHUNK,
           "decode_beam": K, "decode_batch": B}

    # ---- axis 1: chunked early-exit vs full scan ---------------------
    from paddle_tpu.core.argument import Argument
    gen = SequenceGenerator(graph, "gen")
    srcv = jnp.asarray(np.ones((B, H), np.float32))  # all-short workload
    outer = net.apply(params, {"src": Argument(value=srcv)})

    def run_gen(full_scan):
        t, s, ln = gen.generate(params, outer, full_scan=full_scan,
                                decode_chunk=CHUNK)
        jax.block_until_ready(s)
        return np.asarray(t), np.asarray(s), gen.last_info

    full_out = run_gen(True)       # also warms both compiles
    chunk_out = run_gen(False)
    res["decode_bitwise_identical"] = bool(
        np.array_equal(full_out[0], chunk_out[0])
        and np.array_equal(full_out[1], chunk_out[1]))
    res["decode_steps_full"] = full_out[2]["decode_steps"]
    res["decode_steps_chunked"] = chunk_out[2]["decode_steps"]
    best = {"full": 0.0, "chunked": 0.0}
    for _ in range(rounds):
        for mode, fs in (("full", True), ("chunked", False)):
            t0 = time.perf_counter()
            for _ in range(calls):
                run_gen(fs)
            dt = time.perf_counter() - t0
            best[mode] = max(best[mode], calls * B / dt)
    res["decode_full_scan_gen_per_s"] = round(best["full"], 2)
    res["decode_chunked_gen_per_s"] = round(best["chunked"], 2)
    res["decode_chunked_vs_full_scan"] = round(
        best["chunked"] / max(best["full"], 1e-9), 3)

    # ---- axis 2: continuous vs convoy batching -----------------------
    n_requests = int(os.environ.get("BENCH_DECODE_REQUESTS", "32"))
    rng = np.random.RandomState(0)
    samples = [(([-1.0] * H,) if rng.rand() < 0.2 else ([1.0] * H,))
               for _ in range(n_requests)]

    def build(continuous):
        pred = ServingPredictor(graph, params, ["gen"],
                                {"src": dense_vector(H)},
                                batch_buckets=[1, 2, 4, 8],
                                gen_decode_chunk=CHUNK)
        return ServingEngine(pred, max_batch=8, batch_timeout_ms=2.0,
                             queue_depth=n_requests + 8,
                             continuous_batching=continuous).start()

    engines = {"continuous": build(True), "convoy": build(False)}
    best = {}
    for _ in range(rounds):
        for mode, eng in engines.items():
            t0 = time.perf_counter()
            reqs = [eng.submit(s, kind="generate") for s in samples]
            answered = [r.event.wait(300.0) for r in reqs]
            dt = time.perf_counter() - t0
            ok = sum(1 for got, r in zip(answered, reqs)
                     if got and r.error is None)
            best[mode] = max(best.get(mode, 0.0), ok / dt)
    res["serving_convoy_rps"] = round(best["convoy"], 2)
    res["serving_continuous_rps"] = round(best["continuous"], 2)
    res["serving_continuous_vs_convoy_rps"] = round(
        best["continuous"] / max(best["convoy"], 1e-9), 3)
    for mode, eng in engines.items():
        snap = eng.metrics.snapshot()
        res[f"serving_{mode}_decode_steps_p50"] = snap["decode_steps"]["p50"]
        res[f"serving_{mode}_steps_saved_total"] = (
            snap["decode_steps_saved_total"])
        # the hardened guard raises (killing the worker) on any hot-path
        # compile — a clean worker proves zero; a dead one is recorded
        res[f"serving_{mode}_hot_path_recompiles"] = (
            0 if eng.fatal is None else repr(eng.fatal)[:120])
    res["serving_continuous_lane_occupancy"] = (
        engines["continuous"].metrics.snapshot()["lane_occupancy"]["mean"])
    res["serving_continuous_admissions"] = (
        engines["continuous"].metrics.counters[
            "continuous_admissions_total"])
    for eng in engines.values():
        eng.shutdown()
    return res


def bench_autotune(rounds=None):
    """Self-tuning A/B (``python bench.py --autotune`` -> BENCH_r21.json
    plus the two committed ``WORKLOAD_r21_*.json`` traces):

    1. **Record** — drive each canonical mix (``serving/mixes.py``:
       the bursty classifier stream and the 20%-long-tail decode
       convoy) through its engine with the admission tap installed
       (``engine.workload_recorder``), snapshot the offered stream and
       commit it as ``WORKLOAD_r21_<mix>.json`` (the PT401 family; the
       replay tests rebuild these exact fleets from the same module).
    2. **Tune** — ``GridTuner`` coordinate descent over the
       hot-applicable knob grid, every candidate landed through the
       typed ``apply_config`` path on the LIVE engine and scored by
       replaying the committed trace against the declared SLO.
    3. **A/B** — hand-set defaults vs the tuned config, interleaved
       best-of-R per CLAUDE.md's host-drift rule, on the SLO score.
       The defaults shed structurally (queue narrower than the burst),
       so the ordering is count-driven, not a latency coin flip.
    4. **Determinism** — the tuned config replayed twice more: outcome
       counts must match EXACTLY and the score spread must stay within
       ``SCORE_DRIFT_BOUND`` — asserted in-bench, same contract the
       replay tests assert.

    ``failed_non_shed`` is SUMMED over EVERY replay this bench performs
    (record drive, calibration, grid search, A/B, determinism) and
    asserted zero — a dropped request anywhere is a bug, not a tuning
    datum. Zero hot-path recompiles across the whole knob sequence is
    asserted via the hardened guard (``eng.fatal is None``)."""
    from paddle_tpu.serving import mixes
    from paddle_tpu.serving.tuner import GridTuner, SLOTarget
    from paddle_tpu.serving.workload import (SCORE_DRIFT_BOUND, Workload,
                                             WorkloadRecorder,
                                             engine_dispatch, replay,
                                             replay_score)

    rounds = int(os.environ.get("BENCH_AUTOTUNE_ROUNDS", "3")
                 if rounds is None else rounds)
    here = os.path.dirname(os.path.abspath(__file__))
    res = {"autotune_mixes": [], "autotune_workloads": [],
           "autotune_drift_bound": SCORE_DRIFT_BOUND,
           "autotune_rounds": rounds}
    failed_total = 0  # summed over EVERY replay, never best-of'd

    # every grid value sits inside the warmed bucket menu ([1, 2, 4]
    # for both mixes) — the tuner explores, the menu edge stays a 409
    specs = [
        ("short_burst", {"batch_timeout_ms": [0.5, 2.0, 4.0],
                         "max_batch": [2, 4],
                         "queue_depth": [6, 64]}),
        ("convoy", {"batch_timeout_ms": [0.5, 2.0, 8.0],
                    "max_batch": [2, 4],
                    "queue_depth": [4, 64]}),
    ]
    for mix, grid in specs:
        build, make_pacer = mixes.MIXES[mix]
        eng = build()  # the hand-set defaults — the A side
        defaults = {k: v for k, v in eng.current_config().items()
                    if k in grid}
        disp = engine_dispatch(eng)

        def apply(cfg, eng=eng):
            # the shed watermark rides the queue depth here: applying a
            # deeper queue alone leaves the incumbent watermark clamped
            # at the OLD depth (apply_config never widens it silently),
            # which would pin the tuner in a coupled valley where
            # neither knob moves the shed count on its own
            d = dict(cfg)
            if "queue_depth" in d and "shed_watermark" not in d:
                d["shed_watermark"] = d["queue_depth"]
            eng.apply_config(d)

        # ---- 1. record the offered stream through the admission tap
        tap = WorkloadRecorder()
        eng.workload_recorder = tap
        drive = replay(make_pacer(), disp)
        eng.workload_recorder = None
        failed_total += drive["failed_non_shed"]
        trace_path = os.path.join(here, f"WORKLOAD_r21_{mix}.json")
        tap.snapshot(mix).save(trace_path)
        trace = Workload.load(trace_path)  # tune the COMMITTED artifact
        assert len(trace.events) == drive["offered"]

        # SLO calibrated against a generously provisioned replay of the
        # same trace (structural: both A/B sides face the same target,
        # so host drift moves both latency factors together)
        generous = {"queue_depth": max(grid["queue_depth"]),
                    "batch_timeout_ms": min(grid["batch_timeout_ms"]),
                    "max_batch": max(grid["max_batch"])}
        apply(generous)
        cal = replay(trace, disp)
        failed_total += cal["failed_non_shed"]
        slo = SLOTarget(p99_ms=4.0 * max(cal["p99_ms"] or 1.0, 1.0),
                        max_shed_rate=0.02)

        # ---- 2. offline descent, every candidate through apply_config
        def score_fn(cfg):
            nonlocal failed_total
            apply(cfg)
            s = replay_score(trace, disp, slo, rounds=1)
            failed_total += s["failed_non_shed"]
            return s["score"]

        tuner = GridTuner(grid, score_fn, base=defaults, sweeps=2)
        tuned, _ = tuner.tune()

        # ---- 3. defaults-vs-tuned, interleaved best-of-R
        best = {"default": None, "tuned": None}
        for _ in range(rounds):
            for side, cfg in (("default", defaults), ("tuned", tuned)):
                apply(cfg)
                s = replay_score(trace, disp, slo, rounds=1)
                failed_total += s["failed_non_shed"]
                if best[side] is None or s["score"] > best[side]["score"]:
                    best[side] = s
        d, t = best["default"], best["tuned"]
        assert t["score"] > d["score"], (
            f"{mix}: tuned {tuned} scored {t['score']:.3f} <= hand-set "
            f"defaults {defaults} at {d['score']:.3f}")

        # ---- 4. in-bench determinism: counts exact, score in bounds
        apply(tuned)
        r1 = replay_score(trace, disp, slo, rounds=1)
        r2 = replay_score(trace, disp, slo, rounds=1)
        failed_total += r1["failed_non_shed"] + r2["failed_non_shed"]
        for k in ("offered", "ok", "shed", "deadline_miss"):
            assert r1[k] == r2[k], (mix, k, r1[k], r2[k])
        drift = abs(r1["score"] - r2["score"])
        assert drift <= SCORE_DRIFT_BOUND, (mix, drift)
        # the whole knob sequence rode the hardened guard: any hot-path
        # compile would have killed the worker
        assert eng.fatal is None, repr(eng.fatal)
        eng.shutdown()

        res["autotune_mixes"].append(mix)
        res["autotune_workloads"].append(os.path.basename(trace_path))
        res[f"autotune_{mix}_events"] = len(trace.events)
        res[f"autotune_{mix}_slo_p99_ms"] = round(slo.p99_ms, 3)
        res[f"autotune_{mix}_default_config"] = defaults
        res[f"autotune_{mix}_tuned_config"] = tuned
        res[f"autotune_{mix}_grid_evals"] = len(tuner.history)
        res[f"autotune_{mix}_default_score"] = round(d["score"], 4)
        res[f"autotune_{mix}_tuned_score"] = round(t["score"], 4)
        res[f"autotune_{mix}_tuned_vs_default_score"] = round(
            t["score"] / max(d["score"], 1e-9), 3)
        res[f"autotune_{mix}_default_shed"] = d["shed"]
        res[f"autotune_{mix}_tuned_shed"] = t["shed"]
        res[f"autotune_{mix}_default_p99_ms"] = round(d["p99_ms"], 3)
        res[f"autotune_{mix}_tuned_p99_ms"] = round(t["p99_ms"], 3)
        res[f"autotune_{mix}_replay_drift"] = round(drift, 4)
        res[f"autotune_{mix}_hot_path_recompiles"] = 0

    res["fleet_failed_non_shed"] = failed_total
    assert failed_total == 0, f"replays dropped {failed_total} requests"
    return res


def bench_health(batches=None, batch_size=64, rounds=None):
    """Training-health overhead A/B (``python bench.py --health`` ->
    BENCH_r16.json + HEALTH_r16.json): the SAME LSTM-classifier config
    stepped with the health plane FULLY armed — per-layer stats fused
    into EVERY step (period=1, the worst case), sentry on, JSONL
    timeline appending — vs disarmed. Interleaved best-of-R per the
    host-drift rule (each mode keeps its best pass-median step time,
    modes alternate so drift hits both): the headline is the p50
    ratio. Bitwise trajectory identity is asserted IN-BENCH: after all
    rounds both trainers must hold bit-identical parameters, or this
    raises — the overhead number is only meaningful for a telemetry
    that changed nothing."""
    import time as _time

    import numpy as np

    from paddle_tpu.config import dsl
    from paddle_tpu.data import (DataFeeder, integer_value,
                                 integer_value_sequence)
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer import events as ev

    batches = int(os.environ.get("BENCH_HEALTH_BATCHES", "12")
                  if batches is None else batches)
    rounds = int(os.environ.get("BENCH_HEALTH_ROUNDS", "4")
                 if rounds is None else rounds)
    # hidden=256 on purpose: the param-stat reduction's cost is
    # ~constant per parameter (a handful of passes over params/grads)
    # while the step's compute scales with batch*seq*hidden^2, so a
    # toy-sized model would measure XLA:CPU's reduce throughput, not
    # the telemetry's overhead on a real training step (on TPU the
    # same reductions fuse into the update for ~free)
    vocab, seqlen = 5000, 64
    types = {"words": integer_value_sequence(vocab),
             "label": integer_value(2)}
    rng = np.random.RandomState(0)
    data = [(list(rng.randint(0, vocab, size=seqlen)),
             int(rng.randint(0, 2))) for _ in range(batch_size)]
    feeder = DataFeeder(types, pad_multiple=seqlen)

    def reader():
        for _ in range(batches):
            yield data

    import tempfile
    log_path = os.path.join(tempfile.mkdtemp(prefix="bench_health_"),
                            "timeline.jsonl")

    def build(armed):
        dsl.reset()
        cost, out, _ = lstm_text_classifier(
            vocab_size=vocab, embed_dim=64, hidden=256, num_layers=1,
            classes=2)
        tr = SGD(cost=cost, update_equation=Adam(learning_rate=1e-3),
                 seed=0)
        health = ({"period": 1, "sentry": True,
                   "log_path": log_path} if armed else None)
        # warm/compile outside the measured passes (both variants)
        tr.train(lambda: iter([data, data]), feeder=feeder,
                 num_passes=1, health=health)
        return tr

    trainers = {False: build(False), True: build(True)}

    def timed_pass(tr):
        ts = []

        def handler(e):
            if isinstance(e, ev.BeginIteration):
                ts.append(_time.perf_counter())

        tr.train(reader, feeder=feeder, num_passes=1,
                 event_handler=handler)
        return float(np.median(np.diff(ts)))

    best = {False: float("inf"), True: float("inf")}
    for _ in range(rounds):
        for armed, tr in trainers.items():
            best[armed] = min(best[armed], timed_pass(tr))
    off_s, on_s = best[False], best[True]

    # the neutrality claim, asserted in-bench: identical batch/seed
    # streams => bit-identical parameters, or the ratio above measured
    # a telemetry that changed the training it observed
    import jax
    identical = True
    p_off = {k: np.asarray(jax.device_get(v))
             for k, v in trainers[False].params.items()}
    for k, v in trainers[True].params.items():
        if not np.array_equal(p_off[k], np.asarray(jax.device_get(v))):
            identical = False
            break
    if not identical:
        raise RuntimeError(
            "health telemetry changed the trajectory: stats-on params "
            "differ from stats-off after identical streams")

    hm = trainers[True]._health
    hm.close()
    from paddle_tpu.obs.events import load_timeline
    timeline = [r for r in load_timeline(log_path)
                if r.get("event") in ("step", "divergence")]
    snap = hm.snapshot()
    return {
        "health_period": 1,
        "health_sentry": True,
        "health_batches": batches,
        "health_rounds": rounds,
        "health_on_ms_per_step_p50": round(on_s * 1e3, 3),
        "health_off_ms_per_step_p50": round(off_s * 1e3, 3),
        "health_on_vs_off_p50": (round(on_s / off_s, 4)
                                 if off_s > 0 else None),
        "health_overhead_frac": (round(on_s / off_s - 1.0, 4)
                                 if off_s > 0 else None),
        "health_bitwise_identical": identical,
        "health_sentry_trips": snap["sentry_trips"],
        "health_timeline_events": len(timeline),
        "_health_timeline": timeline,  # stripped into HEALTH_r16.json
    }


def bench_fleet(rounds=None, n_requests=None):
    """Fleet serving A/B (``python bench.py --fleet`` -> BENCH_r13.json):

    1. **Cold start: live trace vs AOT cache** — the SAME LSTM deploy
       model built + warmed + answering its first request, (a) tracing
       every bucket variant live vs (b) deserializing the warmed menu
       from the AOT cache (``serving/aot_cache.py``). Interleaved
       best-of-R per CLAUDE.md's host-drift rule. This is the number
       that decides whether kill-and-respawn under load is a non-event:
       a respawned replica pays (b), not (a).
    2. **Kill-and-respawn under open-loop load** — three router-fronted
       replicas (each its own predictor, all warmed from the shared
       cache) under a fixed-rate open-loop request schedule; mid-run a
       seeded chaos fault kills one replica's serving worker
       (``serve_batch`` kill, the in-process SIGKILL analogue). The
       router fails the in-flight request over, ejects the replica, and
       respawns it from the cache. Reported: zero failed non-shed
       requests (asserted), fleet p50/p99 through the router, failover /
       respawn counters, and the respawn's warm time.
    """
    import tempfile
    import threading

    import numpy as np
    import jax
    from paddle_tpu.config import dsl
    from paddle_tpu.data import integer_value, integer_value_sequence
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.serving import (EngineTransport, Overloaded,
                                    ReplicaRouter, ServingEngine,
                                    ServingError, ServingPredictor)
    from paddle_tpu.testing import chaos
    from paddle_tpu.trainer.trainer import Topology

    rounds = int(os.environ.get("BENCH_FLEET_ROUNDS", "2")
                 if rounds is None else rounds)
    n_requests = int(os.environ.get("BENCH_FLEET_REQUESTS", "60")
                     if n_requests is None else n_requests)
    vocab, seqlen = 1000, 32
    dsl.reset()
    cost, out, _ = lstm_text_classifier(
        vocab_size=vocab, embed_dim=32, hidden=48, num_layers=1, classes=2)
    topo = Topology(cost)
    params = topo.network.init_params(jax.random.PRNGKey(0))
    feeding = {"words": integer_value_sequence(vocab),
               "label": integer_value(2)}
    rng = np.random.RandomState(0)

    def mk_sample():
        return (list(rng.randint(0, vocab, size=seqlen)),
                int(rng.randint(0, 2)))

    cache_dir = tempfile.mkdtemp(prefix="paddle_tpu_aot_bench_")

    def build_pred(cached: bool):
        return ServingPredictor(
            topo.graph, params, [out.name], feeding,
            batch_buckets=[1, 4], length_buckets=[seqlen],
            aot_cache=cache_dir if cached else None)

    sample = mk_sample()

    def cold_start_ms(cached: bool) -> float:
        """Build + warm + first answer, the full respawn path."""
        t0 = time.perf_counter()
        pred = build_pred(cached)
        pred.warmup()
        pred.predict_rows([sample])
        return 1e3 * (time.perf_counter() - t0)

    # prime the cache once (not timed as the cache arm — it is the live
    # arm's work product), then interleave live/cache rounds
    prime_ms = cold_start_ms(True)
    best = {"live": float("inf"), "cache": float("inf")}
    for _ in range(rounds):
        best["live"] = min(best["live"], cold_start_ms(False))
        best["cache"] = min(best["cache"], cold_start_ms(True))
    res = {
        "cold_start_live_ms": round(best["live"], 1),
        "cold_start_cache_ms": round(best["cache"], 1),
        "cold_start_live_vs_cache": round(
            best["live"] / max(best["cache"], 1e-9), 2),
        "cold_start_prime_ms": round(prime_ms, 1),
        "fleet_rounds": rounds,
    }

    # ---- kill-and-respawn under open-loop load -----------------------
    def build_engine():
        return ServingEngine(build_pred(True), max_batch=4,
                             batch_timeout_ms=2.0,
                             queue_depth=n_requests + 8
                             ).start(warmup=True)

    best_round = None
    failed_all_rounds = 0  # the zero-drop invariant is PER ROUND —
    # best-of-R applies to perf numbers, never to a correctness counter
    for _ in range(rounds):
        engines = [build_engine() for _ in range(3)]
        router = ReplicaRouter(
            [EngineTransport(e) for e in engines],
            spawn=lambda rid: EngineTransport(build_engine()),
            health_poll_ms=25.0).start()
        # calibrate the open-loop rate off sequential dispatches, then
        # offer ~2x that rate so queues form and failover runs hot
        t0 = time.perf_counter()
        for _ in range(8):
            router.dispatch(sample)
        interval = (time.perf_counter() - t0) / 8 / 2.0
        from paddle_tpu.serving import RouterMetrics
        router.metrics = RouterMetrics()
        # the seeded fault: kill whichever replica serves the Nth batch
        # mid-run; the schedule reproduces from the seed
        plan = chaos.FaultPlan(seed=13, faults=[
            {"type": "kill", "site": "serve_batch", "at": 6,
             "mode": "raise"}])
        counts = {"ok": 0, "shed": 0, "failed": 0}
        lock = threading.Lock()

        def one(s):
            from paddle_tpu.serving import Unavailable
            try:
                router.dispatch(s)
                key = "ok"
            except Unavailable:
                # NO ready replica = outage, not backpressure — it must
                # fail the zero-drop assertion (Unavailable subclasses
                # Overloaded, so this arm must come first)
                key = "failed"
            except Overloaded:
                key = "shed"  # typed backpressure is not a failure
            except ServingError:
                key = "failed"
            with lock:
                counts[key] += 1

        threads = []
        samples = [mk_sample() for _ in range(n_requests)]
        t_start = time.perf_counter()
        with chaos.chaos_plan(plan):
            for i, s in enumerate(samples):
                target = t_start + i * interval
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                th = threading.Thread(target=one, args=(s,))
                th.start()
                threads.append(th)
            for th in threads:
                th.join(120.0)
        elapsed = time.perf_counter() - t_start
        # give the health loop a beat to finish the respawn
        deadline = time.perf_counter() + 10.0
        while (time.perf_counter() < deadline
               and router.metrics.snapshot()["respawns_total"] < 1):
            time.sleep(0.05)
        snap = router.metrics.snapshot()
        health = router.fleet_health()
        round_res = {
            "fleet_requests": n_requests,
            "fleet_open_loop_interval_ms": round(interval * 1e3, 3),
            "fleet_ok": counts["ok"],
            "fleet_shed": counts["shed"],
            "fleet_failed_non_shed": counts["failed"],
            "fleet_rps": round(counts["ok"] / elapsed, 2),
            "fleet_p50_ms": snap["fleet_latency_ms"]["p50_ms"],
            "fleet_p99_ms": snap["fleet_latency_ms"]["p99_ms"],
            "fleet_failovers_total": snap["failovers_total"],
            "fleet_replica_deaths_total": snap["replica_deaths_total"],
            "fleet_respawns_total": snap["respawns_total"],
            "fleet_respawn_warm_ms": next(
                (round(r["last_spawn_ms"], 1)
                 for r in health["replicas"]
                 if r["last_spawn_ms"] is not None), None),
            "fleet_ready_after": health["ready_replicas"],
        }
        router.shutdown()
        failed_all_rounds += counts["failed"]
        # best-of across rounds: most clean answers, then lowest p99
        keyf = (round_res["fleet_ok"],
                -(round_res["fleet_p99_ms"] or 1e9))
        if best_round is None or keyf > best_round[0]:
            best_round = (keyf, round_res)
    res.update(best_round[1])
    # report (and assert) the SUM over every round: a round where the
    # kill DID fail requests must not hide behind a cleaner best-of
    res["fleet_failed_non_shed"] = failed_all_rounds
    # the acceptance invariant, asserted where the evidence is made:
    # a replica SIGKILL under load must not fail a single non-shed
    # request in ANY round (failover + respawn absorb it)
    assert failed_all_rounds == 0, res
    return res


def bench_fleet_autoscale():
    """Autoscale under a traffic ramp (``--fleet`` → BENCH_r14.json):
    one replica behind the router; open-loop traffic at ~3× its
    calibrated capacity makes the EWMA backlog cross the scale-up
    threshold, the autoscaler grows the fleet (warm via the shared AOT
    cache — this is the scale-up-latency half of the cold-start A/B),
    and sustained idle shrinks it back to the floor. Reported: the
    replica-count trajectory (must follow the ramp inside
    [min, max] — asserted), p99 through the ramp (bounded — asserted),
    zero failed non-shed (asserted), and the scale action counters.

    Honesty note (CLAUDE.md): on this 1-core host extra replicas add no
    real compute parallelism — the evidence here is the CONTROL LOOP
    (signal → sustained-threshold → bounded scaling → hysteresis back
    down), not a throughput win; on a pod each replica is its own chip.
    """
    import tempfile
    import threading

    import numpy as np
    import jax
    from paddle_tpu.config import dsl
    from paddle_tpu.data import integer_value, integer_value_sequence
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.serving import (Autoscaler, EngineTransport,
                                    InProcessFleet, Overloaded,
                                    ReplicaRouter, ServingEngine,
                                    ServingError, ServingPredictor)
    from paddle_tpu.trainer.trainer import Topology

    vocab, seqlen = 1000, 32
    n_ramp = int(os.environ.get("BENCH_AUTOSCALE_REQUESTS", "60"))
    max_replicas = 3
    dsl.reset()
    cost, out, _ = lstm_text_classifier(
        vocab_size=vocab, embed_dim=32, hidden=48, num_layers=1,
        classes=2)
    topo = Topology(cost)
    params = topo.network.init_params(jax.random.PRNGKey(0))
    feeding = {"words": integer_value_sequence(vocab),
               "label": integer_value(2)}
    rng = np.random.RandomState(0)

    def mk_sample():
        return (list(rng.randint(0, vocab, size=seqlen)),
                int(rng.randint(0, 2)))

    cache_dir = tempfile.mkdtemp(prefix="paddle_tpu_aot_scale_")

    def build_engine():
        pred = ServingPredictor(
            topo.graph, params, [out.name], feeding,
            batch_buckets=[1, 4], length_buckets=[seqlen],
            aot_cache=cache_dir)
        return ServingEngine(pred, max_batch=4, batch_timeout_ms=2.0,
                             queue_depth=n_ramp + 8
                             ).start(warmup=True)

    # scale-up latency warm-vs-cold: the FIRST engine build traces live
    # and populates the cache; every autoscale scale-up deserializes it
    t0 = time.perf_counter()
    first = build_engine()
    scaleup_cold_ms = 1e3 * (time.perf_counter() - t0)
    router = ReplicaRouter([EngineTransport(first)],
                           health_poll_ms=25.0).start()
    sample = mk_sample()
    # calibrate single-replica service time (per CLAUDE.md: no absolute
    # thresholds on a ±50%-drift host — everything relative to this)
    t0 = time.perf_counter()
    for _ in range(8):
        router.dispatch(sample)
    base_ms = 1e3 * (time.perf_counter() - t0) / 8
    from paddle_tpu.serving import RouterMetrics
    router.metrics = RouterMetrics()

    scaleup_ms = []

    def build():
        t0 = time.perf_counter()
        e = build_engine()
        scaleup_ms.append(1e3 * (time.perf_counter() - t0))
        return EngineTransport(e)

    fleet = InProcessFleet(router, build)
    counts = {"ok": 0, "shed": 0, "failed": 0}
    lock = threading.Lock()

    def one(s):
        try:
            router.dispatch(s)
            key = "ok"
        except Overloaded as e:
            from paddle_tpu.serving import Unavailable
            key = "failed" if isinstance(e, Unavailable) else "shed"
        except ServingError:
            key = "failed"
        with lock:
            counts[key] += 1

    # ---- the ramp: closed-loop saturation ---------------------------
    # single-dispatch rate understates capacity (the batcher coalesces
    # max_batch rows per launch), so pace-to-a-rate can sit inside
    # batched capacity on a fast host and never queue. A CLOSED loop
    # of many concurrent callers queues by construction —
    # host-drift-proof saturation, the same discipline as best-of-R.
    stop_load = threading.Event()
    pool = [mk_sample() for _ in range(32)]

    def worker(w):
        i = w
        while not stop_load.is_set():
            one(pool[i % len(pool)])
            i += 1

    ramp_s = float(os.environ.get("BENCH_AUTOSCALE_RAMP_S", "5.0"))
    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(64)]
    for th in threads:
        th.start()
    # thresholds SELF-CALIBRATE against the loaded signal: the first
    # second of the ramp (autoscaler not yet running) samples the
    # 1-replica backlog hint the policy will read; scale-up triggers at
    # half the typical loaded signal (2x crossing margin at any host
    # speed), scale-down just above the engine's IDLE floor (its
    # batch_timeout) — absolute ms thresholds would be host-drift bait
    samples = []
    cal_deadline = time.monotonic() + 1.0
    while time.monotonic() < cal_deadline:
        b = router.load_backlog_ms()
        if b is not None:
            samples.append(b)
        time.sleep(0.025)
    samples.sort()
    sig = (samples[len(samples) // 2] if samples
           and samples[len(samples) // 2] > 0
           else (samples[-1] if samples else 10.0))
    down_ms = max(1.6 * 2.0, 0.15 * sig)
    up_ms = max(2.2 * down_ms, 0.5 * sig)
    scaler = Autoscaler(
        fleet, min_replicas=1, max_replicas=max_replicas,
        up_backlog_ms=up_ms, down_backlog_ms=down_ms,
        sustain_up_s=0.2, sustain_down_s=1.0, cooldown_s=0.5,
        poll_ms=50.0).start()
    ramp_deadline = time.monotonic() + ramp_s
    while time.monotonic() < ramp_deadline:
        time.sleep(0.05)
    stop_load.set()
    for th in threads:
        th.join(120.0)
    ramp_snap = router.metrics.snapshot()
    peak = max(n for _, n in scaler.trajectory)
    # ---- sustained idle: the fleet must come back to the floor ------
    idle_deadline = time.monotonic() + 30.0
    while (fleet.replica_count() > 1
           and time.monotonic() < idle_deadline):
        time.sleep(0.1)
    scaler.stop()
    final = fleet.replica_count()
    traj = [n for _, n in scaler.trajectory]
    snap = router.metrics.snapshot()
    res = {
        "autoscale_closed_loop_callers": 64,
        "autoscale_ramp_s": ramp_s,
        "autoscale_base_ms": round(base_ms, 2),
        "autoscale_loaded_signal_ms": round(sig, 2),
        "autoscale_up_backlog_ms": round(up_ms, 2),
        "autoscale_down_backlog_ms": round(down_ms, 2),
        "autoscale_replica_trajectory": traj,
        "autoscale_trajectory_t_s": [t for t, _ in scaler.trajectory],
        "autoscale_peak_replicas": peak,
        "autoscale_final_replicas": final,
        "autoscale_min_replicas": 1,
        "autoscale_max_replicas": max_replicas,
        "autoscale_p99_ms": ramp_snap["fleet_latency_ms"]["p99_ms"],
        "autoscale_p50_ms": ramp_snap["fleet_latency_ms"]["p50_ms"],
        "autoscale_ok": counts["ok"],
        "autoscale_shed": counts["shed"],
        "autoscale_failed_non_shed": counts["failed"],
        "autoscale_scale_up_total": snap["scale_up_total"],
        "autoscale_scale_down_total": snap["scale_down_total"],
        "scaleup_cold_trace_ms": round(scaleup_cold_ms, 1),
        "scaleup_warm_cache_ms": (round(min(scaleup_ms), 1)
                                  if scaleup_ms else None),
    }
    # the acceptance invariants, asserted where the evidence is made
    assert counts["failed"] == 0, res
    assert peak > 1, ("the ramp never scaled up", res)
    assert all(1 <= n <= max_replicas for n in traj), res
    assert final == 1, ("idle never scaled back down", res)
    p99 = res["autoscale_p99_ms"]
    assert p99 is not None and p99 < 1e3 * 60, res  # bounded, not hung
    router.shutdown(drain=False)
    return res


def bench_router_failover():
    """Router-kill failover time (``--fleet`` → BENCH_r14.json): two
    role-fenced routers (active + warm standby) front two replicas;
    open-loop traffic rides HA client endpoints; a seeded chaos
    partition silences the active's lease renewals and the harness
    tears its listener down at the seeded moment (the router-process
    kill). Reported: kill → standby-adoption lag and kill → first
    standby-answered OK (both must land within the lease ttl plus a
    few health intervals — asserted), with zero failed non-shed
    requests (asserted)."""
    import tempfile
    import threading

    import numpy as np
    import jax
    from paddle_tpu.config import dsl
    from paddle_tpu.core.network import Network
    from paddle_tpu.data import dense_vector, integer_value
    from paddle_tpu.dist.master import InMemStore, RoleLease
    from paddle_tpu.serving import (EngineTransport, Overloaded,
                                    ReplicaRouter, RouterHA,
                                    ServingClient, ServingEngine,
                                    ServingError, ServingPredictor,
                                    Unavailable, make_router_server)
    from paddle_tpu.testing import chaos

    dim, classes = 8, 4
    dsl.reset()
    x = dsl.data(name="x", size=dim)
    lab = dsl.data(name="label", size=classes)
    out = dsl.fc(input=x, size=classes, act="softmax", name="out")
    dsl.classification_cost(input=out, label=lab, name="cost")
    graph = dsl.current_graph()
    params = Network(graph, outputs=["out"]).init_params(
        jax.random.PRNGKey(0))
    feeding = {"x": dense_vector(dim), "label": integer_value(classes)}
    cache_dir = tempfile.mkdtemp(prefix="paddle_tpu_aot_ha_")

    def build_engine():
        pred = ServingPredictor(graph, params, ["out"], feeding,
                                batch_buckets=[1, 2],
                                aot_cache=cache_dir)
        return ServingEngine(pred, max_batch=2, batch_timeout_ms=1.0,
                             queue_depth=64).start(warmup=True)

    sample = ((np.arange(dim, dtype=float) / dim).tolist(), 1)
    ttl, interval_ms = 0.4, 100.0
    engs = [build_engine() for _ in range(2)]
    store = InMemStore()
    lease_a = RoleLease(store, "A", ttl_s=ttl, settle_s=0.0)
    lease_b = RoleLease(store, "B", ttl_s=ttl, settle_s=0.0)
    active = ReplicaRouter([EngineTransport(e) for e in engs],
                           fence=lease_a, health_poll_ms=25.0)
    standby = ReplicaRouter([], fence=lease_b, health_poll_ms=25.0)
    srv_a = make_router_server(active, port=0)
    srv_b = make_router_server(standby, port=0)
    for s in (srv_a, srv_b):
        threading.Thread(target=s.serve_forever, daemon=True).start()
    by_id = {f"r{i}": e for i, e in enumerate(engs)}

    def peer_healthz():
        import http.client
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv_a.server_address[1], timeout=1.0)
        try:
            conn.request("GET", "/healthz")
            return json.loads(conn.getresponse().read())
        finally:
            conn.close()

    def adopt(snaps):
        return [(s["id"], EngineTransport(by_id[s["id"]]))
                for s in snaps if s["id"] in by_id]

    assert lease_a.try_acquire()
    active.start()
    standby.start()
    ha_a = RouterHA(active, lease_a, interval_ms=interval_ms).start()
    ha_b = RouterHA(standby, lease_b, peer_healthz=peer_healthz,
                    adopt=adopt, adopt_after=2,
                    interval_ms=interval_ms).start()
    plan = chaos.FaultPlan(seed=17, faults=[
        # drop holder A's renewals only — the adopted standby's own
        # renewals must sail through (chaos "match" targeting)
        {"type": "partition", "site": "lease_renew", "after": 4,
         "count": 100000, "match": {"holder": "A"}}])
    n_requests, req_interval = 40, 0.05
    counts = {"ok": 0, "shed": 0, "failed": 0}
    lock = threading.Lock()
    endpoints = [f"127.0.0.1:{srv_a.server_address[1]}",
                 f"127.0.0.1:{srv_b.server_address[1]}"]
    killed = {"t": None}
    first_standby_ok = {"t": None}

    def kill_watch():
        while plan.hits("lease_renew") < 5:
            time.sleep(0.01)
        killed["t"] = time.monotonic()
        # the active router "process" dies: stop the accept loop AND
        # close the listening socket (a real death frees the port;
        # shutdown() alone would backlog-blackhole new connections)
        srv_a.shutdown()
        srv_a.server_close()

    def one(i):
        client = ServingClient(endpoints=list(endpoints), timeout=10.0,
                               retries=8, backoff_base_ms=20.0,
                               backoff_seed=1000 + i)
        try:
            client.score(sample)
            key = "ok"
            # EXACT endpoint compare: a suffix match on the port digits
            # could credit the ACTIVE (e.g. :18080 ends with "8080")
            ep = (client.last_provenance or {}).get("endpoint", "")
            if ep == f"127.0.0.1:{srv_b.server_address[1]}":
                with lock:
                    if first_standby_ok["t"] is None:
                        first_standby_ok["t"] = time.monotonic()
        except Unavailable:
            key = "failed"
        except Overloaded:
            key = "shed"
        except (ServingError, OSError):
            key = "failed"
        with lock:
            counts[key] += 1

    threads = []
    with chaos.chaos_plan(plan):
        watcher = threading.Thread(target=kill_watch, daemon=True)
        watcher.start()
        t0 = time.monotonic()
        for i in range(n_requests):
            target = t0 + i * req_interval
            d = target - time.monotonic()
            if d > 0:
                time.sleep(d)
            th = threading.Thread(target=one, args=(i,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(60.0)
        watcher.join(10.0)
        deadline = time.monotonic() + 10.0
        while ha_b.adoptions == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
    assert killed["t"] is not None and ha_b.adoptions == 1
    adoption_lag_ms = 1e3 * (ha_b.adopted_at - killed["t"])
    answer_lag_ms = (1e3 * (first_standby_ok["t"] - killed["t"])
                     if first_standby_ok["t"] is not None else None)
    res = {
        "failover_requests": n_requests,
        "failover_ok": counts["ok"],
        "failover_shed": counts["shed"],
        "fleet_failed_non_shed_failover": counts["failed"],
        "failover_adoption_lag_ms": round(adoption_lag_ms, 1),
        "failover_kill_to_first_standby_ok_ms": (
            round(answer_lag_ms, 1) if answer_lag_ms else None),
        "failover_lease_ttl_ms": ttl * 1e3,
        "failover_health_interval_ms": interval_ms,
        "failover_adoptions": ha_b.adoptions,
        "failover_fenced_total": (
            active.metrics.snapshot()["fenced_total"]),
    }
    # acceptance: zero failed non-shed, and the standby ANSWERED within
    # one health interval of becoming eligible (lease ttl after the
    # kill), with scheduling slack for the 1-core host
    assert counts["failed"] == 0, res
    budget_ms = ttl * 1e3 + 3 * interval_ms + 500.0
    assert adoption_lag_ms < budget_ms, res
    assert answer_lag_ms is not None and answer_lag_ms < budget_ms + \
        500.0, res
    ha_a.shutdown(release=False)
    ha_b.shutdown(release=False)
    srv_b.shutdown()
    for e in engs:
        e.shutdown(drain=False)
    return res


def bench_fleet_trace(rounds=None, n_requests=None):
    """Tracing pays for itself (``--fleet`` → BENCH_r15.json +
    TRACE_r15.json): two replicas behind the router HTTP frontend,
    scored sequentially with tracing OFF and ON in interleaved
    best-of-R rounds (CLAUDE.md host-drift rule: a single A/B pair is
    meaningless on this box — each mode keeps its best p50). Reported:
    p50 per mode, the on-vs-off overhead in percent (asserted ≤ 5%, the
    docs/observability.md policy bound), and the acceptance trace — one
    scored request with an induced failover whose spans reconstruct the
    client-observed latency (root ``client.request`` wall time within
    5% of the measured call) with the failover visible as sibling
    ``router.attempt`` spans; the trace dumps to ``TRACE_r15.json``
    and must pass its own PT401 schema before this function returns."""
    import statistics
    import tempfile
    import threading

    import numpy as np
    import jax
    from paddle_tpu.config import dsl
    from paddle_tpu.core.network import Network
    from paddle_tpu.data import dense_vector, integer_value
    from paddle_tpu.obs import trace as _trace
    from paddle_tpu.serving import (EngineTransport, ReplicaRouter,
                                    ServingClient, ServingEngine,
                                    ServingPredictor,
                                    make_router_server)
    from paddle_tpu.testing import chaos

    rounds = int(os.environ.get("BENCH_TRACE_ROUNDS", "3")
                 if rounds is None else rounds)
    n_requests = int(os.environ.get("BENCH_TRACE_REQUESTS", "30")
                     if n_requests is None else n_requests)
    dim, classes = 8, 4
    dsl.reset()
    x = dsl.data(name="x", size=dim)
    lab = dsl.data(name="label", size=classes)
    out = dsl.fc(input=x, size=classes, act="softmax", name="out")
    dsl.classification_cost(input=out, label=lab, name="cost")
    graph = dsl.current_graph()
    params = Network(graph, outputs=["out"]).init_params(
        jax.random.PRNGKey(0))
    feeding = {"x": dense_vector(dim), "label": integer_value(classes)}
    cache_dir = tempfile.mkdtemp(prefix="paddle_tpu_aot_trace_")

    def build_engine():
        pred = ServingPredictor(graph, params, ["out"], feeding,
                                batch_buckets=[1, 2],
                                aot_cache=cache_dir)
        return ServingEngine(pred, max_batch=2, batch_timeout_ms=1.0,
                             queue_depth=64).start(warmup=True)

    engines = [build_engine() for _ in range(2)]
    router = ReplicaRouter([EngineTransport(e) for e in engines],
                           health_poll_ms=25.0).start()
    server = make_router_server(router, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    client = ServingClient(port=server.server_address[1])
    sample = ((np.arange(dim, dtype=float) / dim).tolist(), 1)
    client.score(sample)  # connection path + menu warm before timing

    # ---- the A/B: interleaved PER REQUEST (host throughput drifts
    # ±50% between windows — alternating modes request by request puts
    # both arms under the same drift, and best-of-R rounds on top
    # absorbs what alternation cannot), each mode keeps its best p50
    ab_tracer = _trace.Tracer("bench", buffer=65536)

    def p50_pair():
        lat = {"off": [], "on": []}
        for i in range(2 * n_requests):
            mode = "on" if i % 2 else "off"
            _trace.install(ab_tracer if mode == "on" else None)
            t0 = time.perf_counter()
            client.score(sample)
            lat[mode].append(1e3 * (time.perf_counter() - t0))
        _trace.install(None)
        return (statistics.median(lat["off"]),
                statistics.median(lat["on"]))

    best = {"off": float("inf"), "on": float("inf")}
    try:
        for _ in range(rounds):
            off, on = p50_pair()
            best["off"] = min(best["off"], off)
            best["on"] = min(best["on"], on)
        overhead_pct = 1e2 * (best["on"] - best["off"]) / best["off"]

        # ---- the acceptance trace: one scored request, induced
        # failover, spans reconstruct the client measurement ----------
        tracer = _trace.install(_trace.Tracer("bench"))
        plan = chaos.FaultPlan(seed=15, faults=[
            {"type": "drop", "site": "route_dispatch", "at": 1},
            {"type": "delay", "site": "serve_batch", "at": 1,
             "seconds": 0.05}])
        with chaos.chaos_plan(plan):
            t0 = time.perf_counter()
            result = client.score(sample)
            measured_ms = 1e3 * (time.perf_counter() - t0)
        prov = result["provenance"]
        tid = prov["trace_id"]
        # the worker emits replica.score THEN its phase children; wait
        # for phase.decode (the last write of that sequence) so the
        # committed artifact always carries the full phase split
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            spans = tracer.spans(tid)
            if any(s["name"] == "phase.decode" for s in spans):
                break
            time.sleep(0.01)
        else:
            raise RuntimeError(
                "acceptance trace never grew its phase.decode span — "
                "refusing to commit an incomplete TRACE artifact "
                f"(got {sorted(s['name'] for s in spans)})")
        attempts = [s for s in spans if s["name"] == "router.attempt"]
        roots = [s for s in spans if s["name"] == "client.request"]
        root_ms = roots[0]["dur_ms"] if roots else None
    finally:
        _trace.install(None)
        server.shutdown()
        server.server_close()  # free the listening socket, not just
        # the accept loop — shutdown() alone backlog-blackholes
        router.shutdown(drain=False)

    here = os.path.dirname(os.path.abspath(__file__))
    trace_path = os.path.join(here, "TRACE_r15.json")
    with open(trace_path, "w") as f:
        json.dump({"metric": "failover_trace", "trace_id": tid,
                   "client_measured_ms": round(measured_ms, 3),
                   "spans": spans}, f, indent=1)
    from paddle_tpu.analysis.bench_schema import check_bench_file
    schema_findings = check_bench_file(trace_path, "TRACE_r15.json")
    res = {
        "trace_rounds": rounds,
        "trace_requests_per_round": n_requests,
        "trace_off_p50_ms": round(best["off"], 3),
        "trace_on_p50_ms": round(best["on"], 3),
        "trace_overhead_pct": round(overhead_pct, 2),
        "trace_failovers": prov["failovers"],
        "trace_attempt_spans": len(attempts),
        "trace_span_count": len(spans),
        "trace_client_measured_ms": round(measured_ms, 3),
        "trace_root_span_ms": (round(root_ms, 3)
                               if root_ms is not None else None),
        "trace_root_delta_pct": (
            round(1e2 * abs(measured_ms - root_ms) / measured_ms, 2)
            if root_ms is not None else None),
        "trace_schema_findings": len(schema_findings),
    }
    # acceptance, asserted where the evidence is made: the failover is
    # two sibling attempts of ONE trace, the root span reconstructs the
    # client measurement within 5%, the artifact passes its schema, and
    # tracing costs ≤ 5% on the interleaved best-of p50 (honest about
    # drift: both arms already kept their best round)
    assert prov["failovers"] == 1 and len(attempts) == 2, res
    assert len({a["parent_id"] for a in attempts}) == 1, res
    assert root_ms is not None \
        and abs(measured_ms - root_ms) <= 0.05 * measured_ms, res
    assert schema_findings == [], [f.message for f in schema_findings]
    assert overhead_pct <= 5.0, res
    return res


def bench_serve_train(requests=None, batch_rows=None):
    """The r20 online loop end to end (``--serve_train`` →
    BENCH_r20.json): one process group closes
    serving→training→publish→serving.

    1. **The live loop.** A 2-replica fleet serves a published PTM1 CTR
       artifact; an open-loop traffic driver scores labeled rows
       through the router while the MAIN thread trains the replay
       stream the engines append (sealed PTRL1 segments → ledger tasks
       → sparse-lazy Momentum batches). On the publish cadence the
       trainer's weights merge + roll across the fleet pinned to the
       artifact digest. Evidence: held-out CTR error FALLS across the
       published versions (each artifact re-scored through the serving
       predictor — the model the fleet actually answered with), zero
       failed non-shed requests through every reload, zero hot-path
       recompiles (every engine's hardened guards stay silent).
    2. **Chaos drills**, trainer-only (the matrix cells' shapes at
       bench scale): a seeded kill mid-loop + rebuilt-loop resume that
       must be BITWISE the never-killed twin (exactly-once), and a
       NaN-poisoned batch the divergence sentry must skip with every
       published artifact staying finite (zero bad publishes).
    """
    import shutil
    import tempfile
    import threading

    import numpy as np
    import jax
    from paddle_tpu.config import dsl
    from paddle_tpu.data import (DataFeeder, integer_value,
                                 integer_value_sequence)
    from paddle_tpu.dist.checkpoint import Checkpointer
    from paddle_tpu.models import ctr_model
    from paddle_tpu.online import (ModelPublisher, ReplayTailer,
                                   ReplayWriter, ServeTrainLoop)
    from paddle_tpu.optim import Momentum
    from paddle_tpu.serving import (EngineTransport, Overloaded,
                                    ReplicaRouter, ServingEngine,
                                    ServingError, ServingPredictor)
    from paddle_tpu.testing import chaos
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer.merge_model import load_merged_ex

    requests = int(os.environ.get("BENCH_SERVE_TRAIN_REQUESTS", "200")
                   if requests is None else requests)
    batch_rows = int(batch_rows or 10)
    vocab, maxlen, marker = 50, 16, 2
    seg_records, publish_every = 20, 6
    feeding = {"words": integer_value_sequence(vocab),
               "label": integer_value(2)}

    def build_trainer(seed=0):
        dsl.reset()
        cost, _out, _names = ctr_model(vocab_size=vocab, embed_dim=16,
                                       hidden=32, classes=2)
        tr = SGD(cost=cost,
                 update_equation=Momentum(learning_rate=0.1, momentum=0.9),
                 seed=seed)
        # the sparse-lazy path IS the subject: touched-rows slots only
        assert "t_rows" in tr.opt_state["slots"]["_embed.w0"]
        return tr

    def mk_rows(n, seed):
        # learnable CTR traffic: label = presence of the marker token
        # (positives carry it in ~1/3 of positions). Rows keep their
        # label slot — the feedback join the replay log trains on.
        rng = np.random.RandomState(seed)
        rows = []
        for _ in range(n):
            length = int(rng.randint(5, maxlen + 1))
            ids = rng.randint(3, vocab, size=length)
            label = int(rng.rand() < 0.5)
            if label:
                k = max(1, length // 3)
                ids[rng.choice(length, size=k, replace=False)] = marker
            rows.append(([int(i) for i in ids], label))
        return rows

    held = mk_rows(100, seed=99)
    work = tempfile.mkdtemp(prefix="paddle_tpu_serve_train_")
    replay_dir = os.path.join(work, "replay")
    publish_dir = os.path.join(work, "published")
    cache_dir = os.path.join(work, "aot")

    # ---- phase 1: the live loop ------------------------------------
    trainer = build_trainer()
    writer = ReplayWriter(replay_dir, segment_records=seg_records,
                          schema=list(feeding))
    engines_made = []

    def make_engine(model_path):
        pred = ServingPredictor.from_merged(
            model_path, feeding, batch_buckets=[1, 4],
            length_buckets=[maxlen], aot_cache=cache_dir)
        eng = ServingEngine(pred, max_batch=4, batch_timeout_ms=2.0,
                            queue_depth=requests + 8,
                            replay_sink=writer).start(warmup=True)
        engines_made.append(eng)
        return eng

    publisher = ModelPublisher(
        trainer, model_dir=publish_dir, outputs=["output"],
        build_transport=lambda path, rid: EngineTransport(
            make_engine(path)),
        every_batches=publish_every)
    publisher.publish()  # v0: the fleet's starting artifact
    router = ReplicaRouter(
        [EngineTransport(make_engine(publisher.last_good))
         for _ in range(2)],
        spawn=lambda rid: EngineTransport(
            make_engine(publisher.last_good)),
        health_poll_ms=25.0).start()
    publisher.router = router

    tailer = ReplayTailer(replay_dir, batch_rows=batch_rows,
                          scan_period_s=0.1, poll_s=0.02)
    loop = ServeTrainLoop(
        trainer, tailer=tailer, publisher=publisher,
        feeder=DataFeeder(feeding, pad_multiple=maxlen), writer=writer,
        health={"sentry": True, "policy": "skip_batch"})

    samples = mk_rows(requests, seed=7)
    counts = {"ok": 0, "shed": 0, "failed": 0}
    clock = threading.Lock()
    # calibrate the open-loop rate off sequential dispatches, then
    # offer ~1.5x so queues form without drowning the shared core
    t0 = time.perf_counter()
    for s in samples[:8]:
        router.dispatch(s)
    interval = (time.perf_counter() - t0) / 8 / 1.5

    def one(s):
        from paddle_tpu.serving import Unavailable
        try:
            router.dispatch(s)
            key = "ok"
        except Unavailable:
            key = "failed"  # no ready replica = outage, not backpressure
        except Overloaded:
            key = "shed"
        except ServingError:
            key = "failed"
        with clock:
            counts[key] += 1

    def drive():
        threads, t_start = [], time.perf_counter()
        for i, s in enumerate(samples[8:]):
            target = t_start + i * interval
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            th = threading.Thread(target=one, args=(s,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(300.0)
        loop.stop()  # seal the tail, close the stream: the reader drains

    driver = threading.Thread(target=drive, name="traffic-driver")
    driver.start()
    loop.run()  # the MAIN thread trains the stream, publishing on cadence
    driver.join(300.0)
    router.shutdown(drain=True)

    # held-out error of every published version, re-scored through the
    # serving predictor — the artifact the fleet answered with, not the
    # trainer's live params
    def artifact_error(path):
        pred = ServingPredictor.from_merged(
            path, feeding, batch_buckets=[20], length_buckets=[maxlen])
        wrong = 0
        for i in range(0, len(held), 20):
            outs, _info = pred.predict_rows(held[i:i + 20])
            pick = np.argmax(outs["output"], axis=1)
            wrong += sum(int(p) != r[1]
                         for p, r in zip(pick, held[i:i + 20]))
        return wrong / len(held)

    artifacts = sorted(os.path.join(publish_dir, p)
                       for p in os.listdir(publish_dir)
                       if p.endswith(".ptmodel"))
    trajectory = [round(artifact_error(p), 4) for p in artifacts]

    # zero hot-path recompiles: every engine ever built (initial fleet +
    # each reload wave) kept its hardened guards silent and its worker
    # alive; check_guards() would raise on any post-warmup cache growth
    for eng in engines_made:
        assert eng.fatal is None, repr(eng.fatal)
        eng.predictor.check_guards()
        eng.shutdown()

    res = {
        "serve_train_requests": requests,
        "serve_train_open_loop_interval_ms": round(interval * 1e3, 3),
        "serve_train_ok": counts["ok"] + 8,  # calibration answered too
        "serve_train_shed": counts["shed"],
        "fleet_failed_non_shed": counts["failed"],
        "serve_train_batches_trained": loop.batches_trained,
        "serve_train_replay_segments": writer.segments_sealed,
        "serve_train_replay_rows": writer.records_total,
        "publishes_total": publisher.publishes_total,
        "rollbacks_total": publisher.rollbacks_total,
        "serve_train_error_trajectory": trajectory,
        "serve_train_hot_path_recompiles": 0,  # asserted above
        "serve_train_engines_built": len(engines_made),
    }
    # acceptance, asserted where the evidence is made: the loop LEARNED
    # the traffic across ≥2 published versions, and every reload wave
    # swapped under load without failing a single non-shed request
    assert len(trajectory) >= 2 and trajectory[-1] < trajectory[0], res
    assert counts["failed"] == 0, res
    assert publisher.publishes_total >= 2, res

    # ---- phase 2: chaos drills (trainer-only, matrix shapes) -------
    def final_state(tr):
        from paddle_tpu.trainer.checkpoint import _flatten
        params = {k: np.asarray(jax.device_get(v))
                  for k, v in tr._params_for_save().items()}
        return params, _flatten(tr._opt_state_for_save()), \
            np.asarray(jax.device_get(tr._rng))

    def drill_loop(rdir, mdir, *, ck_dir=None, health=None):
        tr = build_trainer()
        t = ReplayTailer(rdir, batch_rows=batch_rows, poll_s=0.01)
        pub = ModelPublisher(tr, model_dir=mdir, outputs=["output"],
                             every_batches=3)
        ck = None
        if ck_dir is not None:
            ck = Checkpointer(ck_dir, saving_period=1,
                              saving_period_by_batches=2, background=True)
        lp = ServeTrainLoop(tr, tailer=t, publisher=pub,
                            feeder=DataFeeder(feeding,
                                              pad_multiple=maxlen),
                            checkpointer=ck, health=health)
        t.end_stream()  # drain mode: traffic pre-sealed below
        return lp, tr, pub, ck

    drill_rows = mk_rows(60, seed=21)
    kill_dir = os.path.join(work, "drill_kill")
    twin_dir = os.path.join(work, "drill_twin")
    w = ReplayWriter(kill_dir, segment_records=seg_records)
    for r in drill_rows:
        w.append(r)
    w.close()
    shutil.copytree(kill_dir, twin_dir)  # BEFORE any ledger exists

    lp, tr, _, _ = drill_loop(twin_dir, os.path.join(work, "m_twin"),
                              ck_dir=os.path.join(work, "ck_twin"))
    lp.run()
    want = final_state(tr)

    plan = chaos.FaultPlan(seed=0, faults=[
        {"type": "kill", "site": "step_done", "at": 4, "mode": "raise"}])
    lp, tr, _, ck = drill_loop(kill_dir, os.path.join(work, "m_kill"),
                               ck_dir=os.path.join(work, "ck_kill"))
    with chaos.chaos_plan(plan):
        try:
            lp.run()
            raise AssertionError("chaos kill never fired")
        except chaos.ChaosKilled:
            pass
    ck.flush()
    lp, tr, _, _ = drill_loop(kill_dir, os.path.join(work, "m_kill"),
                              ck_dir=os.path.join(work, "ck_kill"))
    lp.run()
    got = final_state(tr)
    for g, wv in ((got[0], want[0]), (got[1], want[1])):
        assert set(g) == set(wv)
        for k in wv:
            np.testing.assert_array_equal(g[k], wv[k], err_msg=k)
    np.testing.assert_array_equal(got[2], want[2])
    res["serve_train_resume_exactly_once_bitwise"] = True

    poison_dir = os.path.join(work, "drill_poison")
    w = ReplayWriter(poison_dir, segment_records=seg_records)
    for r in drill_rows:
        w.append(r)
    w.close()
    plan = chaos.FaultPlan(seed=0, faults=[
        {"type": "corrupt", "site": "step_stats", "at": 3}])
    lp, tr, pub, _ = drill_loop(
        poison_dir, os.path.join(work, "m_poison"),
        health={"period": 1, "sentry": True, "policy": "skip_batch"})
    with chaos.chaos_plan(plan):
        lp.run()
    snap = tr._health.snapshot()
    bad = 0
    for p in os.listdir(os.path.join(work, "m_poison")):
        _, params, _, _ = load_merged_ex(
            os.path.join(work, "m_poison", p))
        bad += any(not np.isfinite(v).all() for v in params.values())
    # the sentry skipped the poisoned update; nothing poisoned published
    assert snap["sentry_trips"] == 1 and snap["skipped_batches"] == 1, snap
    assert pub.publishes_total >= 1 and bad == 0, (pub.publishes_total,
                                                   bad)
    res["serve_train_poison_sentry_trips"] = snap["sentry_trips"]
    res["serve_train_poison_bad_publishes"] = bad
    shutil.rmtree(work, ignore_errors=True)
    return res


def fleet_main():
    """``python bench.py --fleet``: the CPU-side fleet benches alone,
    forced onto CPU; one JSON line, mirrored to BENCH_r15.json. Four
    scenarios in one artifact: the r13 cold-start A/B + replica-kill
    rounds (still the respawn-warmth evidence), the autoscale traffic
    ramp (replica count follows load inside [min, max], p99 bounded,
    zero failed non-shed), the router-kill HA failover (standby answers
    within one health interval, zero failed non-shed), and the r15
    tracing A/B (on-vs-off p50 overhead ≤ 5%, failover trace →
    TRACE_r15.json)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "serving_fleet_autoscale_ha_failover",
              "platform": jax.devices()[0].platform}
    result.update(bench_fleet())
    result.update(bench_fleet_autoscale())
    result.update(bench_router_failover())
    result.update(bench_fleet_trace())
    # the headline zero-drop number sums EVERY scenario's counter —
    # no failure hides behind a sibling scenario
    result["fleet_failed_non_shed"] = (
        result["fleet_failed_non_shed"]
        + result["autoscale_failed_non_shed"]
        + result["fleet_failed_non_shed_failover"])
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r15.json"), "w") as f:
        f.write(line + "\n")
    return 0


def decode_main():
    """``python bench.py --decode``: the CPU-side decode A/B alone,
    forced onto CPU; one JSON line, mirrored to BENCH_r10.json."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "decode_early_exit_continuous_batching_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_decode())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r10.json"), "w") as f:
        f.write(line + "\n")
    return 0


def serving_main():
    """``python bench.py --serving``: the CPU-side serving A/B alone,
    forced onto CPU; one JSON line, mirrored to BENCH_r09.json."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "serving_dynamic_batching_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_serving())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r09.json"), "w") as f:
        f.write(line + "\n")
    return 0


def quant_main():
    """``python bench.py --quant``: the CPU-side quantized-serving
    three-way alone, forced onto CPU; one JSON line, mirrored to
    BENCH_r19.json."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "serving_quant_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_serving_quant())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r19.json"), "w") as f:
        f.write(line + "\n")
    return 0


def serve_train_main():
    """``python bench.py --serve_train``: the CPU-side online-loop
    evidence alone, forced onto CPU; one JSON line, mirrored to
    BENCH_r20.json."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "serve_train_loop",
              "platform": jax.devices()[0].platform}
    result.update(bench_serve_train())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r20.json"), "w") as f:
        f.write(line + "\n")
    return 0


def autotune_main():
    """``python bench.py --autotune``: the CPU-side self-tuning A/B
    alone, forced onto CPU; one JSON line, mirrored to BENCH_r21.json,
    with the two recorded traces committed as WORKLOAD_r21_*.json (the
    PT401 ``WORKLOAD_*`` family — ``tests/test_workload_replay.py``
    replays them)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "serving_autotune_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_autotune())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r21.json"), "w") as f:
        f.write(line + "\n")
    return 0


def pipeline_main():
    """``python bench.py --pipeline``: the CPU-side pipeline A/B alone,
    forced onto an 8-virtual-device CPU mesh; one JSON line, mirrored to
    BENCH_r08.json."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "pipeline_parallel_train_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_pipeline())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r08.json"), "w") as f:
        f.write(line + "\n")
    return 0


def zero1_main():
    """``python bench.py --zero1``: the CPU-side ZeRO-1 A/B alone,
    forced onto an 8-virtual-device CPU mesh; one JSON line, mirrored to
    BENCH_r07.json."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "zero1_sharded_optimizer_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_zero1())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r07.json"), "w") as f:
        f.write(line + "\n")
    return 0


def fsdp_main():
    """``python bench.py --fsdp``: the CPU-side full-FSDP A/B alone,
    forced onto an 8-virtual-device CPU mesh; one JSON line, mirrored to
    BENCH_r17.json."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "fsdp_full_param_sharding_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_fsdp())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r17.json"), "w") as f:
        f.write(line + "\n")
    return 0


def overlap_main():
    """``python bench.py --overlap``: the CPU-side FSDP-overlap x
    fused-kernel 2x2 A/B alone, forced onto an 8-virtual-device CPU
    mesh; one JSON line, mirrored to BENCH_r18.json."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "overlap_fsdp_fused_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_overlap())
    line = json.dumps(result)
    print(line, flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_r18.json"), "w") as f:
        f.write(line + "\n")
    return 0


def health_main():
    """``python bench.py --health``: the CPU-side training-health A/B
    alone, forced onto CPU; one JSON line,
    mirrored to BENCH_r16.json, with the armed run's sampled timeline
    committed as HEALTH_r16.json (the PT401 ``HEALTH_*`` family —
    ``tools/healthview.py`` renders/diffs it)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "training_health_telemetry_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_health())
    timeline = result.pop("_health_timeline")
    here = os.path.dirname(os.path.abspath(__file__))
    health_doc = {
        "run": "bench-r16-health",
        "platform": result["platform"],
        "period": result["health_period"],
        "sentry_trips": result["health_sentry_trips"],
        # the final measured pass's steps: a representative, bounded
        # sample of the per-step schema (full runs live in --health_log
        # JSONL files, not in git)
        "events": timeline[-result["health_batches"]:],
    }
    with open(os.path.join(here, "HEALTH_r16.json"), "w") as f:
        json.dump(health_doc, f, indent=1)
        f.write("\n")
    line = json.dumps(result)
    print(line, flush=True)
    with open(os.path.join(here, "BENCH_r16.json"), "w") as f:
        f.write(line + "\n")
    return 0


def input_pipeline_main():
    """``python bench.py --input-pipeline``: the CPU-side metric alone,
    forced onto CPU; one JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    result = {"metric": "input_pipeline_async_prefetch_ab",
              "platform": jax.devices()[0].platform}
    result.update(bench_input_pipeline())
    print(json.dumps(result), flush=True)
    return 0


def default_main():
    """The default path: one process, on the chip, the two measurements
    with history. Any raise is a non-zero exit."""
    import jax

    from paddle_tpu.ops.lstm import kernel_dispatch_table
    from paddle_tpu.utils import runtime
    dev = runtime.require_tpu("bench")
    peak_flops(dev["kind"])  # an unknown device fails before any work
    device = {"platform": dev["platform"], "device_kind": dev["kind"],
              "device_count": dev["count"]}
    row = {"metric": "lstm_imdb_train_ms_per_batch_bs64_h256_seq100",
           "value": round(bench_lstm(), 3), "unit": "ms/batch",
           # which backend each baseline shape takes — pins perf claims
           # to dispatch (tests/test_ops_pallas.py)
           "kernel_dispatch": kernel_dispatch_table(), **device}
    print(json.dumps(row), flush=True)
    res = bench_resnet50()
    row = {"metric": f"resnet50_train_step_ms_bs{res['resnet50_batch']}"
                     "_fp32",
           "value": res["resnet50_step_ms"], "unit": "ms/step",
           **{k: v for k, v in res.items() if k != "device_kind"},
           **device}
    print(json.dumps(row), flush=True)
    return 0


_MODES = {
    "--input-pipeline": input_pipeline_main, "--zero1": zero1_main,
    "--fsdp": fsdp_main, "--overlap": overlap_main,
    "--pipeline": pipeline_main, "--serving": serving_main,
    "--quant": quant_main, "--serve_train": serve_train_main,
    "--autotune": autotune_main, "--decode": decode_main,
    "--fleet": fleet_main, "--health": health_main,
}


def main():
    for flag, mode_main in _MODES.items():
        if flag in sys.argv[1:]:
            return mode_main()
    return default_main()


if __name__ == "__main__":
    sys.exit(main())
