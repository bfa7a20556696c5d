"""The ``paddle_tpu_trainer`` command (`paddle/trainer/TrainerMain.cpp`).

``python -m paddle_tpu.trainer.cli --config=model.py --job=train ...``

Job modes mirror the reference trainer:
- ``train``      — the training loop (+ checkpointing into --save_dir)
- ``test``       — one evaluation pass over the test reader
- ``time``       — steady-state ms/batch benchmark, skipping warmup
                   (`Trainer::time`, `TrainerBenchmark.cpp:27`)
- ``checkgrad``  — numeric-vs-analytic gradient check on one batch
                   (`Trainer::checkGradient`, `Trainer.cpp:299+`)
- ``merge``      — fuse config+params into one deploy file
                   (`MergeModel.cpp`)

The --config file is executed as Python (the reference's embedded-Python
`parse_config` contract, `TrainerConfigHelper.cpp:33-57`): it builds the
model with ``paddle_tpu.config.dsl`` or the v2 layer API and must define
``cost`` (a LayerOutput); optionally ``optimizer``, ``train_reader``,
``test_reader``, ``feeding`` (dict name->data_type), ``outputs``
(inference layers). ``--config_args a=1,b=x`` are injected as variables
before execution, exactly like the reference flag.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Optional


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu_trainer",
        description="TPU trainer (paddle_trainer equivalent)")
    p.add_argument("--config", required=True,
                   help="Python model-config file (executed)")
    p.add_argument("--job", default="train",
                   choices=["train", "test", "time", "checkgrad", "merge",
                            "serve", "serve_fleet", "serve_train"])
    p.add_argument("--config_args", default="",
                   help="comma-separated k=v injected into the config")
    p.add_argument("--num_passes", type=int, default=1)
    p.add_argument("--log_period", type=int, default=100)
    p.add_argument("--dot_period", type=int, default=0,
                   help="print a progress dot every N batches")
    p.add_argument("--show_parameter_stats_period", type=int, default=0,
                   help="log the parameter health dump every N batches")
    p.add_argument("--show_layer_stat", action="store_true",
                   help="log per-layer output stats at each log_period "
                        "(read from the in-step telemetry when "
                        "--show_parameter_stats_period arms it)")
    p.add_argument("--log_error_clipping", action="store_true",
                   help="arm the divergence sentry and log each trip "
                        "(the reference's --log_error_clipping, "
                        "Flags.cpp:69, machine-mapped: loss/grad "
                        "finiteness plus --error_clipping_threshold "
                        "checked INSIDE the compiled step)")
    p.add_argument("--error_clipping_threshold", type=float, default=0.0,
                   help="divergence-sentry gradient threshold: trip "
                        "when max|grad| exceeds this (0 = finiteness "
                        "only; the reference's per-layer "
                        "error_clipping_threshold attr as a global "
                        "training-health knob). The policy on a trip "
                        "is --divergence_policy; skip_batch reproduces "
                        "the reference error-clipping semantics")
    p.add_argument("--divergence_policy", default="skip_batch",
                   choices=["halt", "skip_batch", "dump"],
                   help="what a sentry trip does: halt (postmortem + "
                        "DivergenceError), skip_batch (discard the "
                        "poisoned batch's update in-graph — the "
                        "post-skip trajectory is bitwise the run that "
                        "never saw the batch), dump (postmortem only, "
                        "keep training)")
    p.add_argument("--health_log", default=None,
                   help="append the per-step training-health timeline "
                        "(step, loss, lr, per-layer stats on period "
                        "steps, data_wait/compute) to this JSONL file "
                        "(obs/events.py; render/diff with "
                        "tools/healthview.py)")
    p.add_argument("--save_dir", default=None,
                   help="checkpoint directory (train) / source (test,merge)")
    p.add_argument("--saving_period", type=int, default=1)
    p.add_argument("--saving_period_by_batches", type=int, default=None)
    p.add_argument("--init_model_path", default=None,
                   help="checkpoint file or merged model to start from")
    p.add_argument("--auto_resume", default=True,
                   action=argparse.BooleanOptionalAction,
                   help="restore the newest intact checkpoint in "
                        "--save_dir before training (exact resume: RNG, "
                        "data position and schedule state included); "
                        "--no-auto_resume makes --save_dir save-only")
    p.add_argument("--background_save", default=True,
                   action=argparse.BooleanOptionalAction,
                   help="write checkpoints on a background thread — the "
                        "step loop never blocks on serialize/fsync "
                        "(device state is still snapshotted "
                        "synchronously, so the saved generation is "
                        "exact)")
    p.add_argument("--model_path", default=None,
                   help="output path for --job=merge")
    p.add_argument("--quantize", default=None, choices=["bf16", "int8"],
                   help="--job=merge: quantize weights into the PTM1 "
                        "artifact (per-tensor int8 scales / bf16 "
                        "storage cast, paddle_tpu/quant.py) and embed "
                        "the golden-request set the serving warmup "
                        "accuracy gate replays")
    p.add_argument("--quantize_tol", type=float, default=None,
                   help="override the per-dtype warmup-gate tolerance "
                        "recorded in the quantized artifact "
                        "(quant.GATE_TOLERANCES)")
    p.add_argument("--test_period", type=int, default=0,
                   help="run the test reader every N passes during train")
    p.add_argument("--trainer_count", type=int, default=1,
                   help=">1 builds a data-parallel mesh over that many "
                        "devices")
    p.add_argument("--use_gpu", default=None,
                   help="accepted for compatibility; device choice is "
                        "JAX's")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prev_batch_state", action="store_true",
                   help="carry RNN state across batches (truncated BPTT, "
                        "the reference's --prev_batch_state)")
    p.add_argument("--fp_anomaly", action="store_true",
                   help="raise at the first op producing NaN/Inf (the "
                        "reference's feenableexcept, TrainerMain.cpp:49)")
    p.add_argument("--time_batches", type=int, default=20,
                   help="--job=time: timed batches after warmup")
    p.add_argument("--time_warmup", type=int, default=3)
    p.add_argument("--compute_dtype", default=None,
                   choices=["bfloat16", "float32"],
                   help="mixed precision (TPU-native addition): f32 "
                        "master params, forward/backward in this dtype")
    p.add_argument("--use_async_load_data", action="store_true",
                   help="decode/pad/shard/device_put batches in a "
                        "background thread, overlapped with the device "
                        "step (the reference's --use_async_load_data "
                        "double buffer, DataProvider.h:249)")
    p.add_argument("--prefetch_depth", type=int, default=2,
                   help="batches in flight under --use_async_load_data "
                        "(2 = double buffer)")
    p.add_argument("--metrics_port", type=int, default=0,
                   help="bind a /metrics exporter (Prometheus text + "
                        "?format=json) for this process: --job=train "
                        "exports the live StepBreakdown + per-device "
                        "memory_stats (the serving jobs already expose "
                        "/metrics on their HTTP frontend); 0 disables")
    p.add_argument("--show_step_breakdown", action="store_true",
                   help="log the per-step host-time split {data_wait, "
                        "h2d, compute, callback} and per-device "
                        "param/optimizer-slot bytes at each log_period")
    p.add_argument("--use_zero1", action="store_true",
                   help="ZeRO-1 sharded optimizer update: partition "
                        "optimizer state over the data axis (each device "
                        "holds 1/N of every slot), update shard-wise, "
                        "all-gather params — the pserver's sharded "
                        "update (ParameterServer2.cpp:362), TPU-native")
    p.add_argument("--fsdp", action="store_true",
                   help="full FSDP: shard PARAMETERS (not just optimizer "
                        "slots) flat-packed 1/N over a dedicated fsdp "
                        "mesh axis with one all-gather per layer on use "
                        "and reduce-scattered gradients "
                        "(optim/zero1.py:FsdpUpdater; "
                        "docs/spec_layout.md) — a model ~N× one "
                        "device's memory trains on N devices. The "
                        "--trainer_count width moves onto the fsdp axis "
                        "(batch rows still split over it, so the DP "
                        "degree is unchanged); composes with "
                        "--parallel_nn, --use_zero1 and seq-parallel "
                        "configs. Checkpoints stay format-compatible "
                        "crossing --fsdp on/off")
    p.add_argument("--fsdp_overlap", default="on",
                   choices=["on", "off", "force"],
                   help="--fsdp: overlap each layer's param all-gather "
                        "with the previous layer's compute (and the "
                        "grad reduce-scatters with backward) via an "
                        "optimization-barrier prefetch chain, double-"
                        "buffering at most two gathered layers "
                        "(optim/zero1.py:FsdpUpdater.full_params; "
                        "docs/spec_layout.md). 'on' engages on TPU "
                        "backends only (audit compiles on CPU keep the "
                        "sync spelling), 'force' engages everywhere, "
                        "'off' keeps the sync spelling")
    p.add_argument("--grad_accum_steps", type=int, default=1,
                   help="split each batch into k microbatches scanned "
                        "inside the jitted step, applying the optimizer "
                        "(and gradient clipping) once on the accumulated "
                        "gradient — a k× effective batch at 1/k the "
                        "activation memory")
    p.add_argument("--checkgrad_eps", type=float, default=1e-3,
                   help="--job=checkgrad finite-difference step (the "
                        "reference's --checkgrad_eps; default loosened "
                        "from 1e-5 because the engine computes in f32)")
    p.add_argument("--parallel_nn", action="store_true",
                   help="train the config's per-layer device placement "
                        "as a pipeline: layers pinned device=0..S-1 "
                        "become GPipe stages over an S-slot pipe mesh "
                        "axis, parameters sharded one stage per slot "
                        "(the reference's --parallel_nn, Flags.cpp:23 / "
                        "ParallelNeuralNetwork.h:23-62). Warns and "
                        "trains unpipelined when the config has no "
                        "device attrs or devices are short")
    p.add_argument("--pipeline_microbatches", type=int, default=0,
                   help="microbatches per batch under --parallel_nn "
                        "(bubble fraction = (S-1)/(S+M-1)); 0 = auto "
                        "(the stage count, or --grad_accum_steps)")
    # --job=serve (paddle_tpu.serving): the model server
    p.add_argument("--port", type=int, default=8000,
                   help="--job=serve: HTTP port (0 = ephemeral)")
    p.add_argument("--host", default="127.0.0.1",
                   help="--job=serve: bind address")
    p.add_argument("--batch_timeout_ms", type=float, default=5.0,
                   help="--job=serve: how long the dynamic batcher waits "
                        "to coalesce concurrent requests into one "
                        "device batch")
    p.add_argument("--max_batch", type=int, default=32,
                   help="--job=serve: largest coalesced batch (also the "
                        "largest warmed batch bucket; buckets double "
                        "1,2,4,... up to it)")
    p.add_argument("--queue_depth", type=int, default=128,
                   help="--job=serve: bounded request queue; past the "
                        "shed watermark new requests get a typed 429 "
                        "with Retry-After")
    p.add_argument("--shed_watermark", type=int, default=0,
                   help="--job=serve: queue depth that triggers load "
                        "shedding (0 = queue_depth)")
    p.add_argument("--serving_length_buckets", default="32,64,128",
                   help="--job=serve: comma-separated padded sequence "
                        "lengths to warm (the closed shape menu); "
                        "requests longer than the largest are rejected "
                        "with a typed 400")
    p.add_argument("--serving_deadline_ms", type=float, default=0,
                   help="--job=serve: default per-request deadline "
                        "(0 = none; requests may set their own)")
    p.add_argument("--decode_chunk", type=int, default=None,
                   help="decoder steps per compiled chunk of the "
                        "early-exit beam search (core/generation.py): "
                        "the search exits at the first chunk boundary "
                        "where every beam finished, so decode cost is "
                        "proportional to actual output length, not "
                        "max_length. 0 = full-length scan (the escape "
                        "hatch / A-B baseline); unset = the config's "
                        "pinned decode policy, else chunks of 8")
    p.add_argument("--serving_continuous_batching", action="store_true",
                   help="--job=serve: continuous batching for "
                        "/v1/generate — finished lanes retire and "
                        "queued requests are admitted at every "
                        "--decode_chunk boundary, so one slow request "
                        "no longer convoys its batch and deadlines are "
                        "enforced mid-decode")
    p.add_argument("--replicas", type=int, default=1,
                   help="--job=serve: run N replica engines behind the "
                        "health-aware router (serving/router.py): "
                        "failover on replica death, circuit breakers, "
                        "auto-respawn, rolling reload via POST "
                        "/admin/reload. Each replica warms from the "
                        "shared --aot_cache_dir, so replicas 2..N (and "
                        "every respawn) cold-start in milliseconds")
    p.add_argument("--aot_cache_dir", default=None,
                   help="--job=serve: persist the warmed bucket menu as "
                        "serialized compiled executables keyed by model "
                        "hash x bucket x jax/XLA version "
                        "(serving/aot_cache.py); a respawned replica "
                        "deserializes the menu instead of re-tracing "
                        "it. Misses/stale/corrupt entries fall back to "
                        "the live trace")
    p.add_argument("--hedge_ms", type=float, default=0,
                   help="--job=serve with --replicas>1: fire a capped "
                        "second attempt for an unanswered idempotent "
                        "score request after this many ms (never for "
                        "generate); 0 = hedging off")
    # --job=serve_fleet (serving/supervisor.py): the self-operating
    # fleet — supervisor-spawned single-replica server PROCESSES behind
    # the router, load-driven autoscaling, router HA via a warm standby
    p.add_argument("--min_replicas", type=int, default=1,
                   help="--job=serve_fleet: autoscale floor (the "
                        "supervisor spawns this many replica processes "
                        "at start)")
    p.add_argument("--max_replicas", type=int, default=None,
                   help="--job=serve_fleet: autoscale ceiling (default: "
                        "min_replicas — autoscaling pinned off)")
    p.add_argument("--standby", action="store_true",
                   help="--job=serve_fleet: run this router as the WARM "
                        "STANDBY — frontend bound and answering (503 "
                        "until adoption), watching --peer's /healthz; "
                        "on the active's death it takes the role lease "
                        "and adopts the replica set")
    p.add_argument("--peer", default=None,
                   help="--job=serve_fleet --standby: host:port of the "
                        "active router frontend to watch")
    p.add_argument("--fleet_lease", default=None,
                   help="--job=serve_fleet: path of the active-role "
                        "lease file BOTH routers share (FileStore; the "
                        "epoch-fenced election record). Required when a "
                        "--standby is deployed")
    p.add_argument("--lease_timeout_s", type=float, default=5.0,
                   help="--job=serve_fleet: replica liveness lease — a "
                        "replica whose health probes stop renewing for "
                        "this long is SIGTERM/SIGKILLed and respawned; "
                        "also the active-role lease ttl")
    p.add_argument("--autoscale_up_backlog_ms", type=float, default=50.0,
                   help="--job=serve_fleet: EWMA fleet backlog above "
                        "this (sustained) scales up")
    p.add_argument("--autoscale_down_backlog_ms", type=float,
                   default=5.0,
                   help="--job=serve_fleet: EWMA fleet backlog below "
                        "this (sustained) scales down")
    p.add_argument("--slo_p99_ms", type=float, default=0,
                   help="--job=serve: attach the online SLO controller "
                        "(serving/tuner.py:SLOController) targeting "
                        "this end-to-end p99; it nudges "
                        "batch_timeout_ms (and, when shedding at the "
                        "floor, max_batch within the warmed bucket "
                        "menu) through the same typed apply_config "
                        "path operators use, with Autoscaler-style "
                        "hysteresis. 0 (default) = off")
    p.add_argument("--slo_max_shed_rate", type=float, default=0.0,
                   help="--slo_p99_ms: shed-rate budget of the SLO "
                        "target — a windowed shed rate above this "
                        "counts as an SLO breach even when p99 is "
                        "inside target")
    p.add_argument("--workload_record", default=None,
                   help="--job=serve: tap the admission path "
                        "(serving/workload.py:WorkloadRecorder) and "
                        "write the offered stream — admitted AND shed "
                        "— to this WORKLOAD_*.json artifact at "
                        "shutdown, replayable via replay()/GridTuner "
                        "for offline tuning")
    # --job=serve_train (paddle_tpu/online): the online learning loop —
    # serving traffic streams into the trainer, publishes roll back out
    p.add_argument("--replay_dir", default=None,
                   help="--job=serve_train: replay-log directory — the "
                        "serving engines append answered score rows "
                        "here (durable PTRL1 segments), the tailer "
                        "trains them exactly-once through the ledger "
                        "(its snapshot lives here too), and the loop "
                        "resumes from it after a crash")
    p.add_argument("--publish_dir", default=None,
                   help="--job=serve_train: directory for published "
                        "PTM1 artifacts (model-vNNNN.ptmodel; default "
                        "<replay_dir>/published). --quantize applies "
                        "to every publish merge, gated by the serving "
                        "warmup accuracy gate — a refused artifact "
                        "rolls back and the incumbent keeps serving")
    p.add_argument("--publish_every", type=int, default=50,
                   help="--job=serve_train: publish + rolling hot-swap "
                        "cadence in trained batches")
    p.add_argument("--replay_segment_records", type=int, default=200,
                   help="--job=serve_train: rows per replay segment "
                        "before the fsync'd seal makes it visible to "
                        "the tailer (the durability granularity of the "
                        "serving->training edge)")
    p.add_argument("--replay_batch_rows", type=int, default=100,
                   help="--job=serve_train: rows per training batch "
                        "assembled from a sealed segment")
    p.add_argument("--serve_train_batches", type=int, default=0,
                   help="--job=serve_train: close the stream after this "
                        "many trained batches (0 = run until killed; "
                        "the durable replay+ledger+checkpoint state "
                        "resumes the loop exactly-once on restart)")
    args = p.parse_args(argv)
    if args.publish_dir is None and args.replay_dir:
        args.publish_dir = os.path.join(args.replay_dir, "published")
    return args


def load_config(path: str, config_args: str = ""):
    """Execute the config file; returns its namespace. Configs that import
    the v1 surface (``from paddle.trainer_config_helpers import *``) go
    through the compat config compiler (the reference's embedded
    ``parse_config`` contract, ``TrainerConfigHelper.cpp:33-57``) so
    reference configs run unmodified; native configs are executed directly
    and must define ``cost``."""
    import re
    with open(path) as f:
        src = f.read()
    # route on actual import statements, not mere mentions in comments;
    # .conf files are ALWAYS v1 configs — the oldest ones use the bare
    # @config_func spelling (default_initial_std, TrainData, Layer...)
    # with no import at all (paddle_trainer injected the names)
    if path.endswith(".conf") or re.search(
            r"^\s*(from|import)\s+paddle\.trainer", src, re.M):
        return _load_v1_config(path, config_args)
    from paddle_tpu.config import dsl
    dsl.reset()
    ns = {"__file__": os.path.abspath(path), "__name__": "__paddle_config__"}
    for kv in filter(None, config_args.split(",")):
        k, _, v = kv.partition("=")
        try:
            ns[k] = int(v)
        except ValueError:
            try:
                ns[k] = float(v)
            except ValueError:
                ns[k] = v
    with open(path) as f:
        code = compile(f.read(), path, "exec")
    exec(code, ns)
    if "cost" not in ns:
        raise SystemExit(f"config {path} must define `cost`")
    return ns


def _load_v1_config(path: str, config_args: str = ""):
    """v1 config -> the same namespace contract the native path produces
    (cost/optimizer/train_reader/test_reader/feeding/outputs)."""
    from paddle_tpu.compat import parse_config
    parsed = parse_config(path, config_args)

    out_names = list(parsed.context.output_layer_names)
    if not parsed.cost_layers() and not out_names:
        raise SystemExit(f"config {path} declares no outputs()")
    # --job=train on an inference-only topology fails later, by design
    cost = parsed.topology()

    ns = {
        "__file__": os.path.abspath(path),
        "parsed_config": parsed,
        "cost": cost,
        "optimizer": parsed.optimizer(),
        "feeding": parsed.feeding(),
        "outputs": out_names,
        "evaluators": list(parsed.context.evaluators),
    }
    ns["train_reader"] = (parsed.train_reader()
                          if parsed.context.train_source else None)
    ns["test_reader"] = (parsed.test_reader()
                         if parsed.context.test_source else None)
    return ns


def _build_trainer(ns, args):
    from paddle_tpu.optim.optimizers import Momentum
    from paddle_tpu.trainer.trainer import SGD, Topology
    topo = (ns["cost"] if isinstance(ns["cost"], Topology)
            else Topology(ns["cost"]))
    mesh = None
    n_pipe = 1
    if getattr(args, "parallel_nn", False):
        # the reference flag: per-layer device placement becomes GPipe
        # stages (ParallelNeuralNetwork.h:23-62); the mesh needs a pipe
        # axis exactly as wide as the config's stage count
        from paddle_tpu.parallel.pipeline import split_pipeline_graph
        from paddle_tpu.utils import logger
        try:
            stages, _ = split_pipeline_graph(topo.graph)
            n_pipe = len(stages)
        except ValueError as e:
            logger.warning("--parallel_nn: %s — training unpipelined", e)
    import jax
    need = max(args.trainer_count, 1) * n_pipe
    if need > len(jax.devices()):
        # never train narrower than asked: a run meant for four chips
        # that comes up on one must fail, not degrade
        raise SystemExit(
            f"--trainer_count {max(args.trainer_count, 1)}"
            + (f" x {n_pipe} --parallel_nn stages" if n_pipe > 1 else "")
            + f" needs {need} devices, this process has "
            f"{len(jax.devices())} x {jax.devices()[0].device_kind}")
    n_fsdp = 1
    if getattr(args, "fsdp", False):
        # the data-parallel width moves onto the fsdp axis: batch rows
        # still split over it (mesh.batch_axes includes fsdp), but
        # parameters/slots pack 1/N per device instead of replicating
        n_fsdp = (args.trainer_count if args.trainer_count > 1
                  else len(jax.devices()) // n_pipe)
        if n_fsdp <= 1:
            raise SystemExit(
                f"--fsdp: {len(jax.devices())} device(s) for {n_pipe} "
                "pipeline stage(s) — nothing to shard parameters over")
    if n_pipe > 1 or n_fsdp > 1:
        from paddle_tpu.parallel import create_mesh
        mesh = create_mesh(
            n_data=(max(args.trainer_count, 1) if n_fsdp == 1 else 1),
            n_fsdp=n_fsdp, n_pipe=n_pipe)
    elif args.trainer_count > 1:
        from paddle_tpu.parallel import create_mesh
        mesh = create_mesh(n_data=args.trainer_count)
    optimizer = ns.get("optimizer") or Momentum(learning_rate=0.01,
                                                momentum=0.9)
    dtype = getattr(args, "compute_dtype", None)
    trainer = SGD(cost=topo, update_equation=optimizer, mesh=mesh,
                  seed=args.seed, evaluators=ns.get("evaluators"),
                  prev_batch_state=getattr(args, "prev_batch_state", False),
                  compute_dtype=None if dtype in (None, "float32") else dtype)
    if args.init_model_path:
        # BEFORE enable_pipeline: init files carry flat per-stage names
        # and _init_params maps them through the (flat) meta
        _init_params(trainer, args.init_model_path)
    if n_pipe > 1:
        # enabled HERE so every --job (train/time/...) sees the
        # pipelined step; SGD.train(pipeline=None) keeps the mode sticky
        trainer.enable_pipeline(
            microbatches=getattr(args, "pipeline_microbatches", 0) or None)
    if n_fsdp > 1:
        # likewise HERE (after the pipeline stacks its body, so the
        # fsdp plan sees the final layout); train(fsdp=None) is sticky
        overlap = {"on": True, "off": False, "force": "force"}[
            getattr(args, "fsdp_overlap", "on")]
        trainer.enable_fsdp(overlap=overlap)
    return trainer


def _init_params(trainer, path):
    import os
    if os.path.isdir(path):
        # a reference pass/model directory: one Parameter::save binary
        # file per parameter (the --init_model_path contract,
        # Trainer.cpp:229-250) — reference-trained models load directly
        import jax.numpy as jnp

        from paddle_tpu.compat.param_format import load_v1_model_dir
        raw = load_v1_model_dir(path)
        params = dict(trainer.params)
        missing, loaded = [], 0
        for name, spec in trainer.meta.items():
            if name not in raw:
                missing.append(name)
                continue
            flat = raw[name]
            want = 1
            for d in spec.shape:
                want *= int(d)
            if flat.size != want:
                raise ValueError(
                    f"--init_model_path: parameter {name!r} has "
                    f"{flat.size} values, the model needs {want} "
                    f"(shape {spec.shape}; fused-gate layouts may need "
                    "repacking)")
            params[name] = jnp.asarray(flat.reshape(spec.shape))
            loaded += 1
        if missing:
            from paddle_tpu.utils import logger
            logger.warning("--init_model_path: %d parameters missing in "
                           "%s (kept initialized): %s", len(missing),
                           path, missing[:5])
        trainer.load_state(params)
        return
    if path.endswith(".ptmodel"):
        from paddle_tpu.trainer.merge_model import load_merged
        _, params, _ = load_merged(path)
        trainer.load_state(params)
    else:
        from paddle_tpu.trainer.checkpoint import load_params
        params, opt_flat = load_params(path)
        trainer.load_state(params, opt_flat)


def _feeder(ns):
    from paddle_tpu.data.feeder import DataFeeder
    feeding = ns.get("feeding")
    return DataFeeder(feeding) if isinstance(feeding, dict) else feeding


def cmd_train(ns, args):
    from paddle_tpu.trainer import events as ev
    trainer = _build_trainer(ns, args)
    reader = ns.get("train_reader")
    if reader is None:
        raise SystemExit("config must define `train_reader` for --job=train")
    ck = None
    if args.save_dir:
        from paddle_tpu.dist.checkpoint import Checkpointer
        ck = Checkpointer(args.save_dir, saving_period=args.saving_period,
                          saving_period_by_batches=(
                              args.saving_period_by_batches),
                          background=getattr(args, "background_save", True))

    test_reader = ns.get("test_reader")
    feeder = _feeder(ns)

    def handler(e):
        if isinstance(e, ev.EndPass):
            print(f"Pass {e.pass_id}: " + " ".join(
                f"{k}={v:.5g}" for k, v in e.evaluator.items()))
            if (test_reader is not None and args.test_period
                    and (e.pass_id + 1) % args.test_period == 0):
                res = trainer.test(test_reader, feeder=feeder)
                print(f"  Test: cost={res.cost:.5g} " + " ".join(
                    f"{k}={v:.5g}" for k, v in res.evaluator.items()))

    # training-health plane: the sentry flags arm the in-step
    # finiteness/threshold check; --health_log adds the JSONL scalar
    # timeline; --show_parameter_stats_period arms the fused per-layer
    # telemetry inside trainer.train (the dedupe — no second forward)
    health = None
    sentry = bool(getattr(args, "error_clipping_threshold", 0.0)
                  or getattr(args, "log_error_clipping", False))
    if sentry or getattr(args, "health_log", None):
        health = {
            "sentry": sentry,
            "grad_threshold": getattr(args, "error_clipping_threshold",
                                      0.0),
            "policy": getattr(args, "divergence_policy", "skip_batch"),
            "log_clipping": getattr(args, "log_error_clipping", False),
            "log_path": getattr(args, "health_log", None),
        }

    metrics_srv = None
    if getattr(args, "metrics_port", 0):
        # metrics federation for the training side: the SAME scrape
        # surface the serving fleet has, exporting the live
        # StepBreakdown split + per-device memory accounting + the
        # training-health snapshot (pillar 4) — so the router-side
        # federation pattern shows trainer health with zero extra code
        from paddle_tpu.obs import MetricsRegistry, serve_metrics

        def train_snapshot():
            out = {"step_breakdown": trainer.breakdown.summary()}
            try:
                from paddle_tpu.utils.profiler import memory_stats
                out["memory"] = memory_stats(
                    trainer.params, getattr(trainer, "opt_state", None))
            except Exception as e:  # noqa: BLE001 — a scrape must
                # never interrupt training
                out["memory"] = {"error": repr(e)}
            return out

        def health_snapshot():
            hm = getattr(trainer, "_health", None)
            return hm.snapshot() if hm is not None else {"armed": False}

        registry = MetricsRegistry().register("train", train_snapshot)
        registry.register("health", health_snapshot)
        metrics_srv = serve_metrics(registry, host=args.host,
                                    port=args.metrics_port)
        print(f"train metrics on http://{args.host}:"
              f"{metrics_srv.server_address[1]}/metrics", flush=True)
    try:
        trainer.train(reader, feeder=feeder, num_passes=args.num_passes,
                      event_handler=handler, log_period=args.log_period,
                      dot_period=args.dot_period,
                      show_parameter_stats_period=(
                          args.show_parameter_stats_period),
                      show_layer_stat=args.show_layer_stat,
                      async_load_data=getattr(args, "use_async_load_data",
                                              False),
                      prefetch_depth=getattr(args, "prefetch_depth", 2),
                      show_step_breakdown=getattr(args,
                                                  "show_step_breakdown",
                                                  False),
                      zero1=True if getattr(args, "use_zero1", False)
                      else None,
                      fsdp=True if getattr(args, "fsdp", False) else None,
                      grad_accum_steps=getattr(args, "grad_accum_steps",
                                               1),
                      checkpointer=ck,
                      auto_resume=getattr(args, "auto_resume", True),
                      health=health)
    finally:
        if metrics_srv is not None:
            metrics_srv.shutdown()
            metrics_srv.server_close()
    return 0


def cmd_test(ns, args):
    trainer = _build_trainer(ns, args)
    if not args.init_model_path and args.save_dir:
        from paddle_tpu.dist.checkpoint import Checkpointer
        restored = Checkpointer(args.save_dir).restore()
        if restored:
            trainer.load_state(restored[0], restored[1])
    reader = ns.get("test_reader") or ns.get("train_reader")
    res = trainer.test(reader, feeder=_feeder(ns))
    print(f"Test: cost={res.cost:.5g} " + " ".join(
        f"{k}={v:.5g}" for k, v in res.evaluator.items()))
    return 0


def cmd_time(ns, args):
    """`paddle_trainer --job=time`: steady-state batch latency. Batches
    whose shapes differ from the first (e.g. a smaller final partial
    batch) are excluded — their jit recompile would otherwise put XLA
    compile time inside the timed window."""
    trainer = _build_trainer(ns, args)
    reader = ns.get("train_reader")
    if reader is None:
        raise SystemExit("config must define `train_reader` for --job=time")
    feeder = _feeder(ns)
    want = args.time_warmup + args.time_batches
    batches = []
    while len(batches) < want:
        before = len(batches)
        for data in reader():
            batches.append(data)
            if len(batches) >= want:
                break
        if len(batches) == before:
            break  # reader is empty/exhausted; time what we have
    if not batches:
        raise SystemExit("train_reader produced no batches")
    import jax
    import jax.numpy as jnp

    def shape_sig(feed):
        return tuple(sorted((k, v.value.shape) for k, v in feed.items()))

    times = []
    sig0 = None
    for i, data in enumerate(batches):
        feed = feeder(data) if feeder is not None else data
        # the feeder's host arrays, placed outside the timed step
        feed = jax.device_put(feed)
        sig = shape_sig(feed)
        sig0 = sig0 or sig
        trainer._rng, step_rng = jax.random.split(trainer._rng)
        t0 = time.perf_counter()
        trainer.params, trainer.opt_state, metrics = trainer._train_step(
            trainer.params, trainer.opt_state, feed, step_rng, jnp.int32(0))
        # the host fetch closes the timed window (it waits for the step)
        float(metrics["cost"])
        dt = time.perf_counter() - t0
        if i >= args.time_warmup and sig == sig0:
            times.append(dt)
    if not times:
        raise SystemExit("no steady-state batches to time (all warmup or "
                         "shape-mismatched)")
    ms = 1e3 * sum(times) / len(times)
    print(f"TimeInfo: avg_batch_time={ms:.3f}ms over {len(times)} batches "
          f"(skipped {args.time_warmup} warmup)")
    return 0


def cmd_checkgrad(ns, args, *, epsilon=None, rtol=5e-2, samples=6):
    """Numeric gradient check on one batch (`Trainer::checkGradient`).
    rtol is loose relative to the reference's double-precision check:
    the engine computes in float32, so the central difference itself
    carries ~1e-2 relative noise."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if epsilon is None:
        epsilon = getattr(args, "checkgrad_eps", 1e-3)
    trainer = _build_trainer(ns, args)
    reader = ns.get("train_reader")
    feeder = _feeder(ns)
    data = next(iter(reader()))
    feed = feeder(data) if feeder is not None else data
    network, cost_name = trainer.network, trainer.topology.cost_name
    # the flat per-stage view: under --parallel_nn the live params are
    # stage-stacked, but the check runs the plain graph
    tparams = trainer._flat_params_view()

    # feed is a traced argument, not a closure capture: XLA embeds
    # captures as program constants (graftlint PT101, the ~4x/step
    # deopt class) — and the numeric loop below re-calls loss_fn with
    # perturbed params against the SAME embedded batch either way
    @jax.jit
    def loss_fn(params, feed):
        out = network.apply(params, feed, train=False)
        return jnp.sum(out[cost_name].value) / out[cost_name].value.shape[0]

    analytic = jax.jit(jax.grad(loss_fn))(tparams, feed)
    rng = np.random.RandomState(args.seed)
    worst = 0.0
    failed = []
    for name, g in analytic.items():
        if trainer.network.param_specs[name].is_static:
            continue
        p0 = np.asarray(tparams[name], dtype=np.float64)
        for idx in rng.choice(p0.size, size=min(samples, p0.size),
                              replace=False):
            delta = np.zeros(p0.size)
            delta[idx] = epsilon
            delta = delta.reshape(p0.shape)
            pp = dict(tparams)
            pp[name] = jnp.asarray(p0 + delta, jnp.float32)
            pm = dict(tparams)
            pm[name] = jnp.asarray(p0 - delta, jnp.float32)
            num = (float(loss_fn(pp, feed))
                   - float(loss_fn(pm, feed))) / (2 * epsilon)
            ana = float(np.asarray(g).reshape(-1)[idx])
            denom = max(abs(num), abs(ana), 1e-4)
            rel = abs(num - ana) / denom
            worst = max(worst, rel)
            if rel > rtol:
                failed.append((name, int(idx), num, ana))
    if failed:
        for name, idx, num, ana in failed[:10]:
            print(f"FAIL {name}[{idx}]: numeric={num:.6g} "
                  f"analytic={ana:.6g}")
        print(f"checkgrad FAILED ({len(failed)} mismatches, "
              f"worst rel err {worst:.3g})")
        return 1
    print(f"checkgrad PASSED (worst rel err {worst:.3g})")
    return 0


def cmd_merge(ns, args):
    from paddle_tpu.config import dsl
    from paddle_tpu.trainer.merge_model import merge_model
    trainer = _build_trainer(ns, args)
    if not args.init_model_path and args.save_dir:
        from paddle_tpu.dist.checkpoint import Checkpointer
        restored = Checkpointer(args.save_dir).restore()
        if restored:
            trainer.load_state(restored[0], restored[1])
    out_path = args.model_path or "model.ptmodel"
    outputs = ns.get("outputs")
    names = ([o.name if hasattr(o, "name") else o for o in outputs]
             if outputs else [ns["cost"].name])
    params = trainer._params_for_save()
    quant_meta = golden = None
    if args.quantize:
        from paddle_tpu import quant as quant_lib
        feeding = ns.get("feeding")
        if not isinstance(feeding, dict):
            feeding = getattr(feeding, "feeding", None)
        if not isinstance(feeding, dict):
            raise SystemExit(
                "--quantize needs the config to define `feeding` "
                "(data-layer name -> InputType) so the golden "
                "warmup-gate set can be recorded with the artifact")
        # golden refs come from the UNQUANTIZED params — the fp32
        # reference side of the warmup accuracy gate
        golden = quant_lib.golden_section(
            trainer.topology.graph, params, names, feeding)
        sparse = {name for name, spec in trainer.meta.items()
                  if getattr(spec, "sparse_grad", False)}
        params, quant_meta = quant_lib.quantize_params(
            params, args.quantize, sparse_names=sparse)
        if args.quantize_tol is not None:
            quant_meta["tol"] = float(args.quantize_tol)
    merge_model(out_path, trainer.topology.graph, params,
                outputs=names, quant=quant_meta, golden=golden)
    tag = f" ({args.quantize} quantized)" if args.quantize else ""
    print(f"merged model written to {out_path}{tag}")
    return 0


def _ensure_generation_params(graph, params):
    """The trainer only initializes parameters reachable from the cost;
    a ``beam_search_group``'s hoisted step-net params and generated-word
    embedding are not, so serving a generating config from a fresh init
    would KeyError inside the jitted search. Fill the gaps (tiny random
    init) with a warning — real deployments load them via
    ``--init_model_path`` / a checkpoint, where the training-time
    decoder shares the same hoisted names."""
    import numpy as np

    import jax.numpy as jnp

    from paddle_tpu.core.registry import get_layer_impl
    from paddle_tpu.utils.log import get_logger
    rng = np.random.RandomState(0)
    missing = []
    for name, ldef in graph.layers.items():
        if ldef.type != "beam_search_group":
            continue
        impl = get_layer_impl("beam_search_group")
        for _, spec in impl.params(ldef, []).items():
            if spec.absolute_name not in params:
                missing.append(spec.absolute_name)
                params[spec.absolute_name] = jnp.asarray(
                    rng.randn(*spec.shape).astype(np.float32) * 0.01)
        g = ldef.attrs["gen"]
        if g["embedding_name"] not in params:
            missing.append(g["embedding_name"])
            params[g["embedding_name"]] = jnp.asarray(rng.randn(
                g["size"], g["embedding_size"]).astype(np.float32) * 0.01)
    if missing:
        get_logger("serving").warning(
            "generation parameters %s were not in the loaded/initialized "
            "table (the trainer only walks the cost graph); serving with "
            "fresh small-random values — load a trained model via "
            "--init_model_path for real generation", missing)


def _serving_plan(ns, args):
    """The shared --job=serve wiring: (graph, params, output names,
    feeding, predictor kwargs, engine kwargs) — everything a replica
    engine is built from. Parameter source order mirrors --job=test:
    --init_model_path (checkpoint file, merged .ptmodel, or a reference
    model dir), else the newest checkpoint in --save_dir; the config
    supplies graph + feeding + outputs."""
    trainer = _build_trainer(ns, args)
    if not args.init_model_path and args.save_dir:
        from paddle_tpu.dist.checkpoint import Checkpointer
        restored = Checkpointer(args.save_dir).restore()
        if restored:
            trainer.load_state(restored[0], restored[1])
    feeding = ns.get("feeding")
    if not isinstance(feeding, dict):
        feeding = getattr(feeding, "feeding", None)
    if not isinstance(feeding, dict):
        raise SystemExit("--job=serve needs the config to define "
                         "`feeding` (data-layer name -> InputType)")
    outputs = ns.get("outputs")
    names = ([o.name if hasattr(o, "name") else o for o in outputs]
             if outputs else [ns["cost"].name])
    max_batch = max(args.max_batch, 1)
    batch_buckets = [1]
    while batch_buckets[-1] < max_batch:
        batch_buckets.append(min(batch_buckets[-1] * 2, max_batch))
    length_buckets = [int(x) for x in filter(
        None, str(args.serving_length_buckets).split(","))]
    # None = inherit the config's pinned decode policy; 0 = full scan
    decode_chunk = getattr(args, "decode_chunk", None)
    params = dict(trainer._flat_params_view())
    pred_kwargs = dict(
        batch_buckets=batch_buckets, length_buckets=length_buckets,
        gen_decode_chunk=decode_chunk,
        gen_full_scan=(None if decode_chunk is None
                       else decode_chunk <= 0),
        aot_cache=getattr(args, "aot_cache_dir", None))
    mp = args.init_model_path
    if mp and mp.endswith(".ptmodel"):
        # A merged artifact owns its serving identity: the PTM1 digest
        # keys the AOT cache and names the published model_version (the
        # same identity the fleet reload path reports), and a
        # ``--quantize`` artifact's optional sections MUST reach the
        # predictor — the trainer round-trip above goes through the
        # extras-ignoring old reader, which would silently serve raw
        # storage-dtype leaves with no scales and no warmup gate.
        from paddle_tpu.trainer.merge_model import (load_merged_ex,
                                                    merged_digest)
        _, mparams, _, extras = load_merged_ex(mp)
        pred_kwargs["model_hash"] = merged_digest(mp)
        if extras.get("quant") or extras.get("golden"):
            params = dict(mparams)  # storage-dtype leaves, scales apart
            pred_kwargs["quant"] = extras.get("quant")
            pred_kwargs["golden"] = extras.get("golden")
    _ensure_generation_params(trainer.topology.graph, params)
    eng_kwargs = dict(
        max_batch=max_batch,
        batch_timeout_ms=args.batch_timeout_ms,
        queue_depth=args.queue_depth,
        shed_watermark=args.shed_watermark or None,
        default_deadline_ms=args.serving_deadline_ms or None,
        continuous_batching=getattr(args, "serving_continuous_batching",
                                    False))
    return trainer.topology.graph, params, names, feeding, \
        pred_kwargs, eng_kwargs


def build_serving_engine(ns, args):
    """One replica engine from the serving plan (tests and embedders
    build the engine without entering serve_forever)."""
    from paddle_tpu.serving import ServingEngine, ServingPredictor
    graph, params, names, feeding, pk, ek = _serving_plan(ns, args)
    return ServingEngine(
        ServingPredictor(graph, params, names, feeding, **pk), **ek)


def build_serving_fleet(ns, args):
    """--replicas N: N replica engines (each its own predictor, all
    warming from the shared --aot_cache_dir — replica 0 traces live and
    populates the cache, replicas 1..N-1 and every respawn deserialize
    it) behind the health-aware router. Returns ``(router,
    reload_builder)`` — the builder backs ``POST /admin/reload``
    (rolling hot-swap to a new merged artifact)."""
    from paddle_tpu.serving import (EngineTransport, ReplicaRouter,
                                    ServingEngine, ServingPredictor)
    graph, params, names, feeding, pk, ek = _serving_plan(ns, args)

    def make_engine(from_model_path=None):
        if from_model_path is not None:
            pred = ServingPredictor.from_merged(
                from_model_path, feeding, **pk)
        else:
            pred = ServingPredictor(graph, params, names, feeding, **pk)
        return ServingEngine(pred, **ek).start(warmup=True)

    transports = [EngineTransport(make_engine())
                  for _ in range(max(1, args.replicas))]
    # the respawn factory rebuilds a replica after worker death; the
    # reload builder swaps in a NEW artifact (both warm from the cache)
    router = ReplicaRouter(
        transports,
        spawn=lambda rid: EngineTransport(make_engine()),
        hedge_ms=(args.hedge_ms or None))

    def reload_builder(model_path, rid):
        return EngineTransport(make_engine(from_model_path=model_path))

    return router, reload_builder


def _replica_cmd(args, port):
    """The child command line for one supervised single-replica server:
    the parent's serving config re-spelled as ``--job=serve`` on its own
    port, with ``--aot_cache_dir`` threaded through so every respawn
    deserializes its bucket menu instead of re-tracing it."""
    cmd = [sys.executable, "-m", "paddle_tpu.trainer.cli",
           "--config", args.config, "--job", "serve",
           "--host", args.host, "--port", str(port),
           "--batch_timeout_ms", str(args.batch_timeout_ms),
           "--max_batch", str(args.max_batch),
           "--queue_depth", str(args.queue_depth),
           "--serving_length_buckets", str(args.serving_length_buckets)]
    if args.config_args:
        cmd += ["--config_args", args.config_args]
    if args.shed_watermark:
        cmd += ["--shed_watermark", str(args.shed_watermark)]
    if args.serving_deadline_ms:
        cmd += ["--serving_deadline_ms", str(args.serving_deadline_ms)]
    if args.decode_chunk is not None:
        cmd += ["--decode_chunk", str(args.decode_chunk)]
    if args.serving_continuous_batching:
        cmd += ["--serving_continuous_batching"]
    if args.aot_cache_dir:
        cmd += ["--aot_cache_dir", args.aot_cache_dir]
    if args.init_model_path:
        cmd += ["--init_model_path", args.init_model_path]
    elif args.save_dir:
        cmd += ["--save_dir", args.save_dir]
    return cmd


def cmd_serve_fleet(ns, args):
    """``--job=serve_fleet``: the self-operating fleet. The supervisor
    spawns ``--min_replicas`` real single-replica server processes
    (``--job=serve`` children) and leases their liveness; the router
    fronts them over HTTPTransports; the autoscaler moves the count
    inside ``[--min_replicas, --max_replicas]`` on the EWMA backlog
    signal. With ``--fleet_lease`` the router is role-fenced;
    ``--standby`` runs the warm-standby side instead (bound frontend,
    watching ``--peer``, adopting the fleet on the active's death)."""
    import subprocess

    from paddle_tpu.dist.master import FileStore, RoleLease
    from paddle_tpu.serving import (Autoscaler, ReplicaRouter,
                                    ReplicaSupervisor, RouterHA,
                                    serve_router_forever)
    from paddle_tpu.serving.supervisor import free_port

    min_r = max(1, args.min_replicas)
    max_r = args.max_replicas if args.max_replicas else min_r
    lease = None
    if args.fleet_lease:
        holder = f"{'standby' if args.standby else 'active'}-{os.getpid()}"
        lease = RoleLease(FileStore(args.fleet_lease), holder,
                          ttl_s=args.lease_timeout_s)
    elif args.standby:
        raise SystemExit("--standby needs --fleet_lease (the shared "
                         "role-election record both routers read)")

    if args.standby:
        if not args.peer:
            raise SystemExit("--standby needs --peer host:port (the "
                             "active router frontend to watch)")
        host, _, port = str(args.peer).rpartition(":")
        router = ReplicaRouter([], fence=lease)
        ha = RouterHA(router, lease,
                      peer=(host or "127.0.0.1", int(port)),
                      interval_ms=max(100.0,
                                      args.lease_timeout_s * 1e3 / 4))
        ha.start()
        try:
            return serve_router_forever(router, host=args.host,
                                        port=args.port)
        finally:
            ha.shutdown()

    def spawn(replica_id):
        port = free_port(args.host)
        proc = subprocess.Popen(_replica_cmd(args, port))
        return proc, args.host, port

    supervisor = ReplicaSupervisor(
        spawn, replicas=min_r, lease_timeout_s=args.lease_timeout_s,
        poll_ms=max(100.0, args.lease_timeout_s * 1e3 / 4))
    transports = supervisor.start(wait_ready_s=600.0)
    router = ReplicaRouter(transports, spawn=None, fence=lease,
                           hedge_ms=(args.hedge_ms or None),
                           metrics=supervisor.metrics)
    supervisor.attach_router(router)
    supervisor.start_monitor()
    ha = None
    if lease is not None:
        ha = RouterHA(router, lease,
                      interval_ms=max(100.0,
                                      args.lease_timeout_s * 1e3 / 4))
        ha.start(take_role=True)
    scaler = None
    if max_r > min_r:
        scaler = Autoscaler(
            supervisor, min_replicas=min_r, max_replicas=max_r,
            up_backlog_ms=args.autoscale_up_backlog_ms,
            down_backlog_ms=args.autoscale_down_backlog_ms).start()
    # metrics federation: the router frontend's /metrics additionally
    # carries the supervisor's replica table (+ the autoscale
    # trajectory) so ONE scrape shows the whole self-operating fleet
    from paddle_tpu.obs import MetricsRegistry
    registry = MetricsRegistry().register("supervisor",
                                          supervisor.snapshot)
    if scaler is not None:
        registry.register(
            "autoscaler",
            lambda: {"replicas": supervisor.replica_count(),
                     "ewma_backlog_ms": scaler.ewma,
                     "trajectory": [list(p) for p in
                                    scaler.trajectory[-64:]]})
    try:
        return serve_router_forever(router, host=args.host,
                                    port=args.port, registry=registry)
    finally:
        if scaler is not None:
            scaler.stop()
        if ha is not None:
            ha.shutdown()
        supervisor.shutdown(drain=True)


def build_serve_train_loop(ns, args, *, start_fleet=True):
    """The --job=serve_train wiring, reusable by bench/tests: returns
    ``(loop, router, writer)`` — a ready :class:`ServeTrainLoop`, the
    serving fleet fronting the published artifact (None when
    ``start_fleet=False``: the trainer-only mode), and the replay
    writer the engines append through.

    The loop closes over ONE trainer; the fleet never serves live
    trainer params — replicas are always built from a published PTM1
    artifact (v0 is merged before the first replica warms), so the
    running model is exactly the artifact its ``model_hash`` pins and a
    reload is a weight-only swap against an unchanged AOT menu."""
    from paddle_tpu.online import (ModelPublisher, ReplayTailer,
                                   ReplayWriter, ServeTrainLoop)
    if not args.replay_dir:
        raise SystemExit("--job=serve_train needs --replay_dir")
    graph, _params, names, feeding, pk, ek = _serving_plan(ns, args)
    del graph
    trainer = _build_trainer(ns, args)
    if not args.init_model_path and args.save_dir:
        from paddle_tpu.dist.checkpoint import Checkpointer
        restored = Checkpointer(args.save_dir).restore()
        if restored:
            trainer.load_state(restored[0], restored[1])
    publish_dir = args.publish_dir or os.path.join(args.replay_dir,
                                                   "published")
    writer = ReplayWriter(args.replay_dir,
                          segment_records=args.replay_segment_records,
                          schema=list(feeding))
    ek = dict(ek, replay_sink=writer)

    def make_engine(model_path):
        from paddle_tpu.serving import ServingEngine, ServingPredictor
        pred = ServingPredictor.from_merged(model_path, feeding, **pk)
        return ServingEngine(pred, **ek).start(warmup=True)

    def build_transport(model_path, rid):
        from paddle_tpu.serving import EngineTransport
        return EngineTransport(make_engine(model_path))

    publisher = ModelPublisher(
        trainer, model_dir=publish_dir, outputs=names,
        build_transport=build_transport,
        every_batches=args.publish_every,
        quantize=getattr(args, "quantize", None), feeding=feeding)
    router = None
    if start_fleet:
        from paddle_tpu.serving import EngineTransport, ReplicaRouter
        publisher.publish()  # v0: the fleet's starting artifact
        transports = [EngineTransport(make_engine(publisher.last_good))
                      for _ in range(max(1, args.replicas))]
        router = ReplicaRouter(
            transports,
            spawn=lambda rid: EngineTransport(
                make_engine(publisher.last_good)),
            hedge_ms=(args.hedge_ms or None))
        publisher.router = router

    ck = None
    if args.save_dir:
        from paddle_tpu.dist.checkpoint import Checkpointer
        ck = Checkpointer(
            args.save_dir, saving_period=args.saving_period,
            saving_period_by_batches=(args.saving_period_by_batches
                                      or 20),
            background=getattr(args, "background_save", True))
    tailer = ReplayTailer(args.replay_dir,
                          batch_rows=args.replay_batch_rows)
    # the divergence sentry is armed BY DEFAULT in-loop: an unattended
    # trainer fed by live traffic must not publish a poisoned update
    # (skip_batch discards it in-graph; flags tighten/loosen as in
    # --job=train)
    health = {
        "sentry": True,
        "grad_threshold": getattr(args, "error_clipping_threshold", 0.0),
        "policy": getattr(args, "divergence_policy", "skip_batch"),
        "log_clipping": getattr(args, "log_error_clipping", False),
        "log_path": getattr(args, "health_log", None),
    }
    loop = ServeTrainLoop(
        trainer, tailer=tailer, publisher=publisher, feeder=_feeder(ns),
        writer=writer, checkpointer=ck, health=health,
        max_batches=(args.serve_train_batches or None),
        log_period=args.log_period)
    return loop, router, writer


def cmd_serve_train(ns, args):
    """``--job=serve_train``: one supervised process group closing
    serving→training→publish→serving. The fleet serves (and its HTTP
    frontend binds) while the main thread trains the replay stream; on
    the batch budget (or SIGTERM) the stream closes, the reader drains,
    and the trainer unwinds through its end-of-pass commit."""
    import threading

    from paddle_tpu.serving.router import (
        install_router_signal_handlers, make_router_server)
    loop, router, writer = build_serve_train_loop(ns, args)
    router.start()
    server = make_router_server(router, args.host, args.port)
    install_router_signal_handlers(router, server)
    print(f"serve_train: router on http://{args.host}:"
          f"{server.server_address[1]}, publishing every "
          f"{args.publish_every} batches", flush=True)
    frontend = threading.Thread(target=server.serve_forever,
                                kwargs={"poll_interval": 0.2},
                                name="serve-train-frontend", daemon=True)
    frontend.start()
    try:
        loop.run()
    finally:
        loop.stop()
        server.shutdown()
        server.server_close()
        router.shutdown(drain=True)
        writer.close()
    print(f"serve_train: {loop.batches_trained} batches trained, "
          f"{loop.publisher.publishes_total} publishes "
          f"({loop.publisher.rollbacks_total} rollbacks)", flush=True)
    return 0


def cmd_serve(ns, args):
    if getattr(args, "replicas", 1) > 1:
        from paddle_tpu.serving import serve_router_forever
        router, reload_builder = build_serving_fleet(ns, args)
        return serve_router_forever(
            router, host=args.host, port=args.port,
            reload_builder=reload_builder,
            model_path=getattr(args, "model_path", None))
    from paddle_tpu.serving import serve_forever
    engine = build_serving_engine(ns, args)
    recorder = controller = None
    if getattr(args, "workload_record", None):
        from paddle_tpu.serving.workload import WorkloadRecorder
        recorder = WorkloadRecorder()
        engine.workload_recorder = recorder
    if getattr(args, "slo_p99_ms", 0):
        from paddle_tpu.serving.tuner import (SLOController, SLOTarget,
                                              engine_signal)
        controller = SLOController(
            engine,
            SLOTarget(p99_ms=args.slo_p99_ms,
                      max_shed_rate=args.slo_max_shed_rate),
            signal=engine_signal(engine),
            timeout_ms=args.batch_timeout_ms,
            timeout_lo_ms=min(0.5, args.batch_timeout_ms),
            timeout_hi_ms=max(50.0, args.batch_timeout_ms),
            max_batch=args.max_batch).start()
    try:
        return serve_forever(engine, host=args.host, port=args.port)
    finally:
        if controller is not None:
            controller.stop()
        if recorder is not None:
            engine.workload_recorder = None
            recorder.snapshot(
                os.path.splitext(os.path.basename(
                    args.workload_record))[0]).save(args.workload_record)


def main(argv=None):
    args = parse_args(argv)
    # deterministic fault injection (tools/chaos_soak.py arms children
    # through the env); a no-op unless PADDLE_TPU_CHAOS_PLAN is set
    from paddle_tpu.testing import chaos as _chaos
    _chaos.install_from_env()
    # observability plane (a no-op unless $PADDLE_TPU_TRACE_DIR /
    # $PADDLE_TPU_FLIGHT_DIR are set): spans + flight events dump at
    # exit, tagged with this process's job kind so tools/blackbox.py
    # can merge a whole fleet's dumps into one named timeline
    from paddle_tpu import obs
    obs.arm_from_env(args.job)
    if args.job != "serve_fleet":
        # place the compile cache, log platform/kind/count, and refuse a
        # silent CPU fallback. The serve_fleet supervisor stays OFF JAX:
        # a chip belongs to one process at a time, and a parent that
        # touched it would lock every --job=serve child out (each child
        # comes through here and reports its own device)
        from paddle_tpu.utils import runtime
        runtime.start(f"--job={args.job}")
    if getattr(args, "fp_anomaly", False):
        from paddle_tpu.utils.fp import enable_fp_anomaly
        enable_fp_anomaly()
    ns = load_config(args.config, args.config_args)
    return {"train": cmd_train, "test": cmd_test, "time": cmd_time,
            "checkgrad": cmd_checkgrad, "merge": cmd_merge,
            "serve": cmd_serve, "serve_fleet": cmd_serve_fleet,
            "serve_train": cmd_serve_train}[args.job](ns, args)


if __name__ == "__main__":
    sys.exit(main())
