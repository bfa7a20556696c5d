"""Chip smoke: the train-and-serve path, once, on the TPU, in one process.

    python chip_smoke.py          # on the chip (through the chip tool)

Drives the system's main path through the entry points a user calls, at
the full width of the headline model (``models.lstm_text_classifier``,
vocab 30000, embed 128, hidden 256, two layers, batch 64, sequence 100,
Adam), with random weights and a synthetic task both made from a seed:

1. train: config DSL -> ``SGD.train`` with a reader + ``DataFeeder`` on
   the prefetch thread, ``RecompileGuard`` and events armed; the loss
   must be finite and lower at the end than at the start, and the
   compiled step's HLO must hold Mosaic custom calls (the Pallas LSTM
   went through the compiler, not through ``ref`` or ``interpret``);
2. serve: the trained parameters merged to a ``.ptmodel``, loaded by
   ``ServingPredictor.from_merged`` behind ``ServingEngine`` and
   ``make_server`` on a free port; ``ServingClient`` scores batches of
   one and four, the same sample twice (byte-equal), and one answer is
   checked against the scan-reference forward; ``healthz`` reports no
   ``fatal``, ``metrics`` counted the requests, and the engine drains;
3. with four or more devices, the train phase again on a
   ``create_mesh(n_data=4)`` mesh: the kernels' operands in the compiled
   HLO carry the per-device batch, the gradient all-reduce is there, and
   the loss follows the one-chip run.

Any failed phase is a non-zero exit; there is no CPU fallback and no
``try/except`` that prints and carries on. Off the chip, or with a
kernel-forcing environment set, it refuses to start. The last line of
standard output is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it. ``tests/test_chip_smoke.py`` runs the same phases on the
CPU at a tiny width with the kernels interpreted, so the script cannot
rot between chip runs.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import tempfile
import threading
import time

import numpy as np

SEED = 20260926
# allowed |loss difference| per step between the mesh and one-chip runs
# (same data, same seed; f32 reduction order differs across devices)
MESH_LOSS_TOL = 2e-2
# served softmax vs the scan-reference forward at batch 1. The kernel
# multiplies at the chip's default f32 matmul precision; XLA computes
# the reference's matrix-vector product at full f32, so the two differ
# there (TPU_EVIDENCE.json "precision", PERF.md) and agree bitwise from
# batch 2 up. A smoke check of the serving stack, not of kernel
# precision: that evidence is tools/tpu_evidence.py's.
REFERENCE_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Width:
    vocab: int = 30000
    embed: int = 128
    hidden: int = 256
    layers: int = 2
    batch: int = 64
    seqlen: int = 100
    batches: int = 4      # distinct batches, cycled every pass
    passes: int = 4
    pool: int = 48        # token ids that carry each class


FULL = Width()


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_task(w: Width, seed: int = SEED):
    """A learnable synthetic corpus from a seed: each class draws its
    tokens from its own small pool of ids spread over the whole
    vocabulary, so a few Adam steps separate them. Returns
    ``(batches, feeding)``."""
    from paddle_tpu.data import integer_value, integer_value_sequence
    rng = np.random.RandomState(seed)
    ids = rng.choice(w.vocab, size=2 * w.pool, replace=False)
    pools = (ids[:w.pool], ids[w.pool:])
    batches = []
    for _ in range(w.batches):
        rows = []
        for _ in range(w.batch):
            label = int(rng.randint(0, 2))
            rows.append((rng.choice(pools[label], size=w.seqlen).tolist(),
                         label))
        batches.append(rows)
    feeding = {"words": integer_value_sequence(w.vocab),
               "label": integer_value(2)}
    return batches, feeding


def _mosaic_calls(hlo: str):
    """``(count, shapes)`` of the Mosaic custom calls in a compiled
    module's text; ``shapes`` lists, per call, the operand shapes from
    its ``operand_layout_constraints``."""
    lines = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    shapes = []
    for ln in lines:
        m = re.search(r"operand_layout_constraints=\{(.*?)\}\}", ln)
        shapes.append(re.findall(r"(\w+\[[\d,]*\])", m.group(1))
                      if m else [])
    return len(lines), shapes


def _memory(device):
    """``memory_stats()`` of one device, cut to the two figures worth
    printing (None where the backend reports nothing, as XLA:CPU)."""
    stats = device.memory_stats()
    return stats and {k: stats.get(k)
                      for k in ("bytes_in_use", "peak_bytes_in_use")}


def train_phase(w: Width, *, mesh=None, expect_mosaic: bool = True,
                seed: int = SEED):
    """Build the LSTM classifier, compile its train step ahead of time
    (to read the HLO and time the compile apart from the steps), then
    train through ``SGD.train``. Returns a report dict that includes the
    trainer (``"trainer"``) and per-step costs (``"costs"``)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.config import dsl
    from paddle_tpu.data import DataFeeder
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.ops import common
    from paddle_tpu.ops.lstm import lstm_dispatch
    from paddle_tpu.optim import Adam
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.trainer import SGD, events

    batches, feeding = make_task(w, seed)
    dsl.reset()
    cost, _out, _ = lstm_text_classifier(
        vocab_size=w.vocab, embed_dim=w.embed, hidden=w.hidden,
        num_layers=w.layers, classes=2)
    graph = dsl.current_graph()
    trainer = SGD(cost=cost, update_equation=Adam(learning_rate=2e-3),
                  mesh=mesh, seed=seed % 1000)
    feeder = DataFeeder(feeding, pad_multiple=w.seqlen)

    # ahead-of-time compile of the very step train() will run: which
    # path each kernel took (trace-time tally), how long the compiler
    # needed, and what it produced
    feed = feeder(batches[0])
    if mesh is not None:
        feed = mesh_lib.shard_batch(feed, mesh)
    with common.record_dispatch() as tally:
        lowered = trainer._train_step.lower(
            trainer.params, trainer.opt_state, feed,
            jax.random.PRNGKey(0), jnp.int32(0), None)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    n_mosaic, operand_shapes = _mosaic_calls(hlo)
    with common.step_mesh(mesh):
        split = common.batch_split(w.batch)
    local_batch = w.batch // max(split, 1)
    report = {
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "kernel_mode": common.mode(),
        "lstm_dispatch": lstm_dispatch(local_batch, w.hidden),
        "dispatch_tally": tally,
        "tpu_custom_calls": n_mosaic,
        "custom_call_operands": operand_shapes,
        "all_reduce_in_hlo": "all-reduce" in hlo,
        "all_gather_in_hlo": "all-gather" in hlo,
        "compile_s": round(compile_s, 2),
    }
    if expect_mosaic:
        if n_mosaic < 1:
            raise AssertionError(
                "the compiled train step holds no tpu_custom_call: the "
                f"kernels did not go through Mosaic (tally {tally})")
        if report["lstm_dispatch"] != "resident" or \
                tally.get("lstm") != {"resident": w.layers}:
            raise AssertionError(f"LSTM left the resident kernel: {report}")
    if split > 1 and expect_mosaic:
        # the kernels must see the per-device batch, not the gathered one
        local = f"{w.seqlen},{local_batch},"
        gathered = f"{w.seqlen},{w.batch},"
        flat = [s for call in operand_shapes for s in call]
        if not any(local in s for s in flat) or \
                any(gathered in s for s in flat):
            raise AssertionError(
                "a kernel is fed the gathered batch on the mesh: "
                f"{operand_shapes}")
        if not report["all_reduce_in_hlo"]:
            raise AssertionError("no gradient all-reduce in the mesh HLO")

    steps = []  # (cost, seconds) per EndIteration
    began = [0.0]

    def on_event(e):
        if isinstance(e, events.BeginIteration):
            began[0] = time.perf_counter()
        elif isinstance(e, events.EndIteration):
            steps.append((float(e.cost), time.perf_counter() - began[0]))

    trainer.train(lambda: iter(batches), feeder=feeder,
                  num_passes=w.passes, event_handler=on_event,
                  async_load_data=True)
    costs = [c for c, _ in steps]
    if len(costs) != w.batches * w.passes:
        raise AssertionError(f"expected {w.batches * w.passes} steps, "
                             f"saw {len(costs)}")
    if not all(np.isfinite(costs)):
        raise AssertionError(f"non-finite loss: {costs}")
    head, tail = np.mean(costs[:w.batches]), np.mean(costs[-w.batches:])
    if not tail < head:
        raise AssertionError(
            f"loss did not fall: first pass {head:.4f}, last {tail:.4f}")
    guard = trainer.recompile_guard.count
    if guard != 1:
        raise AssertionError(f"train step compiled {guard} variants")
    report.update({
        "steps": len(costs),
        "loss_first_pass": round(float(head), 4),
        "loss_last_pass": round(float(tail), 4),
        "first_step_s": round(steps[0][1], 3),
        "steady_step_s_median": round(
            float(np.median([s for _, s in steps[1:]])), 4),
        "recompile_guard_count": guard,
        "device_memory": [_memory(d) for d in (
            mesh.devices.flat if mesh is not None else jax.devices()[:1])],
    })
    shown = {k: v for k, v in report.items() if k != "custom_call_operands"}
    say("train " + json.dumps(shown))
    say("train custom-call operands " + json.dumps(operand_shapes))
    report.update(trainer=trainer, graph=graph, costs=costs,
                  feeding=feeding, batches=batches)
    return report


def serve_phase(w: Width, trained, *, batch_buckets=(1, 2, 4)):
    """Merge the trained parameters to a ``.ptmodel``, serve it over
    HTTP, score it, check it, drain it."""
    import jax

    from paddle_tpu.core.network import Network
    from paddle_tpu.data import DataFeeder
    from paddle_tpu.ops import common
    from paddle_tpu.serving import (ServingClient, ServingEngine,
                                    ServingPredictor, make_server)
    from paddle_tpu.trainer.merge_model import merge_model

    trainer, graph = trained["trainer"], trained["graph"]
    feeding, batches = trained["feeding"], trained["batches"]
    rows = [(ids, label) for ids, label in batches[0][:4]]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = os.path.join(tmp, "model.ptmodel")
        merge_model(path, graph, trainer._params_for_save(),
                    outputs=["output"])
        pred = ServingPredictor.from_merged(
            path, feeding, batch_buckets=list(batch_buckets),
            length_buckets=[w.seqlen])
    t0 = time.perf_counter()
    engine = ServingEngine(pred, max_batch=max(batch_buckets),
                           batch_timeout_ms=20.0,
                           queue_depth=32).start(warmup=True)
    warmup_s = time.perf_counter() - t0
    server = make_server(engine, port=0)
    thread = threading.Thread(target=server.serve_forever,
                              kwargs={"poll_interval": 0.05},
                              daemon=True, name="chip-smoke-http")
    thread.start()
    try:
        client = ServingClient(port=server.server_address[1])
        one = client.score(rows[0])
        again = client.score(rows[0])
        if json.dumps(one["outputs"], sort_keys=True) != \
                json.dumps(again["outputs"], sort_keys=True):
            raise AssertionError("the same sample scored twice differs: "
                                 f"{one['outputs']} vs {again['outputs']}")
        four = client.score_rows(rows)
        answers = [one] + four
        for a in answers:
            probs = np.asarray(a["outputs"]["output"], np.float64)
            if probs.shape != (2,) or not np.all(np.isfinite(probs)) \
                    or abs(probs.sum() - 1.0) > 1e-3:
                raise AssertionError(f"bad served answer: {a}")
        # the repo's own reference: the same forward with every kernel
        # on its lax.scan spelling, outside the serving stack
        net = Network(graph, outputs=["output"])
        feed = DataFeeder(feeding, pad_multiple=w.seqlen)(rows[:1])
        with common.force_mode("ref"):
            ref = jax.jit(lambda p, f: net.apply(
                p, f, train=False)["output"].value)(
                    dict(trainer._flat_params_view()), feed)
        ref = np.asarray(ref)[0]
        got = np.asarray(one["outputs"]["output"])
        if np.max(np.abs(ref - got)) > REFERENCE_TOL:
            raise AssertionError(
                f"served {got} disagrees with the reference {ref}")
        health = client.healthz()
        if health.get("fatal") or not health.get("ready"):
            raise AssertionError(f"unhealthy engine: {health}")
        metrics = client.metrics()
        hits = metrics["bucket_hits"]
        if metrics["responses_total"] < 6 or \
                not any(k.startswith("b1_") for k in hits) or \
                not any(k.startswith("b4_") for k in hits):
            raise AssertionError(f"metrics missed the requests: {metrics}")
        for g in pred.guards:
            g.check()
        report = {
            "warmup_s": round(warmup_s, 2),
            "buckets": {"batch": pred.batch_buckets,
                        "length": pred.length_buckets},
            "answered": len(answers) + 1,
            "repeat_byte_equal": True,
            "max_abs_vs_reference": float(np.max(np.abs(ref - got))),
            "bucket_hits": hits,
            "responses_total": metrics["responses_total"],
            "fatal": health.get("fatal"),
            "model_version": health.get("model_version"),
        }
    finally:
        server.shutdown()
        server.server_close()
        engine.shutdown(drain=True)
        thread.join(timeout=10.0)
    if thread.is_alive() or engine.health()["live"] is not True:
        raise AssertionError("the server did not drain cleanly")
    say("serve " + json.dumps(report))
    return report


def mesh_phase(w: Width, one_chip, *, n_data: int = 4,
               expect_mosaic: bool = True):
    """The train phase again on an ``n_data``-way data-parallel mesh;
    its loss must follow the one-chip run's."""
    import jax

    from paddle_tpu.parallel import create_mesh
    mesh = create_mesh(n_data=n_data, devices=jax.devices()[:n_data])
    report = train_phase(w, mesh=mesh, expect_mosaic=expect_mosaic)
    diff = float(np.max(np.abs(np.asarray(report["costs"])
                               - np.asarray(one_chip["costs"]))))
    if diff > MESH_LOSS_TOL:
        raise AssertionError(
            f"the mesh run's loss left the one-chip run's by {diff:.4f}: "
            f"{report['costs']} vs {one_chip['costs']}")
    say(f"mesh loss follows the one-chip run (max |diff| {diff:.2e})")
    return report


def main() -> int:
    # the package is imported here, not at module import, so a directory
    # that holds only this file fails on the first line below
    from paddle_tpu import native
    from paddle_tpu.ops import common
    from paddle_tpu.utils import runtime

    if common.forced() is not None:
        raise SystemExit(
            f"chip_smoke: {common.FORCE_ENV}={common.forced()!r} forces "
            "the kernel path; a chip smoke must see the path the "
            "platform selects — unset it")
    device = runtime.require_tpu("chip_smoke")
    cache_dir = runtime.compile_cache_dir()[0]
    import jax
    import jaxlib
    say(f"device {json.dumps(device)} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} compile_cache_dir {cache_dir} "
        f"native.available {native.available()}")
    with runtime.CacheCounter() as cache:
        trained = train_phase(FULL)
        serve_phase(FULL, trained)
        if device["count"] >= 4:
            mesh_phase(FULL, trained)
        else:
            say(f"mesh phase NOT run: {device['count']} device(s), "
                "needs 4")
    # hits > 0 on a second run in the same checkout (only compiles of a
    # second or more are kept)
    say(f"compile cache {json.dumps(cache.snapshot())} in {cache_dir}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
