#!/usr/bin/env python
"""Randomized (seeded) multi-process chaos soak over the elastic
training stack — the acceptance drill for docs/fault_tolerance.md:

    python tools/chaos_soak.py --seed 7 --events 4 --workdir /tmp/soak

One standalone MASTER process (``python -m paddle_tpu.dist.master``,
FileStore snapshot) feeds one WORKER process (this script, ``--role
worker``) training a deterministic model through ``master_reader`` with
background checkpointing and ``--auto_resume`` semantics. A seeded
schedule then commits crimes:

- ``kill_worker``  — SIGKILL the trainer at a random moment
- ``kill_master``  — SIGKILL the master; restart it (same port, same
                     snapshot); the worker's client redials
- ``corrupt``      — truncate the newest checkpoint generation on disk
- ``plan_kill``    — re-arm the worker's env FaultPlan to die AT a
                     specific step (deterministic in-process exit)

plus a standing low-rate message-drop/delay FaultPlan in the worker's
env (``PADDLE_TPU_CHAOS_PLAN``). Dead processes are restarted with
zero manual intervention until the worker completes its pass budget.

The PASS bar: the chaos run's final parameters are BITWISE equal to a
clean run's (same seed, no faults) — exact resume + lease-based task
recovery + commit-after-durable-checkpoint mean no kill timing, master
death, corruption or message loss may perturb the trajectory. Exits 0
on equality; prints one JSON line either way.

Tier-1 keeps the fast in-process chaos subset (tests/test_chaos.py);
this soak runs as tests/test_chaos_soak.py, marked ``slow`` + ``chaos``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time

# ---------------------------------------------------------------- model
# (worker-role imports of jax/paddle_tpu happen inside worker_main so
# the controller stays import-light)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WIDTH, CLASSES, B = 8, 3, 8


def _child_env(extra=None):
    """Env for spawned children: repo root on PYTHONPATH (running this
    file by path puts ``tools/`` on sys.path, not the repo)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if extra:
        env.update(extra)
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker_main(args) -> int:
    os.environ.setdefault("XLA_FLAGS", "")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    import jax.numpy as jnp

    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.dist.checkpoint import Checkpointer
    from paddle_tpu.dist.master import MasterClient, master_reader
    from paddle_tpu.optim import Adam
    from paddle_tpu.testing import chaos
    from paddle_tpu.trainer import SGD

    chaos.install_from_env()

    done_marker = os.path.join(args.workdir, "DONE")
    if os.path.exists(done_marker):
        return 0

    rng = np.random.RandomState(args.seed)
    X = rng.randn(args.batches * B, WIDTH).astype(np.float32)
    W = rng.randn(WIDTH, CLASSES)
    Y = np.argmax(X @ W, axis=1).astype(np.int32)
    feeds = [{"x": Argument(value=jnp.asarray(X[i:i + B])),
              "label": Argument(value=jnp.asarray(Y[i:i + B]))}
             for i in range(0, args.batches * B, B)]

    dsl.reset()
    x = dsl.data(name="x", size=WIDTH)
    lbl = dsl.data(name="label", size=CLASSES)
    h = dsl.fc(input=x, size=WIDTH, act="tanh")
    h = dsl.dropout(input=h, rate=0.25)
    out = dsl.fc(input=h, size=CLASSES, act="softmax")
    cost = dsl.classification_cost(input=out, label=lbl)
    trainer = SGD(cost=cost, update_equation=Adam(learning_rate=3e-3),
                  seed=args.seed)

    host, _, port = args.master.rpartition(":")
    client = MasterClient((host, int(port)), trainer_id="trainer-0",
                          retries=200, retry_delay=0.02, backoff_cap=0.5,
                          heartbeat_s=0.5)
    client.set_dataset(list(range(args.batches)))

    def load_chunk(i):
        yield feeds[int(i)]

    reader = master_reader(client, load_chunk)
    ck = Checkpointer(os.path.join(args.workdir, "ckpt"),
                      saving_period=1, saving_period_by_batches=2,
                      background=True)
    trainer.train(reader, num_passes=args.passes, checkpointer=ck)

    params = {k: np.asarray(jax.device_get(v))
              for k, v in trainer._params_for_save().items()}
    tmp = args.out + ".tmp.npz"  # savez appends .npz to bare names
    np.savez(tmp, **params)
    os.replace(tmp, args.out)
    with open(done_marker, "w") as f:
        f.write("ok")
    client.close()
    return 0


# ----------------------------------------------------------- controller

class _Procs:
    def __init__(self):
        self.master = None
        self.worker = None

    def kill_all(self):
        for p in (self.master, self.worker):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()


def _spawn_master(port, store, log):
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.dist.master",
         "--port", str(port), "--store", store,
         "--timeout_s", "10", "--failure_max", "1000"],
        env=_child_env(), stdout=log, stderr=log)


def _spawn_worker(args, port, workdir, out, plan_json, log):
    env = _child_env({"PADDLE_TPU_MASTER": f"127.0.0.1:{port}"})
    if plan_json:
        env["PADDLE_TPU_CHAOS_PLAN"] = plan_json
    else:
        env.pop("PADDLE_TPU_CHAOS_PLAN", None)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--role", "worker",
         "--seed", str(args.seed), "--passes", str(args.passes),
         "--batches", str(args.batches), "--workdir", workdir,
         "--master", f"127.0.0.1:{port}", "--out", out],
        env=env, stdout=log, stderr=log)


def _run_to_completion(args, tag, chaos_events, log_path):
    """One full job (master + worker [+ scheduled faults]) to DONE;
    returns the final-params path."""
    workdir = os.path.join(args.workdir, tag)
    os.makedirs(workdir, exist_ok=True)
    out = os.path.join(workdir, "final_params.npz")
    store = os.path.join(workdir, "master.snap")
    port = _free_port()
    schedule = random.Random(args.seed * 7919 + (1 if chaos_events else 0))
    base_plan = None
    if chaos_events:
        base_plan = json.dumps({"seed": args.seed, "faults": [
            {"type": "drop", "site": "msg_recv", "rate": 0.03},
            {"type": "delay", "site": "msg_send", "every": 13,
             "seconds": 0.005}]})
    procs = _Procs()
    events = []
    deadline = time.monotonic() + args.timeout
    log = open(log_path, "ab")
    try:
        procs.master = _spawn_master(port, store, log)
        procs.worker = _spawn_worker(args, port, workdir, out, base_plan,
                                     log)
        remaining = list(chaos_events)
        while True:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{tag}: soak did not converge within {args.timeout}s "
                    f"(events run: {events})")
            rc = procs.worker.poll()
            if rc == 0 and os.path.exists(os.path.join(workdir, "DONE")):
                break
            if rc is not None:
                # the worker died (SIGKILL'd, plan-killed, or crashed):
                # restart it — auto-resume, zero manual intervention.
                # A fresh incarnation gets a clean plan (a plan_kill
                # must fire once, not once per life).
                events.append(f"worker_exit:{rc}")
                procs.worker = _spawn_worker(args, port, workdir, out,
                                             base_plan, log)
            if procs.master.poll() is not None:
                events.append("master_exit")
                procs.master = _spawn_master(port, store, log)
            if remaining:
                time.sleep(schedule.uniform(0.5, 1.5))
                action = remaining.pop(0)
                events.append(action)
                if action == "kill_worker":
                    if procs.worker.poll() is None:
                        procs.worker.send_signal(signal.SIGKILL)
                elif action == "kill_master":
                    if procs.master.poll() is None:
                        procs.master.send_signal(signal.SIGKILL)
                elif action == "corrupt":
                    ckdir = os.path.join(workdir, "ckpt")
                    if os.path.isdir(ckdir):
                        npzs = sorted(n for n in os.listdir(ckdir)
                                      if n.endswith(".npz"))
                        if npzs:
                            victim = os.path.join(ckdir, npzs[-1])
                            try:
                                size = os.path.getsize(victim)
                                with open(victim, "r+b") as f:
                                    f.truncate(max(1, size // 2))
                            except OSError:
                                pass
                elif action == "plan_kill":
                    # deterministic in-process death: restart the worker
                    # with a plan killing it N steps into its life
                    if procs.worker.poll() is None:
                        procs.worker.kill()
                        procs.worker.wait()
                    k = schedule.randint(1, max(2, args.batches))
                    plan = json.dumps({"seed": args.seed, "faults": [
                        {"type": "kill", "site": "step_done", "at": k,
                         "mode": "exit"}]})
                    procs.worker = _spawn_worker(args, port, workdir, out,
                                                 plan, log)
            else:
                time.sleep(0.25)
        return out, events
    finally:
        procs.kill_all()
        log.close()


def controller_main(args) -> int:
    import numpy as np

    os.makedirs(args.workdir, exist_ok=True)
    log_path = os.path.join(args.workdir, "soak.log")
    t0 = time.time()
    clean_out, _ = _run_to_completion(args, "clean", [], log_path)

    rng = random.Random(args.seed)
    actions = ["kill_worker", "kill_master", "corrupt", "plan_kill"]
    # every action class appears; order seeded
    chaos_events = list(actions)
    while len(chaos_events) < args.events:
        chaos_events.append(rng.choice(actions))
    rng.shuffle(chaos_events)
    chaos_events = chaos_events[:max(args.events, 1)]

    chaos_out, events = _run_to_completion(args, "chaos", chaos_events,
                                           log_path)

    clean = np.load(clean_out)
    chaotic = np.load(chaos_out)
    mismatches = []
    if sorted(clean.files) != sorted(chaotic.files):
        mismatches.append("param-set differs")
    else:
        for k in clean.files:
            if not np.array_equal(clean[k], chaotic[k]):
                mismatches.append(k)
    result = {
        "soak": "chaos",
        "seed": args.seed,
        "passes": args.passes,
        "batches": args.batches,
        "events": events,
        "bitwise_equal": not mismatches,
        "mismatches": mismatches,
        "wall_s": round(time.time() - t0, 1),
    }
    print(json.dumps(result), flush=True)
    return 0 if not mismatches else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--role", choices=["controller", "worker"],
                    default="controller")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batches", type=int, default=6)
    ap.add_argument("--events", type=int, default=4,
                    help="chaos actions in the seeded schedule")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-run convergence budget (seconds)")
    ap.add_argument("--workdir", default="/tmp/paddle_tpu_chaos_soak")
    ap.add_argument("--master", default="",
                    help="(worker) master host:port")
    ap.add_argument("--out", default="",
                    help="(worker) final-params npz path")
    args = ap.parse_args(argv)
    if args.role == "worker":
        return worker_main(args)
    return controller_main(args)


if __name__ == "__main__":
    sys.exit(main())
