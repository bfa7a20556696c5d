"""Multi-host job launcher.

Plays ``paddle/scripts/cluster_train/paddle.py:63-157``: the reference
fabric script copies the job dir to every node and starts pservers +
trainers with the right ``--trainer_id``/``--pserver`` wiring. The TPU
equivalent starts one worker process per host wired with:

- the JAX **coordinator address** (process 0) + process count/index —
  what ``jax.distributed.initialize`` needs to form a multi-host SPMD
  job over ICI/DCN (the pserver endpoints' role);
- the **master endpoint** — the fault-tolerant task-dispatch service
  (dist/master.py, the Go master's role) feeding every worker's input
  pipeline.

Local mode (``launch_local``) spawns N processes on this machine — the
in-proc-pserver trick of ``test_TrainerOnePass.cpp:246-251`` at launcher
granularity — and is how the launcher is tested without a cluster.
Multi-host mode emits per-host commands (``build_host_commands``) with
the same environment contract; run them under ssh/k8s/gcloud.

Worker-side: ``init_from_env()`` reads the contract and (on real
multi-host TPU) calls ``jax.distributed.initialize``.

Environment contract (all set by the launcher):
  PADDLE_TPU_NUM_PROCESSES / PADDLE_TPU_PROCESS_ID
  PADDLE_TPU_COORDINATOR   host:port of process 0 (jax coordinator)
  PADDLE_TPU_MASTER        host:port of the task master ("" = none)
  PADDLE_TPU_DISTRIBUTED   "1" => init_from_env calls
                           jax.distributed.initialize (real pods; unset
                           for local CPU testing)
"""

from __future__ import annotations

import dataclasses
import os
import shlex
import socket
import subprocess
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass
class LaunchContext:
    """What a launched worker knows about its job."""

    num_processes: int
    process_id: int
    coordinator: str
    master: str = ""

    @property
    def is_chief(self) -> bool:
        return self.process_id == 0

    def master_client(self, **kw):
        from paddle_tpu.dist.master import MasterClient
        if not self.master:
            raise RuntimeError("this job was launched without a master")
        host, _, port = self.master.rpartition(":")
        return MasterClient((host, int(port)),
                            trainer_id=f"trainer-{self.process_id}", **kw)


def init_from_env() -> LaunchContext:
    """Worker entry: parse the launcher's environment contract; on real
    multi-host accelerators (PADDLE_TPU_DISTRIBUTED=1) also bring up the
    JAX coordination service so pjit spans all hosts."""
    ctx = LaunchContext(
        num_processes=int(os.environ.get("PADDLE_TPU_NUM_PROCESSES", "1")),
        process_id=int(os.environ.get("PADDLE_TPU_PROCESS_ID", "0")),
        coordinator=os.environ.get("PADDLE_TPU_COORDINATOR", ""),
        master=os.environ.get("PADDLE_TPU_MASTER", ""))
    if os.environ.get("PADDLE_TPU_DISTRIBUTED") == "1":
        import jax

        # Gloo CPU collectives ship with jaxlib but are not selected by
        # default — without this, any cross-process computation on the
        # CPU backend dies with "Multiprocess computations aren't
        # implemented on the CPU backend" (the local 2-process launcher
        # test's failure mode). Set unconditionally: it only affects the
        # CPU backend, and probing the platform here would initialize a
        # backend BEFORE distributed.initialize.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=ctx.coordinator,
            num_processes=ctx.num_processes,
            process_id=ctx.process_id)
    return ctx


def _worker_env(base: Dict[str, str], *, nproc: int, pid: int,
                coordinator: str, master: str,
                distributed: bool) -> Dict[str, str]:
    env = dict(base)
    env.update({
        "PADDLE_TPU_NUM_PROCESSES": str(nproc),
        "PADDLE_TPU_PROCESS_ID": str(pid),
        "PADDLE_TPU_COORDINATOR": coordinator,
    })
    if master:
        env["PADDLE_TPU_MASTER"] = master
    else:  # keep an externally-provided endpoint from the caller's env
        env.setdefault("PADDLE_TPU_MASTER", "")
    if distributed:
        env["PADDLE_TPU_DISTRIBUTED"] = "1"
    return env


def launch_local(script: str, nproc: int, *,
                 script_args: Sequence[str] = (),
                 master_chunks: Optional[List[Any]] = None,
                 chunks_per_task: int = 1,
                 env: Optional[Dict[str, str]] = None,
                 timeout: float = 600.0,
                 distributed: bool = False) -> List[int]:
    """Spawn ``nproc`` local worker processes running ``script``; when
    ``master_chunks`` is given, host the task master in this process and
    wire every worker to it. Returns per-process exit codes."""
    from paddle_tpu.dist.master import MasterServer, MasterService
    coordinator = f"127.0.0.1:{_free_port()}"
    server = None
    master_addr = ""
    try:
        if master_chunks is not None:
            service = MasterService(chunks_per_task=chunks_per_task)
            service.set_dataset(list(master_chunks))
            server = MasterServer(service).start()
            master_addr = f"{server.addr[0]}:{server.addr[1]}"
        procs = []
        try:
            for pid in range(nproc):
                wenv = _worker_env(dict(env or os.environ), nproc=nproc,
                                   pid=pid, coordinator=coordinator,
                                   master=master_addr,
                                   distributed=distributed)
                procs.append(subprocess.Popen(
                    [sys.executable, script, *script_args], env=wenv))
        except OSError:
            for p in procs:  # don't orphan the already-spawned workers
                p.kill()
            raise
        # one shared deadline: a wedged fleet costs ONE timeout, not
        # nproc of them
        import time
        deadline = time.monotonic() + timeout
        rcs = []
        for p in procs:
            try:
                rcs.append(p.wait(
                    timeout=max(0.0, deadline - time.monotonic())))
            except subprocess.TimeoutExpired:
                p.kill()
                rcs.append(-9)
        return rcs
    finally:
        if server is not None:
            server.stop()


def build_host_commands(hosts: Sequence[str], script: str, *,
                        script_args: Sequence[str] = (),
                        coordinator_port: int = 8476,
                        master_addr: str = "",
                        distributed: bool = True
                        ) -> List[Tuple[str, str]]:
    """Per-host shell commands carrying the same environment contract —
    what the reference's fabric loop ran over ssh
    (``cluster_train/paddle.py:106-157``). Host 0 is the coordinator."""
    cmds = []
    coordinator = f"{hosts[0]}:{coordinator_port}"
    for pid, host in enumerate(hosts):
        env = _worker_env({}, nproc=len(hosts), pid=pid,
                          coordinator=coordinator, master=master_addr,
                          distributed=distributed)
        exports = " ".join(f"{k}={shlex.quote(v)}"
                           for k, v in sorted(env.items()))
        args = " ".join(shlex.quote(a) for a in (script, *script_args))
        cmds.append((host, f"env {exports} {shlex.quote(sys.executable)} "
                           f"{args}"))
    return cmds


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.dist.launch",
        description="Start a multi-process paddle_tpu job "
                    "(cluster_train/paddle.py role)")
    ap.add_argument("--nproc", type=int, default=1,
                    help="local worker process count")
    ap.add_argument("--hosts", default="",
                    help="comma-separated hosts: print per-host commands "
                         "instead of launching locally")
    ap.add_argument("--master", default="",
                    help="external master endpoint host:port")
    ap.add_argument("--distributed", default=None,
                    action=__import__("argparse").BooleanOptionalAction,
                    help="workers call jax.distributed.initialize "
                         "(default: on for --hosts, off locally)")
    ap.add_argument("script")
    ap.add_argument("script_args", nargs="*")
    args = ap.parse_args(argv)

    if args.hosts:
        for host, cmd in build_host_commands(
                args.hosts.split(","), args.script,
                script_args=args.script_args, master_addr=args.master,
                distributed=(args.distributed
                             if args.distributed is not None else True)):
            print(f"# {host}\n{cmd}")
        return 0
    rcs = launch_local(args.script, args.nproc,
                       script_args=args.script_args,
                       env={**os.environ,
                            **({"PADDLE_TPU_MASTER": args.master}
                               if args.master else {})},
                       distributed=bool(args.distributed))
    return 0 if all(rc == 0 for rc in rcs) else 1


if __name__ == "__main__":
    sys.exit(main())
