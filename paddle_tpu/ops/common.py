"""Kernel-dispatch policy: pallas-compiled / pallas-interpret / reference.

Mirrors the role of the reference's CPU stub layer
(`paddle/cuda/include/stub/*_stub.h`): every kernel has a reference
implementation that runs anywhere, and the fast path is selected by the
platform actually present.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, List, Optional

import jax

FORCE_ENV = "PADDLE_TPU_KERNELS"

# None = auto; "pallas" = force compiled; "interpret" = force interpreter;
# "ref" = force pure-JAX reference implementation.
_FORCED: Optional[str] = os.environ.get(FORCE_ENV) or None

# VMEM budget a kernel's whole working set must fit, counted as Mosaic
# allocates it: pipelined blocks twice (double-buffered), scratch and
# in-kernel copies once. 14 MiB leaves an eighth of headroom under the
# 16 MiB scoped-VMEM limit a TPU v5e compile runs with — the limit at
# which the tiled LSTM was refused ("scoped allocation 17.84M, limit
# 16.00M"; chip run, PERF.md). No kernel raises vmem_limit_bytes.
VMEM_BUDGET_BYTES = 14 * 1024 * 1024

# active record_dispatch() tallies; written at TRACE time only
_RECORDERS: List[Dict[str, Dict[str, int]]] = []


@contextlib.contextmanager
def force_mode(mode: Optional[str]):
    """Force kernel dispatch for a scope (tests use "interpret"/"ref")."""
    global _FORCED
    prev, _FORCED = _FORCED, mode
    try:
        yield
    finally:
        _FORCED = prev


def forced() -> Optional[str]:
    """The forced dispatch mode (env ``PADDLE_TPU_KERNELS`` or an
    enclosing ``force_mode``), None when dispatch follows the platform —
    chip evidence (``chip_smoke.py``) refuses to run unless this is None."""
    return _FORCED


def mode() -> str:
    if _FORCED is not None:
        return _FORCED
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def use_pallas(resident_bytes: int = 0) -> bool:
    """Should this op take the Pallas path (compiled or interpreted)?"""
    m = mode()
    if m == "ref":
        return False
    if resident_bytes > VMEM_BUDGET_BYTES:
        return False
    return True


@contextlib.contextmanager
def record_dispatch():
    """Tally which path every kernel entry takes while the scope is
    open: ``{kernel: {path: count}}``, one count per TRACE of a dispatch
    site (a cached jit re-uses its trace and records nothing). The shape
    and budget gates stay silent dispatch; this is how a caller SEES
    them (``chip_smoke.py``, ``tools/tpu_evidence.py``)."""
    tally: Dict[str, Dict[str, int]] = {}
    _RECORDERS.append(tally)
    try:
        yield tally
    finally:
        # by identity: list.remove compares dicts by VALUE and two empty
        # nested tallies are equal
        _RECORDERS[:] = [t for t in _RECORDERS if t is not tally]


def note(kernel: str, path: str) -> str:
    """Record one dispatch decision into every open tally; returns
    ``path`` so a dispatch site can note and branch in one expression."""
    for tally in _RECORDERS:
        paths = tally.setdefault(kernel, {})
        paths[path] = paths.get(path, 0) + 1
    return path


def pallas_path() -> str:
    """Name of the non-reference path under the current mode."""
    return "interpret" if interpret() else "pallas"


def interpret() -> bool:
    return mode() == "interpret"


# the step mesh ---------------------------------------------------------------
# XLA cannot partition a Mosaic kernel: jax 0.9.0 refuses to lower one
# inside a multi-device step ("Mosaic kernels cannot be automatically
# partitioned. Please wrap the call in a shard_map" — four-chip run,
# PERF.md). So a kernel entry must know, at TRACE time, whether the step
# it is traced into is partitioned over a mesh. That is declared in the
# three places a mesh meets a trace — ``Network.apply`` given ``mesh=``,
# the trainer's jitted step (``SGD._jit_step``), and
# ``parallel.mesh.shard_map_compat``, whose body is per-device code — and
# read here, so no layer or ops signature carries a mesh.

_STEP = threading.local()   # .mesh: the declaration open on this thread


@contextlib.contextmanager
def _declare(mesh):
    prev = current_mesh()
    _STEP.mesh = mesh
    try:
        yield
    finally:
        _STEP.mesh = prev


def step_mesh(mesh):
    """Scope: what is traced inside belongs to a step partitioned over
    ``mesh``. None declares nothing (an enclosing declaration stands),
    so a sub-network applied without a mesh inherits its caller's."""
    return _declare(mesh) if mesh is not None else contextlib.nullcontext()


def per_device():
    """Scope: what is traced inside is per-device code (a ``shard_map``
    body) — every device already holds its own block, nothing is
    partitioned, a kernel is called as on one chip."""
    return _declare(None)


def current_mesh():
    return getattr(_STEP, "mesh", None)


def partitioned() -> bool:
    """Is the code being traced part of a step XLA has to partition over
    several devices? A kernel there either runs per device
    (``batch_local``) or stands down to its reference."""
    mesh = current_mesh()
    return mesh is not None and mesh.size > 1


def batch_split(B: int) -> int:
    """How many ways the step mesh's batch axes split a batch of ``B``
    rows: 1 when nothing is partitioned, the data-parallel degree when
    it divides ``B`` (the kernel then runs through ``batch_local``), and
    0 when the step is partitioned but its batch axes cannot split ``B``
    — the kernel entry must take its reference path."""
    if not partitioned():
        return 1
    from paddle_tpu.parallel import mesh as mesh_lib  # lazy: import cycle
    n = mesh_lib.data_parallel_degree(current_mesh())
    return n if n > 1 and B % n == 0 else 0


def batch_local(fn, split: int, in_dims, out_dims):
    """``fn`` run by every device on its OWN batch rows: a ``shard_map``
    over the step mesh's batch axes; ``fn`` itself when ``split`` (from
    ``batch_split``) is 1. ``in_dims`` and ``out_dims`` give, per
    positional argument/result, the index of its batch dimension, or
    None for an operand every device holds whole (weights: their
    cotangents come back summed over the batch axes); a bare
    ``out_dims`` is for a ``fn`` returning one array."""
    if split <= 1:
        return fn
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import mesh as mesh_lib  # lazy: import cycle
    mesh = current_mesh()
    axes = mesh_lib.batch_axes(mesh)

    def spec(d):
        return P() if d is None else P(*([None] * d + [axes]))

    return mesh_lib.shard_map_compat(
        fn, mesh, in_specs=tuple(spec(d) for d in in_dims),
        out_specs=(tuple(spec(d) for d in out_dims)
                   if isinstance(out_dims, tuple) else spec(out_dims)))


# shared kernel-layout vocabulary -------------------------------------------

NEG = -1e30     # finite -inf stand-in (log-space padding)
LANE = 128      # TPU vector lane width; minor axes pad to a multiple

# The name (``jax.ad_checkpoint.checkpoint_name``) a kernel's forward rule
# gives the residuals that are dearer to recompute than to keep. The
# executor's per-layer checkpoint (``core/network.py``) keeps exactly the
# arrays under this name; outside a checkpoint the name is an identity.
KEPT_RESIDUAL = "kept_residual"


def time_block(*shape):
    """BlockSpec for a [T, ...]-shaped operand consumed one step per grid
    index (the sequential-time pattern every fused recurrence uses)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec((1,) + shape, lambda t: (t,) + (0,) * len(shape),
                        memory_space=pltpu.VMEM)


def resident_block(*shape):
    """BlockSpec for an operand resident in VMEM across all grid steps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape),
                        memory_space=pltpu.VMEM)
