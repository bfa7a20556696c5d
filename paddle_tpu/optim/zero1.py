"""ZeRO-1 sharded optimizer update (stage-1 optimizer-state partitioning).

The reference splits the parameter update across pservers so no node holds
the full optimizer state: each ``ParameterServer2`` owns a contiguous block
of every parameter, applies the optimizer to its block after
``addGradient`` (``ParameterServer2.cpp:362``), and trainers gather the
updated values. This module is that partitioning re-expressed on the mesh's
data axis (ZeRO stage 1, Rajbhandari et al.; the same scheme as
TensorFlow's parameter-server sharding):

1. every eligible parameter (and each of its optimizer slots) is viewed as
   a flat vector, zero-padded to a multiple of the data-parallel degree N,
   and reshaped to ``(N, chunk)`` — slots are STORED this way, sharded
   ``P(data)``, so each device permanently holds 1/N of every slot;
2. inside the jitted train step a ``shard_map_compat`` over the mesh
   applies ``Optimizer._update_param`` (the exact replicated code path) to
   each device's shard — XLA sees the gradient consumed shard-wise and can
   lower the backward all-reduce + slice into a reduce-scatter;
3. the updated parameter shards are all-gathered (``lax.all_gather``) back
   to full replicated arrays for the next forward pass.

The update math is elementwise per parameter for every dense optimizer, so
the sharded result is bit-exact vs the replicated path. Excluded from the
plan (they fall back to the replicated per-parameter update inside the same
``update`` call):

- static parameters (no slots at all);
- sparse lazy-path parameters (``Optimizer._is_sparse``: the per-row
  ``t_rows`` bookkeeping is row-structured, not flat-elementwise);
- parameters with a non-replicated sharding rule (e.g. embedding tables
  row-sharded over the model axis — their slots already follow the table,
  ``parallel/mesh.py:shard_opt_state``). Since r08 this is also how the
  pipeline composes: stage-stacked body parameters carry ``P(pipe, ...)``
  rules (``parallel/pipeline.py:PipelineTrainPlan.shard_rules``), so
  their slots stay 1/S-per-device on the pipe axis while the replicated
  head still partitions over the data axis here
  (``docs/pipeline_parallel.md`` interaction matrix).

Model-averaging state (``avg``) stays replicated: it is consumed whole by
``averaged_params`` at eval/save time and is rare enough not to warrant a
second layout.

Checkpoint format compatibility: ``gather_opt_state`` restores every slot
to its parameter's full shape before a save (``trainer/checkpoint.py``
stores the same keys as a replicated run), and ``pack_for_load`` reshards a
full-shape slot on restore — so resume crosses sharded<->replicated modes
in both directions.

The communication contract is machine-checked (graftlint pass 4,
``analysis/shard_audit.py``): the step's ONE fused all-gather and its
unchanged backward all-reduce are pinned in ``analysis/comm_budget.toml``
(PT501), the pack-buffer ``with_sharding_constraint`` pins below are
asserted at the jaxpr level (PT503 — removing one fails tier-1), and a
planned slot that loses its ``P(data)`` placement is PT502.

r17 generalized this module into the full-FSDP plane
(:class:`FsdpUpdater`): the same flat ``(N, chunk)`` packing applied to
the PARAMETERS themselves, partitioned over the mesh's dedicated
``fsdp`` axis with gather-on-use — each device permanently holds 1/N of
every eligible parameter and slot, the forward all-gathers each
parameter per layer, the backward reduce-scatters its gradient, and the
shard-wise update needs NO trailing gather (the next step re-gathers).
Eligibility for both updaters is ONE question asked of the canonical
layout (``parallel/layout.py:SpecLayout.fsdp_eligible``), so
model-sharded tables and pipeline stage-stacked keys are excluded by
the same rule table that places them. The fsdp programs' collectives
and per-device bytes are pinned like zero1's (``fsdp_train`` /
``fsdp_pipe`` in both budgets; the ~1/N param-bytes law is graftlint
PT602, a full-gather materialization fails PT604).
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core.registry import ParamSpec
from paddle_tpu.optim.optimizers import Optimizer
from paddle_tpu.parallel import mesh as mesh_lib

# Overlap-spelling override for the FSDP gather path (r18): None = auto
# (double-buffer chain on TPU, sync spelling elsewhere — the CPU audit
# compiles must stage the exact program the budgets were pinned on);
# "force" = stage the chain regardless of backend (tests, bench A/B);
# "off" = pin the sync spelling.
_OVERLAP_FORCED: Optional[str] = os.environ.get(
    "PADDLE_TPU_FSDP_OVERLAP") or None


@contextlib.contextmanager
def overlap_spelling(mode: Optional[str]):
    """Force the FSDP gather-overlap spelling for a scope ("force" /
    "off" / None=auto). Trace-time only — it picks which program gets
    staged; re-jit after changing it."""
    global _OVERLAP_FORCED
    prev, _OVERLAP_FORCED = _OVERLAP_FORCED, mode
    try:
        yield
    finally:
        _OVERLAP_FORCED = prev


@jax.custom_vjp
def _prefetch_fence(leaf, prev_gathered):
    """``optimization_barrier`` on (next gather's input, previous
    gather's output): identity on values, but the scheduler cannot
    start gather k+1 before gather k materialises. custom_vjp because
    the primitive has no differentiation rule — and the backward we
    want is the SAME fence on the cotangents, which serializes the
    grad reduce-scatters pairwise in reverse schedule order (each one
    overlapping the previous layer's backward compute)."""
    return jax.lax.optimization_barrier((leaf, prev_gathered))


def _prefetch_fence_fwd(leaf, prev_gathered):
    return jax.lax.optimization_barrier((leaf, prev_gathered)), None


def _prefetch_fence_bwd(_, ct):
    ct_leaf, ct_prev = ct
    return jax.lax.optimization_barrier((ct_leaf, ct_prev))


_prefetch_fence.defvjp(_prefetch_fence_fwd, _prefetch_fence_bwd)


class Zero1Updater:
    """Drop-in for the ``update`` protocol of :class:`Optimizer`, with
    optimizer slots partitioned over the mesh's batch axes.

    Construct once per trainer (the plan — shapes, pad sizes, eligibility —
    is static per model); ``convert_state`` reshards an existing replicated
    state in place of a fresh ``init``.
    """

    def __init__(self, optimizer: Optimizer, mesh, params: Dict[str, Any],
                 meta: Optional[Dict[str, ParamSpec]] = None,
                 rules: Optional[Dict[str, P]] = None,
                 fsdp: bool = False):
        from paddle_tpu.parallel.layout import SpecLayout
        self.opt = optimizer
        self.mesh = mesh
        self.meta = meta or {}
        # the partition axes and sharding are THE layout's packed-role
        # derivation (SpecLayout.packed_*): the batch axes for ZeRO-1
        # (slots follow the gradient partition), the dedicated fsdp
        # axis for FsdpUpdater — one packing, two layouts, derived in
        # one place
        layout = SpecLayout(mesh, rules=rules)
        self.layout = layout
        self.axes = layout.packed_axes(fsdp=fsdp)
        self._packed_sharding = layout.packed_sharding(fsdp=fsdp)
        n = 1
        for a in self.axes:
            n *= int(dict(mesh.shape).get(a, 1))
        self.n = n
        if self.n <= 1:
            raise ValueError(
                "ZeRO-1/FSDP needs a partition degree > 1 over "
                f"{self.axes or 'the batch axes'}; with one device "
                "there is nothing to partition (callers fall back to "
                "the replicated update)")
        # plan: name -> (orig_shape, size, chunk). Only these params take
        # the sharded path; everything else falls back per-parameter.
        # Eligibility is the canonical layout's ONE question
        # (SpecLayout.fsdp_eligible): static and sparse-lazy params are
        # out, and so is anything the rule table already places —
        # model-sharded tables and pipeline stage-stacked keys follow
        # their own rule instead of the flat packing.
        self.plan: Dict[str, tuple] = {}
        self.dtypes: Dict[str, np.dtype] = {}
        for name, p in params.items():
            spec = self.meta.get(name)
            if not layout.fsdp_eligible(name, spec, optimizer):
                continue
            shape = tuple(int(d) for d in p.shape)
            size = 1
            for d in shape:
                size *= d
            chunk = -(-size // self.n)  # ceil
            self.plan[name] = (shape, size, chunk)
            self.dtypes[name] = np.dtype(p.dtype)

    # ------------------------------------------------------- layout helpers
    def _pack(self, x, name: str):
        """Full array -> zero-padded (N, chunk) view (trace-time op; free
        for replicated inputs — each device slices its own rows).

        Padding uses ``concatenate``, NOT ``jnp.pad``: on the CPU backend a
        pad op fused into the downstream elementwise update changes its
        codegen enough to round real elements differently (observed multi-
        ulp drift vs the replicated path); concatenate keeps the update
        bit-exact, which the parity tests assert."""
        _, size, chunk = self.plan[name]
        flat = x.reshape(-1)
        pad = self.n * chunk - size
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        return flat.reshape(self.n, chunk)

    def _unpack(self, x2d, name: str):
        shape, size, _ = self.plan[name]
        return x2d.reshape(-1)[:size].reshape(shape)

    def _pack_host(self, x: np.ndarray, name: str) -> np.ndarray:
        _, size, chunk = self.plan[name]
        flat = np.asarray(x).reshape(-1)
        pad = self.n * chunk - size
        if pad:
            flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
        return flat.reshape(self.n, chunk)

    def _slot_sharding(self) -> NamedSharding:
        return self._packed_sharding

    # ------------------------------------------------------------ lifecycle
    def init(self, params, meta=None):
        """Replicated init, then shard the plan's slots."""
        return self.convert_state(self.opt.init(params, meta or self.meta))

    def convert_state(self, state):
        """Reshard a replicated optimizer state: every slot of a planned
        parameter moves to the (N, chunk) ``P(data)`` layout (including
        ``prune_mask`` — it is elementwise like the rest). Scalars and the
        ``avg`` tree stay replicated. Idempotent on already-converted
        leaves."""
        sharding = self._slot_sharding()
        new_slots = {}
        for name, slots in state["slots"].items():
            if name not in self.plan:
                new_slots[name] = slots
                continue
            _, _, chunk = self.plan[name]
            out = {}
            for slot, leaf in slots.items():
                if leaf.ndim == 2 and leaf.shape == (self.n, chunk):
                    out[slot] = jax.device_put(leaf, sharding)
                else:
                    out[slot] = jax.device_put(
                        self._pack_host(jax.device_get(leaf), name), sharding)
            new_slots[name] = out
        return {**state, "slots": new_slots}

    def gather_opt_state(self, state):
        """The checkpoint view: every planned slot back at its parameter's
        full shape (unpad + reshape), so the saved key set and array shapes
        are identical to a replicated run's — ``trainer/checkpoint.py``
        stays format-compatible and a replicated resume needs no
        conversion."""
        new_slots = {}
        for name, slots in state["slots"].items():
            if name not in self.plan:
                new_slots[name] = slots
                continue
            new_slots[name] = {slot: self._unpack(leaf, name)
                               for slot, leaf in slots.items()}
        return {**state, "slots": new_slots}

    def pack_for_load(self, key: str, value: np.ndarray, current):
        """Reshard one restored opt-state leaf (flattened key
        ``slots/<param>/<slot>``) into this plan's layout when it arrives
        at the parameter's full shape; pass-through otherwise."""
        parts = key.split("/")
        if len(parts) == 3 and parts[0] == "slots" and parts[1] in self.plan:
            if tuple(np.shape(value)) != tuple(current.shape):
                return self._pack_host(value, parts[1])
        return value

    # --------------------------------------------------------------- update
    def update(self, grads, state, params,
               meta: Optional[Dict[str, ParamSpec]] = None,
               batch_size=1, num_passes=0):
        """Same contract as :meth:`Optimizer.update`. Planned parameters
        update shard-wise under ``shard_map``; the rest run the replicated
        per-parameter body. One shared t/num_samples/lr computation keeps
        the two sub-paths on the same schedule step."""
        from paddle_tpu.optim.schedules import learning_rate_at
        opt = self.opt
        meta = meta if meta is not None else self.meta

        t = state["t"] + 1
        num_samples = state["num_samples"] + batch_size
        lr_t = learning_rate_at(
            opt.learning_rate_schedule, opt.learning_rate,
            opt.learning_rate_decay_a, opt.learning_rate_decay_b,
            num_samples, args=opt.learning_rate_args, num_passes=num_passes)
        if opt.sum_gradients:
            bsz = jnp.asarray(batch_size, jnp.float32)
            grads = {n: g * bsz for n, g in grads.items()}

        new_params = dict(params)
        new_slots = {n: s for n, s in state["slots"].items()
                     if n not in grads}
        z_names = sorted(n for n in grads
                         if n in self.plan and n in state["slots"])

        # fallback set: sparse lazy tables, model-sharded params, and any
        # grad for a param without slots — identical to Optimizer.update
        for name, g in grads.items():
            if name in z_names:
                continue
            if name not in state["slots"]:
                new_params[name] = params[name]
                continue
            spec = meta.get(name) if meta else None
            p_new, s_new = opt._update_param(
                g, params[name], state["slots"][name], spec, lr_t, t)
            new_params[name] = p_new
            new_slots[name] = s_new

        if z_names:
            # ONE fused buffer for params and grads (the ZeRO bucketing
            # trick): per-parameter (N, chunk) shards concatenate along
            # the chunk dim into a single (N, sum_chunks) array, so the
            # step issues ONE all-gather instead of one per parameter —
            # on CPU-emulated meshes per-collective dispatch dominates,
            # on TPU one large ICI transfer beats many small ones.
            offs, off = {}, 0
            for n in z_names:
                chunk = self.plan[n][2]
                offs[n] = (off, off + chunk)
                off += chunk
            # pin the fused buffers replicated: without the constraint,
            # sharding propagation lets the shard_map's P(data) demand
            # leak into the BACKWARD pass and reshape its collectives
            # (observed 2x whole-step slowdown); with it, the backward is
            # byte-identical to the replicated path's and the shard_map
            # just slices local rows
            rep = NamedSharding(self.mesh, P())
            p_fused = jax.lax.with_sharding_constraint(jnp.concatenate(
                [self._pack(params[n], n) for n in z_names], axis=1), rep)
            g_fused = jax.lax.with_sharding_constraint(jnp.concatenate(
                [self._pack(grads[n], n) for n in z_names], axis=1), rep)
            s_sh = {n: state["slots"][n] for n in z_names}
            specs = {n: (meta.get(n) if meta else None) for n in z_names}
            axes = self.axes

            def shard_update(p_loc, g_loc, s_sh, lr_t, t):
                # local view: this device's (1, sum_chunks) row of the
                # fused buffer plus its (1, chunk) slot shards. The
                # reduce-scatter of the issue lives here implicitly: the
                # gradient is consumed shard-wise, so XLA's collective
                # optimizer can fold the backward all-reduce + slice into
                # a reduce-scatter over the data axis.
                out_p, out_s = [], {}
                for n in z_names:
                    lo, hi = offs[n]
                    p1, s1 = opt._update_param(
                        g_loc[:, lo:hi], p_loc[:, lo:hi], s_sh[n],
                        specs[n], lr_t, t)
                    out_p.append(p1)
                    out_s[n] = s1
                # the ZeRO-1 all-gather: updated shards -> the full
                # replicated fused buffer for the next forward
                return jax.lax.all_gather(
                    jnp.concatenate(out_p, axis=1), axis_name=axes,
                    axis=0, tiled=True), out_s

            gathered, s_new = mesh_lib.shard_map_compat(
                shard_update, self.mesh,
                in_specs=(P(self.axes), P(self.axes), P(self.axes),
                          P(), P()),
                out_specs=(P(), P(self.axes)))(p_fused, g_fused, s_sh,
                                               lr_t, t)
            for n in z_names:
                lo, hi = offs[n]
                new_params[n] = self._unpack(gathered[:, lo:hi], n)
                new_slots[n] = s_new[n]

        new_state = {"slots": new_slots, "t": t, "num_samples": num_samples}
        if "avg" in state:
            # model averaging stays replicated (see module docstring); the
            # window semantics live in ONE place, fed by gathered params
            new_state["avg"] = opt._update_avg(state["avg"], t, new_params,
                                               new_slots)
        return new_params, new_state

    # ------------------------------------------------- delegated protocol
    def catch_up(self, params, state, meta=None, num_passes=0):
        """Sparse lazy tables are excluded from the plan, so their rows
        live replicated in the same state tree — the wrapped optimizer's
        catch-up applies unchanged."""
        return self.opt.catch_up(params, state, meta, num_passes=num_passes)

    def prune_params(self, params, state):
        return self.opt.prune_params(params, self.gather_opt_state(state))

    def averaged_params(self, state, params):
        return self.opt.averaged_params(state, params)


class FsdpUpdater(Zero1Updater):
    """Full FSDP (ZeRO stage 3): parameters AND optimizer slots
    partitioned 1/N over the mesh's dedicated ``fsdp`` axis.

    Same flat ``(N, chunk)`` packing as ZeRO-1, promoted from optimizer
    slots to the parameters themselves:

    - **storage** — every planned parameter lives packed ``(N, chunk)``
      sharded ``P(fsdp)`` (``pack_params``); each device permanently
      holds 1/N of it. The fsdp axis ALSO carries batch rows
      (``mesh.batch_axes`` includes it), so the data-parallel story is
      unchanged — only parameter residency shrinks, which is how a
      model ~N× one device's memory trains on an N-device mesh.
    - **gather-on-use** — ``full_params`` rebuilds each full parameter
      inside the jitted step with ONE all-gather over fsdp per
      parameter (a ``with_sharding_constraint`` to replicated, then the
      unpad/reshape). Per layer, deliberately: the largest gathered
      buffer is one layer's parameter, never the whole model — the
      full-gather-materialization smell graftlint PT604 rejects.
    - **backward** — the gather's transpose makes XLA reduce the
      per-device partial gradients back INTO the packed layout
      (reduce-scatter, or all-reduce + local slice — whichever the
      partitioner picks is pinned in ``analysis/comm_budget.toml``).
    - **update** — the ZeRO shard-wise update on the local rows, with
      NO trailing all-gather: the updated parameter stays sharded and
      the next step's forward re-gathers it. Slots pack identically
      (``convert_state`` inherited), so ``--use_zero1`` composes as a
      no-op — FSDP already holds slots at 1/N.

    Packing padding stays EXACTLY zero across steps: the unpack slice's
    transpose writes zero cotangents into the pad region and every
    dense optimizer maps (0 param, 0 grad, 0 slots) to 0, so the
    gather-on-save/reshard-on-load checkpoint round trip (full shapes
    on disk, the zero1/pipeline format precedent) is lossless.

    Exactness: the gathered forward is bit-identical to the unsharded
    one (the gather reconstructs exact bits) and the shard-wise update
    is the proven zero1 elementwise math; only the gradient REDUCTION
    order may differ from plain DP's all-reduce, so parity vs the
    unsharded step is asserted at 1e-7, not bitwise
    (``tests/test_fsdp.py``) — while exact resume (same program twice)
    stays bitwise (``tests/test_exact_resume_matrix.py``).
    """

    def __init__(self, optimizer: Optimizer, mesh, params: Dict[str, Any],
                 meta: Optional[Dict[str, ParamSpec]] = None,
                 rules: Optional[Dict[str, P]] = None,
                 overlap=True, graph=None):
        if mesh_lib.FSDP_AXIS not in mesh.axis_names or \
                dict(mesh.shape)[mesh_lib.FSDP_AXIS] <= 1:
            raise ValueError(
                f"FSDP needs a {mesh_lib.FSDP_AXIS!r} mesh axis of size "
                "> 1; build one with create_mesh(n_fsdp=N) (callers "
                "stand down to the replicated step)")
        super().__init__(optimizer, mesh, params, meta, rules=rules,
                         fsdp=True)
        # the double-buffer prefetch order: planned names sorted by
        # first consumer in the network's topo order (SpecLayout is the
        # ONE derivation point; falls back to the given — alphabetical
        # init — order without a graph)
        self.schedule: List[str] = self.layout.prefetch_schedule(
            list(self.plan), graph)
        if overlap and len(self.plan) < 2:
            from paddle_tpu.utils.log import logger
            logger.warning(
                "FSDP overlap: only %d planned parameter(s) — nothing "
                "to double-buffer; standing down to the sync gather "
                "spelling", len(self.plan))
            overlap = False
        # True/False = auto (chain on TPU only); "force" = always chain
        self.overlap_mode = overlap

    def _overlap_active(self) -> bool:
        """Does THIS trace stage the double-buffer gather chain? Forced
        mode wins (tests / bench A/B); otherwise the chain is TPU-only —
        the CPU audit compiles must stage the sync spelling the pinned
        comm/mem budgets describe (the byte-identity is separately
        regression-tested by forcing the chain, ``tests/test_analysis``)."""
        if _OVERLAP_FORCED == "off":
            return False
        if _OVERLAP_FORCED == "force" or self.overlap_mode == "force":
            return True
        if not self.overlap_mode:
            return False
        return jax.default_backend() == "tpu"

    def gather_peak_bytes(self) -> int:
        """Per-device transient gathered-buffer peak: the largest single
        gathered parameter under the sync spelling, the largest ADJACENT
        PAIR in schedule order under double-buffering (two layers'
        buffers live while gather k+1 flies behind layer k's compute) —
        the number ``utils/profiler.py:memory_stats`` reports so
        ``--show_step_breakdown`` agrees with the compiled truth."""
        sizes = []
        for name in self.schedule:
            _, _, chunk = self.plan[name]
            itemsize = self.dtypes.get(name, np.dtype(np.float32)).itemsize
            sizes.append(self.n * chunk * itemsize)
        if not sizes:
            return 0
        if not self._overlap_active() or len(sizes) == 1:
            return max(sizes)
        return max(a + b for a, b in zip(sizes, sizes[1:]))

    # -------------------------------------------------- parameter layout
    def _is_packed(self, x, name: str) -> bool:
        _, _, chunk = self.plan[name]
        return (getattr(x, "ndim", 0) == 2
                and tuple(x.shape) == (self.n, chunk))

    def pack_params(self, params):
        """Full-shape params -> the storage layout: planned leaves
        packed ``(N, chunk)`` sharded ``P(fsdp)``. Eager (enable/load
        time); idempotent on already-packed-and-placed leaves. A leaf
        whose FULL shape happens to equal ``(N, chunk)`` (an N-row fc
        weight) is a shape coincidence, not a packed leaf — packing is
        the identity reshape for it, but it must still be RESHARDED or
        it sits replicated at full per-device bytes (review-round
        finding; regression-tested)."""
        sharding = self._slot_sharding()
        out = dict(params)
        for name in self.plan:
            leaf = out.get(name)
            if leaf is None:
                continue
            if self._is_packed(leaf, name) and \
                    getattr(leaf, "sharding", None) == sharding:
                continue
            if not self._is_packed(leaf, name):
                leaf = self._pack_host(jax.device_get(leaf), name)
            out[name] = jax.device_put(leaf, sharding)
        return out

    def unpack_params(self, params):
        """Storage -> full shapes (jnp ops: works eagerly for the
        checkpoint/eval view and under a trace). The eager spelling
        performs the gather as a device op — ``_params_for_save`` passes
        this lazily so saves not due pay nothing."""
        out = dict(params)
        for name in self.plan:
            leaf = out.get(name)
            if leaf is not None and self._is_packed(leaf, name):
                out[name] = self._unpack(leaf, name)
        return out

    def full_params(self, params):
        """The gather-on-use view inside the jitted step: per planned
        parameter, pin the packed leaf replicated (ONE all-gather over
        the fsdp axis) and unpad/reshape to the full shape. The rest of
        the step — forward, backward, metrics — consumes the result
        exactly as it consumes replicated parameters.

        Overlap spelling (``_overlap_active``): the gathers are chained
        with ``optimization_barrier`` in prefetch-schedule order — the
        packed input of gather k+1 is fenced on gather k's OUTPUT, so
        the scheduler can fly at most one gather ahead of its consumer
        (gather k+1 behind layer k's compute: classic double-buffering,
        peak = two gathered layers, never the whole model) while each
        layer's compute is free to overlap the next gather. The barrier
        is the identity on values, adds NO collectives (graftlint pass 4
        budgets byte-identically; regression-tested), and its transpose
        is the same chain reversed — the backward's grad reduce-scatters
        are fenced pairwise too, overlapping the PREVIOUS layer's
        backward compute symmetrically."""
        rep = NamedSharding(self.mesh, P())
        out = dict(params)
        if not self._overlap_active():
            for name in self.plan:
                leaf = out.get(name)
                if leaf is not None:
                    out[name] = self._unpack(
                        jax.lax.with_sharding_constraint(leaf, rep), name)
            return out
        names = [n for n in self.schedule if out.get(n) is not None]
        gathered: Dict[str, Any] = {}
        prev = None
        for name in names:
            leaf = out[name]
            if prev is not None:
                leaf, gathered[prev] = _prefetch_fence(
                    leaf, gathered[prev])
            gathered[name] = jax.lax.with_sharding_constraint(leaf, rep)
            prev = name
        for name in names:
            out[name] = self._unpack(gathered[name], name)
        return out

    def pack_params_host(self, params):
        """Host-side packing of a restored full-shape param dict (numpy
        in, numpy out) so ``SGD.load_state``'s place() sees arrays
        matching the live packed leaves."""
        out = dict(params)
        for name in self.plan:
            if name in out:
                arr = np.asarray(out[name])
                _, _, chunk = self.plan[name]
                if arr.ndim == 2 and arr.shape == (self.n, chunk):
                    continue  # already packed (a same-mode resume)
                out[name] = self._pack_host(arr, name)
        return out

    # --------------------------------------------------------------- update
    def update(self, grads, state, params,
               meta: Optional[Dict[str, ParamSpec]] = None,
               batch_size=1, num_passes=0):
        """Shard-wise update on the packed storage: planned parameters
        and their gradients arrive ``(N, chunk)`` (the gather's
        transpose already reduced the cotangent into the packed
        layout), fuse along the chunk dim, update each device's row,
        and RETURN THE SHARDS — no trailing all-gather; the next
        forward's per-layer gather is the only reconstruction."""
        from paddle_tpu.optim.schedules import learning_rate_at
        if "avg" in state:
            raise ValueError(
                "FSDP does not compose with model averaging ('avg' "
                "state is consumed whole at eval/save time); "
                "enable_fsdp stands down before building this updater")
        opt = self.opt
        meta = meta if meta is not None else self.meta

        t = state["t"] + 1
        num_samples = state["num_samples"] + batch_size
        lr_t = learning_rate_at(
            opt.learning_rate_schedule, opt.learning_rate,
            opt.learning_rate_decay_a, opt.learning_rate_decay_b,
            num_samples, args=opt.learning_rate_args, num_passes=num_passes)
        if opt.sum_gradients:
            bsz = jnp.asarray(batch_size, jnp.float32)
            grads = {n: g * bsz for n, g in grads.items()}

        new_params = dict(params)
        new_slots = {n: s for n, s in state["slots"].items()
                     if n not in grads}
        z_names = sorted(n for n in grads
                         if n in self.plan and n in state["slots"])

        # fallback set: sparse lazy tables, ruled (model/pipe) params,
        # grads for slot-less params — the replicated per-param body,
        # identical to Optimizer.update (and to Zero1Updater's)
        for name, g in grads.items():
            if name in z_names:
                continue
            if name not in state["slots"]:
                new_params[name] = params[name]
                continue
            spec = meta.get(name) if meta else None
            p_new, s_new = opt._update_param(
                g, params[name], state["slots"][name], spec, lr_t, t)
            new_params[name] = p_new
            new_slots[name] = s_new

        if z_names:
            # one fused (N, sum_chunks) buffer per role, exactly the
            # zero1 bucketing — except the operands are ALREADY packed
            # and sharded, so the concatenate runs shard-wise. The pins
            # keep propagation honest (graftlint PT503: a pack feeding
            # a sharded shard_map in_spec must carry a constraint).
            offs, off = {}, 0
            for n in z_names:
                chunk = self.plan[n][2]
                offs[n] = (off, off + chunk)
                off += chunk
            shd = self._slot_sharding()
            p_fused = jax.lax.with_sharding_constraint(jnp.concatenate(
                [params[n] for n in z_names], axis=1), shd)
            g_fused = jax.lax.with_sharding_constraint(jnp.concatenate(
                [grads[n] for n in z_names], axis=1), shd)
            s_sh = {n: state["slots"][n] for n in z_names}
            specs = {n: (meta.get(n) if meta else None) for n in z_names}

            def shard_update(p_loc, g_loc, s_sh, lr_t, t):
                # this device's (1, sum_chunks) row + its slot rows:
                # the elementwise update math is the replicated path's,
                # applied to 1/N of every parameter — and the result
                # STAYS here (no gather; the next forward re-gathers)
                out_p, out_s = [], {}
                for n in z_names:
                    lo, hi = offs[n]
                    p1, s1 = opt._update_param(
                        g_loc[:, lo:hi], p_loc[:, lo:hi], s_sh[n],
                        specs[n], lr_t, t)
                    out_p.append(p1)
                    out_s[n] = s1
                return jnp.concatenate(out_p, axis=1), out_s

            fused_new, s_new = mesh_lib.shard_map_compat(
                shard_update, self.mesh,
                in_specs=(P(self.axes), P(self.axes), P(self.axes),
                          P(), P()),
                out_specs=(P(self.axes), P(self.axes)))(p_fused, g_fused,
                                                        s_sh, lr_t, t)
            for n in z_names:
                lo, hi = offs[n]
                new_params[n] = jax.lax.with_sharding_constraint(
                    fused_new[:, lo:hi], shd)
                new_slots[n] = s_new[n]

        return new_params, {"slots": new_slots, "t": t,
                            "num_samples": num_samples}

    # ------------------------------------------------- delegated protocol
    def prune_params(self, params, state):
        """Pruning masks live at full shapes: gather, prune, re-pack."""
        full = self.unpack_params(params)
        pruned = self.opt.prune_params(full, self.gather_opt_state(state))
        return self.pack_params(pruned)
