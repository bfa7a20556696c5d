"""Device mesh + sharding utilities.

This module is the TPU-native replacement for the reference's entire
parallel/communication stack:

- intra-node data parallelism: ``MultiGradientMachine``'s thread-per-device
  ring scatter/gather (``MultiGradientMachine.h:44-80``) becomes a batch
  sharded over the mesh ``data`` axis; XLA emits the gradient all-reduce
  (psum) over ICI.
- multi-node sync SGD: ``ParameterServer2::addGradient``
  (``ParameterServer2.cpp:362``) + pass barriers become the same all-reduce
  — sync SGD *is* all-reduce semantics.
- sparse/model-parallel embeddings: ``SparseRowMatrix``-style row slices
  (``SparseRowMatrix.h:204``) become embedding tables sharded on the
  ``model`` axis, gathered by XLA all-to-all/all-gather.
- async SGD (``ParameterServer2.cpp:457``): not representable on a
  synchronous fabric; executed as sync SGD (documented approximation,
  SURVEY §2 checklist).

Axes: ``data`` (batch), ``fsdp`` (batch + flat-packed parameter/optimizer
state, 1/N per device — ``optim/zero1.py:FsdpUpdater``), ``model``
(tensor/embedding sharding), ``seq`` (sequence parallelism), ``pipe``
(GPipe stages). Multi-host DCN maps to extra leading mesh dims
transparently through jax.devices().

Since r17 the canonical placement derivations (batch/param/slot/packed
specs, the non-divisible replicated fallback) live in ONE object —
``parallel/layout.py:SpecLayout`` — and the placement helpers below
(``shard_params``/``param_shardings``/``shard_opt_state``) are thin
compatibility wrappers over it (``docs/spec_layout.md``).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.argument import Argument

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"  # batch + flat-packed param/slot shards (zero1.py)
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"  # GPipe stage axis (parallel/pipeline.py)
DCN_AXIS = "dcn"  # cross-slice (data-center network) leading axis


def create_mesh(n_data: Optional[int] = None, n_model: int = 1,
                n_seq: int = 1, devices=None, n_pipe: int = 1,
                n_fsdp: int = 1) -> Mesh:
    """Build a (data, model) mesh — or (data, seq, model) when
    ``n_seq > 1`` for sequence/context parallelism (ring/ulysses
    attention shards the time axis over ``seq``; the axis sits between
    data and model so its ppermute/all-to-all rides ICI next to the
    model axis), or (data, pipe) when ``n_pipe > 1`` for pipeline
    parallelism (one GPipe stage per pipe slot, innermost so the
    stage-handoff ppermute rides ICI; ``--parallel_nn``,
    ``trainer/trainer.py:enable_pipeline``). Defaults to all visible
    devices on the data axis (pure DP, the reference's trainer_count
    semantics).

    ``n_fsdp > 1`` inserts the ``fsdp`` axis right after ``data``: the
    batch shards over BOTH (DP degree = data × fsdp, the same rows/
    gradients story), while eligible parameters and optimizer slots
    live flat-packed 1/n_fsdp per device with gather-on-use
    (``--fsdp``, ``optim/zero1.py:FsdpUpdater``,
    ``docs/spec_layout.md``). The 4D composition forms are
    (data, fsdp, pipe), (data, fsdp, seq, pipe) and
    (data, fsdp, seq, model)."""
    devices = devices if devices is not None else jax.devices()
    if n_pipe > 1 and n_model > 1:
        raise ValueError(
            "n_pipe does not compose with n_model (a pipeline stage owns "
            "its whole layer; shard within a stage via shard_rules "
            "instead)")
    if n_data is None:
        n_data = len(devices) // (n_model * n_seq * n_pipe * n_fsdp)
    if n_pipe > 1:
        dims = [(DATA_AXIS, n_data)]
        if n_fsdp > 1:
            dims.append((FSDP_AXIS, n_fsdp))
        if n_seq > 1:
            dims.append((SEQ_AXIS, n_seq))
        dims.append((PIPE_AXIS, n_pipe))
        total = 1
        for _, sz in dims:
            total *= sz
        devs = np.asarray(devices[:total]).reshape(
            tuple(sz for _, sz in dims))
        return Mesh(devs, tuple(ax for ax, _ in dims))
    if n_fsdp > 1:
        if n_seq > 1 or n_model > 1:
            devs = np.asarray(
                devices[: n_data * n_fsdp * n_seq * n_model]).reshape(
                n_data, n_fsdp, n_seq, n_model)
            return Mesh(devs, (DATA_AXIS, FSDP_AXIS, SEQ_AXIS, MODEL_AXIS))
        devs = np.asarray(devices[: n_data * n_fsdp]).reshape(
            n_data, n_fsdp)
        return Mesh(devs, (DATA_AXIS, FSDP_AXIS))
    if n_seq > 1:
        devs = np.asarray(devices[: n_data * n_seq * n_model]).reshape(
            n_data, n_seq, n_model)
        return Mesh(devs, (DATA_AXIS, SEQ_AXIS, MODEL_AXIS))
    devs = np.asarray(devices[: n_data * n_model]).reshape(n_data, n_model)
    return Mesh(devs, (DATA_AXIS, MODEL_AXIS))


def create_multislice_mesh(n_slices: Optional[int] = None,
                           n_data: Optional[int] = None, n_model: int = 1,
                           devices=None) -> Mesh:
    """Build a hierarchical (dcn, data, model) mesh for multi-slice jobs —
    the TPU-native successor of the reference's multi-*node* story
    (`ParameterServer2` sharded sync SGD over TCP/RDMA,
    `ParameterServer2.cpp:362`; SURVEY §5.8).

    The batch is data-parallel over BOTH the leading ``dcn`` axis (slices,
    connected by data-center network) and the ``data`` axis (chips within a
    slice, connected by ICI); the gradient all-reduce XLA emits over such a
    mesh is hierarchical — reduce-scatter/all-gather rides ICI within each
    slice and only the per-slice partial crosses DCN. The ``model`` axis
    (tensor/embedding sharding, all-to-all traffic) is laid out innermost so
    its collectives never leave a slice.

    On real multi-slice hardware, devices are grouped by their
    ``slice_index`` attribute; elsewhere (virtual CPU meshes, single slice)
    a contiguous reshape stands in, which preserves the axis semantics the
    driver's dryrun validates.
    """
    devices = list(devices if devices is not None else jax.devices())
    by_slice: Dict[int, list] = {}
    for d in devices:
        by_slice.setdefault(getattr(d, "slice_index", 0), []).append(d)
    if n_slices is None:
        n_slices = len(by_slice) if len(by_slice) > 1 else 1
    if len(by_slice) > 1 and n_slices != len(by_slice):
        # never silently mix physical slices inside a dcn group — the
        # data/model axes would then carry "ICI" collectives across DCN
        raise ValueError(
            f"devices span {len(by_slice)} physical slices but "
            f"n_slices={n_slices}; pass n_slices={len(by_slice)} (or a "
            "device subset) so the dcn axis follows slice boundaries")
    if len(by_slice) == n_slices and n_slices > 1:
        per_slice = min(len(v) for v in by_slice.values())
        grouped = [v[:per_slice] for _, v in sorted(by_slice.items())]
    else:  # virtual: contiguous split into n_slices groups
        per_slice = len(devices) // n_slices
        grouped = [devices[i * per_slice:(i + 1) * per_slice]
                   for i in range(n_slices)]
    if n_data is None:
        n_data = per_slice // n_model
    if n_data * n_model > per_slice:
        raise ValueError(
            f"create_multislice_mesh: n_data ({n_data}) x n_model "
            f"({n_model}) = {n_data * n_model} exceeds the {per_slice} "
            f"devices available per slice")
    used = n_slices * n_data * n_model
    if used < len(devices):
        from paddle_tpu.utils.log import logger
        logger.warning(
            "create_multislice_mesh uses %d of %d devices "
            "(n_slices=%d x n_data=%d x n_model=%d); %d devices idle",
            used, len(devices), n_slices, n_data, n_model,
            len(devices) - used)
    devs = np.asarray([g[: n_data * n_model] for g in grouped]).reshape(
        n_slices, n_data, n_model)
    return Mesh(devs, (DCN_AXIS, DATA_AXIS, MODEL_AXIS))


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs,
                     check_vma: bool = False):
    """``jax.shard_map`` with this repo's default (``check_vma`` off):
    one spelling for every shard_map consumer (pipeline/moe/ring/
    zero1/the kernels' per-device wrappers). The body is per-device
    code, and is traced as such: a Pallas kernel inside it is called as
    on one chip (``ops/common.py:per_device``)."""
    from paddle_tpu.ops import common as kernel_common  # lazy: cycle

    @functools.wraps(f)
    def body(*args):
        with kernel_common.per_device():
            return f(*args)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def batch_axes(mesh: Mesh):
    """Mesh axes the batch dimension is split over (dcn is part of DP,
    and so is fsdp — FSDP devices carry independent batch rows exactly
    like plain DP; only the PARAMETER placement differs). A mesh
    WITHOUT a data axis (e.g. a pure ("pipe",) stage mesh) has no
    batch axes: the batch replicates and DP degree is 1."""
    if DATA_AXIS not in mesh.axis_names:
        return ()
    axes = []
    if DCN_AXIS in mesh.axis_names:
        axes.append(DCN_AXIS)
    axes.append(DATA_AXIS)
    if FSDP_AXIS in mesh.axis_names:
        axes.append(FSDP_AXIS)
    return tuple(axes)


def data_parallel_degree(mesh: Mesh) -> int:
    d = 1
    for ax in batch_axes(mesh):
        d *= mesh.shape[ax]
    return d


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """The NamedSharding a rank-``ndim`` batch array takes: dim 0 split
    over the data (+dcn) axes, the rest replicated. The single source of
    truth for batch placement — ``shard_batch`` and the async input
    pipeline's device_put stage (``data/prefetch.py``) both use it, so a
    prefetched batch lands exactly where the step expects it."""
    axes = batch_axes(mesh)
    if not axes:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(axes, *([None] * (ndim - 1))))


def shard_batch(feed: Dict[str, Argument], mesh: Mesh) -> Dict[str, Argument]:
    """Place a feed dict with the batch dim split over the data axis (and
    the dcn axis on a multi-slice mesh). Host leaves (a ``DataFeeder``'s)
    go from the host straight to each device's shard."""

    n_data = data_parallel_degree(mesh)

    def place(x):
        if x.shape[0] % n_data != 0:
            raise ValueError(
                f"batch size {x.shape[0]} not divisible by data-parallel "
                f"degree {n_data}; pad or resize the batch (the reference "
                "splits remainders unevenly across TrainerThreads — on a "
                "SPMD mesh the split must be exact; DataFeeder "
                "batch_buckets pads with masked rows)")
        sharding = batch_sharding(mesh, x.ndim)
        if jax.process_count() > 1:
            # multi-host SPMD (dist.launch jobs): device_put cannot target
            # non-addressable devices; each process contributes the shards
            # it owns, sliced from the host-replicated batch by global
            # index
            host = np.asarray(x)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(place, feed)


def replicate(tree, mesh: Mesh):
    """Replicate a pytree (params/opt state) across the mesh."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)


def key_matches(pat: str, name: str) -> bool:
    """Does one rule key cover ``name``? A key starting with ``=``
    matches the full name EXACTLY (so a rule for ``_emb.w0`` can never
    capture ``_user_emb.w0``); any other key matches as a substring."""
    if pat.startswith("="):
        return pat[1:] == name
    return pat in name


def rule_key_for(name: str, rules: Optional[Dict[str, P]]
                 ) -> Optional[str]:
    """The key ``rule_for`` resolves ``name`` to, or None. Exact keys
    are consulted FIRST, then substring keys in table order — an
    ``=``-pin for one parameter always beats a broad substring rule,
    wherever it sits in the table (precedence pinned by
    tests/test_analysis.py; graftlint PT505's dead/shadowed-key
    analysis calls this same function, so the audit can never drift
    from the semantics it audits)."""
    if rules:
        for pat in rules:
            if pat.startswith("=") and key_matches(pat, name):
                return pat
        for pat in rules:
            if not pat.startswith("=") and key_matches(pat, name):
                return pat
    return None


def rule_for(name: str, rules: Optional[Dict[str, P]]) -> P:
    """First rule whose key matches ``name`` (see ``rule_key_for`` for
    the precedence contract); replicated default."""
    key = rule_key_for(name, rules)
    return rules[key] if key is not None else P()


def shard_params(params: Dict[str, jax.Array], mesh: Mesh,
                 rules: Optional[Dict[str, P]] = None):
    """Place parameters: replicated by default; ``rules`` maps param-name
    substrings to PartitionSpecs (e.g. shard embedding rows on MODEL_AXIS,
    the sparse-embedding model parallelism of SURVEY §2 #5).
    Compatibility wrapper over ``SpecLayout.place_params`` — the rules
    passed here are assumed already effective (the trainer builds them
    through its layout)."""
    from paddle_tpu.parallel.layout import SpecLayout
    return SpecLayout(mesh, rules=rules).place_params(params)


def param_shardings(param_names, mesh: Mesh,
                    rules: Optional[Dict[str, P]] = None):
    """NamedSharding per parameter name (for jit out_shardings so big
    sharded tables are *created* in place, never materialized whole).

    ``param_names`` may be a {name: ParamSpec} dict: parameters flagged
    ``sparse_grad`` (embedding tables) default to row-sharding over the
    model axis when no explicit rule names them — the ``SparseRowMatrix``
    row-slice placement, without configs having to spell it out.
    Compatibility wrapper over ``SpecLayout.param_shardings``."""
    from paddle_tpu.parallel.layout import SpecLayout
    layout = SpecLayout(mesh, param_specs=param_names, rules=rules)
    return layout.param_shardings(param_names)


def effective_rules(param_specs, mesh: Mesh,
                    rules: Optional[Dict[str, P]] = None) -> Dict[str, P]:
    """User rules + the sparse default: tables flagged ``sparse_grad`` with
    no explicit rule row-shard over the model axis. Use the result for both
    param placement and shard_opt_state so slots follow their table."""
    out = dict(rules or {})
    if not isinstance(param_specs, dict):
        return out
    if mesh.shape.get(MODEL_AXIS, 1) <= 1:
        return out
    for name, spec in param_specs.items():
        # guard on "no key matches", NOT on rule_for(...) == P(): a
        # user's explicit P() replication rule must win over the
        # sparse default (same contract as device_attr_rules), and
        # under exact-first precedence an auto-added "=" pin would
        # otherwise override the user's substring rule
        if getattr(spec, "sparse_grad", False) \
                and rule_key_for(name, out) is None:
            out["=" + name] = P(MODEL_AXIS)  # exact: no substring capture
    return out


def device_attr_rules(graph, param_specs, mesh: Mesh,
                      rules: Optional[Dict[str, P]] = None) -> Dict[str, P]:
    """The reference's per-layer ``device`` placement, TPU-native.

    Under ``--parallel_nn`` the reference pins whole layers to devices and
    runs them on per-device worker threads (``ParallelNeuralNetwork.h:
    23-62``, per-layer ``device`` attr in the config). Pinning layers to
    chips is an anti-pattern under SPMD — the XLA-native equivalent of
    "this layer lives on other devices" is sharding its parameters over
    the model axis and letting XLA insert the collectives the reference's
    task queues hand-scheduled. So: every layer whose config carries a
    nonnegative ``device`` gets its parameters sharded over MODEL_AXIS on
    their last (output-feature) dim. Explicit user rules win; parameters
    whose last dim doesn't divide the axis stay replicated (placement is
    a hint, not a contract)."""
    out = dict(rules or {})
    n_model = mesh.shape.get(MODEL_AXIS, 1)
    if graph is None or n_model <= 1 or not isinstance(param_specs, dict):
        return out
    pinned = {name for name, ldef in graph.layers.items()
              if int(getattr(ldef, "attrs", {}).get("device", -1)) >= 0}
    if not pinned:
        return out
    # the SAME config field also spells GPipe stages (pipeline.py:
    # make_pipeline_from_device_attrs). A pipeline config pins EVERY
    # non-data layer with contiguous stage ids from 0 — stand down so
    # the trainer doesn't silently model-shard stage ids; the
    # --parallel_nn shard-hint form pins only SOME layers.
    non_data = [n for n, l in graph.layers.items() if l.type != "data"]
    if non_data and set(non_data) <= pinned:
        stage_ids = sorted({int(graph.layers[n].attrs.get("device"))
                            for n in non_data})
        if len(stage_ids) > 1 and \
                stage_ids == list(range(len(stage_ids))):
            # a user who meant --parallel_nn shard hints (not GPipe
            # stages) must be able to see why they were ignored
            from paddle_tpu.utils.log import logger as _logger
            _logger.warning(
                "device_attr_rules: every non-data layer carries a "
                "contiguous device id 0..%d — treating the config as a "
                "pipeline-stage spelling and standing down the model-axis "
                "shard hints. If you meant --parallel_nn-style placement "
                "hints, leave at least one non-data layer unpinned or "
                "pass explicit shard_rules.", len(stage_ids) - 1)
            return out
    for pname, spec in param_specs.items():
        if any((pat[1:] == pname if pat.startswith("=") else pat in pname)
               for pat in out):
            continue  # a rule already names this parameter — it wins,
            # including an explicit P() asking for replication
        owner = pname[1:].rsplit(".", 1)[0] if pname.startswith("_") else None
        shape = getattr(spec, "shape", None)
        if owner in pinned and shape and shape[-1] % n_model == 0:
            out["=" + pname] = P(
                *([None] * (len(shape) - 1) + [MODEL_AXIS]))
    return out


def shard_opt_state(opt_state, mesh: Mesh,
                    rules: Optional[Dict[str, P]] = None):
    """Shard any optimizer-state pytree: entries of per-parameter dicts
    ("slots", "avg", or any future key whose value is {param_name: ...})
    follow their owning parameter's rule; everything else replicates.

    Rule keys use ``rule_for``'s matching contract: a key starting with
    ``=`` matches the parameter name EXACTLY (the auto-added per-parameter
    rules use this so a rule for ``_emb.w0`` can never capture
    ``_user_emb.w0``); any other key matches as a substring of the name.

    A dimension a rule would shard that is NOT divisible by the mesh axis
    size keeps that leaf replicated — loudly: the warning names the
    parameter, the dim, and the axis. Since r17 the fallback decision
    lives in ``parallel/layout.py:SpecLayout.slot_sharding`` (one
    ``axis_divides`` predicate, shared with graftlint PT502's
    dividing-axis gate, so the placement and the audit always report
    the same decision); this is a compatibility wrapper over
    ``SpecLayout.place_opt_state``."""
    from paddle_tpu.parallel.layout import SpecLayout
    return SpecLayout(mesh, rules=rules).place_opt_state(opt_state)
