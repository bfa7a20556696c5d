"""The training driver.

Replaces the reference's whole driver column — ``Trainer::train ->
trainOnePass -> trainOneDataBatch -> TrainerInternal::trainOneBatch``
(``paddle/trainer/Trainer.cpp:261,492,402``, ``TrainerInternal.cpp:66``) and
the Python v2 loop (``python/paddle/v2/trainer.py:108-175``) — with one
jitted train step:

    (params, opt_state, batch, rng) -> (params, opt_state, metrics)

The reference pipelines parameter updates *during* backward via per-parameter
callbacks (``TrainerInternal.cpp:70-74``); under XLA the fused step gives the
same overlap automatically (grad+update compile into one program). Data
parallelism: pass a ``Mesh`` — the batch is sharded on the ``data`` axis and
XLA inserts the gradient all-reduce, the ICI equivalent of
``MultiGradientMachine``'s ring and the pserver's ``addGradient``.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P_spec

from paddle_tpu.config import dsl as _dsl
from paddle_tpu.config.model_config import ModelDef
from paddle_tpu.testing import chaos as _chaos
from paddle_tpu.core.argument import Argument
from paddle_tpu.core.network import Network
from paddle_tpu.data import prefetch as _prefetch
from paddle_tpu.utils.masks import assert_mask_f32
from paddle_tpu.optim.optimizers import Optimizer
from paddle_tpu.parallel import mesh as mesh_lib
from paddle_tpu.trainer import events as ev
from paddle_tpu.trainer.evaluators import Accumulator, classification_error

_CLASSIFICATION_COSTS = {"multi-class-cross-entropy"}

_END_OF_PASS = object()  # reader-exhausted sentinel for the timed next()


def _call_reader(reader, pass_id: int):
    """Invoke a per-pass reader. Readers that declare ``pass_aware = True``
    (``dist.master.master_reader``) receive the trainer's pass_id so a
    checkpoint-resumed run requests the correct pass from the master
    instead of getting an instant 'end' for already-finished ones."""
    if getattr(reader, "pass_aware", False):
        return reader(pass_id)
    return reader()


class Topology:
    """cost LayerOutput(s) -> executable Network (``python/paddle/v2/
    topology.py:44``). ``cost`` may be a list: multi-task configs train on
    the SUM of their cost layers, as the reference's ``Argument::sum``
    over all output args does."""

    def __init__(self, cost, extra_outputs: Optional[List] = None,
                 graph: Optional[ModelDef] = None):
        costs = list(cost) if isinstance(cost, (list, tuple)) else [cost]
        if graph is None:
            # prefer the graph the cost layer was built in (stays correct
            # after dsl.reset() begins another model)
            graph = getattr(costs[0], "graph", None) or _dsl.current_graph()
        names = [c.name if hasattr(c, "name") else c
                 for c in (costs + list(extra_outputs or []))]
        self.cost_names = names[:len(costs)]
        self.cost_name = names[0]
        graph.output_layer_names = names
        self.network = Network(graph, outputs=names)
        self.graph = graph


class SGD:
    """v2 ``trainer.SGD``: holds topology + parameters + optimizer and runs
    the training loop."""

    def __init__(self, cost, parameters: Optional[Dict[str, Any]] = None,
                 update_equation: Optimizer = None, *,
                 extra_layers: Optional[List] = None,
                 mesh=None, shard_rules: Optional[Dict[str, Any]] = None,
                 seed: int = 0, is_local: bool = True,
                 evaluators: Optional[List[dict]] = None,
                 prev_batch_state: bool = False,
                 compute_dtype: Optional[Any] = None,
                 recompile_warn: int = 8):
        if update_equation is None:
            raise ValueError("update_equation (an Optimizer) is required")
        self.topology = (cost if isinstance(cost, Topology)
                         else Topology(cost, extra_outputs=extra_layers))
        self.network = self.topology.network
        # config-declared evaluators (compat ctx().evaluators and/or the
        # DSL's graph.evaluators) wired to the metric registry — the
        # reference's gm->eval(evaluators) path (TrainerInternal.cpp:160)
        from paddle_tpu.trainer import metrics as _metrics_mod
        graph = self.topology.graph
        ev_cfgs = (list(evaluators or [])
                   + list(getattr(graph, "evaluators", None) or []))
        self._host_evals = _metrics_mod.build_from_configs(ev_cfgs)
        needed = {n for _, ins, _ in self._host_evals for n in ins
                  if n in graph.layers}
        missing = needed - set(self.network.shape_infos)
        if missing:
            # evaluator inputs off the loss path (e.g. a maxid decode
            # branch): extend the executed sub-graph to cover them
            self.network = Network(
                graph, outputs=list(graph.output_layer_names)
                + sorted(missing))
            self.topology.network = self.network
        self._eval_layers = sorted(needed)
        self.optimizer = update_equation
        self.mesh = mesh
        # ZeRO-1 sharded optimizer state (optim/zero1.py): disabled until
        # train(zero1=True) / enable_zero1(); the updater replaces the
        # optimizer in the jitted step, everything else is unchanged
        self._zero1 = None
        # full FSDP (optim/zero1.py:FsdpUpdater): disabled until
        # train(fsdp=True) / enable_fsdp(); while active, eligible
        # parameters live flat-packed (N, chunk) sharded 1/N over the
        # mesh's fsdp axis, the step gathers each one per layer on use,
        # and the shard-wise update keeps them sharded (--fsdp,
        # docs/spec_layout.md)
        self._fsdp = None
        # gather-overlap mode for the fsdp step (--fsdp_overlap):
        # True = double-buffer the next layer's all-gather behind the
        # current layer's compute (TPU traces only; the CPU spelling
        # stays sync so audit budgets pin one program), False = sync,
        # "force" = stage the chain on any backend (tests/bench)
        self._fsdp_overlap = True
        self._zero1_subsumed = False  # zero1 asked for while fsdp holds
        # slots at 1/N already; re-armed if fsdp is later disabled
        # pipeline parallelism (parallel/pipeline.py:PipelineTrainPlan):
        # disabled until train(pipeline=...) / enable_pipeline(); while
        # active, body parameters live stage-stacked [S, ...] sharded
        # one stage per pipe slot and the jitted step runs the GPipe
        # schedule (--parallel_nn, ParallelNeuralNetwork.h:23-62)
        self._pipe = None
        self._pipe_head_net = None
        self._pipe_microbatches = None
        self._flat_meta = None  # pre-stacking meta, restored on disable
        self.grad_accum_steps = 1
        self._recompile_warn = recompile_warn
        key = jax.random.PRNGKey(seed)
        self.meta = self.network.param_meta()
        if mesh is not None:
            # the canonical sharding plane (parallel/layout.py): user
            # rules + the sparse-table row-sharding default + the
            # config's per-layer device placement (--parallel_nn) fold
            # into ONE SpecLayout every derivation below queries —
            # init shardings, slot placement, ZeRO-1/FSDP eligibility,
            # and the pipeline's stage-stacked pins (installed via
            # layout.pin in enable_pipeline)
            from paddle_tpu.parallel.layout import SpecLayout
            self.layout = SpecLayout(mesh, self.network.param_specs,
                                     self.topology.graph, shard_rules)
            # alias, not a copy: pipeline pins flow through both names
            self._shard_rules = self.layout.rules
        else:
            self.layout = None
            self._shard_rules = None
        if parameters is not None:
            self.params = (self.layout.place_params(parameters)
                           if mesh is not None else parameters)
        else:
            # with a mesh, create parameters directly in their final
            # sharding (big tables never materialize on one device)
            shardings = (self.layout.param_shardings(
                self.network.param_specs) if mesh is not None else None)
            self.params = self.network.init_params(key, shardings=shardings)
        self.opt_state = self.optimizer.init(self.params, self.meta)
        # StaticPruningHook: masked weights are zero from step 0
        self.params = self.optimizer.prune_params(self.params,
                                                  self.opt_state)
        if mesh is not None:
            # slots/avg follow their owning parameter; scalars replicate
            self.opt_state = self.layout.place_opt_state(self.opt_state)
        # --prev_batch_state truncated BPTT (Trainer.cpp:396-418,
        # Flags.cpp:73): forward recurrent layers start each batch from the
        # previous batch's final state instead of zeros. Gradients are cut
        # at the batch boundary (stop_gradient), the reference's truncated
        # semantics. Reversed layers can't carry (they'd need the future).
        self.prev_batch_state = prev_batch_state
        self._carry_layers = [
            name for name, ld in self.topology.graph.layers.items()
            if ld.type in ("lstmemory", "gated_recurrent", "recurrent",
                           "recurrent_layer_group")
            and not (ld.attrs.get("reversed") or ld.attrs.get("reverse"))
            and name in self.network.order] if prev_batch_state else []
        self._carried = None  # {layer: state}, threaded across batches
        # mixed precision: master params/optimizer state stay float32,
        # forward+backward run in compute_dtype (bfloat16 feeds the MXU at
        # 2x the f32 rate; grads cast back to f32 before the update)
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)
        self._rng = jax.random.PRNGKey(seed + 1)
        # training-health plane (obs/health.py): None until train()
        # arms it (health= kwarg or --show_parameter_stats_period);
        # while armed, _rebuild_train_step pins TWO program variants —
        # stats-off (the hot step, + the sentry scalars when the sentry
        # is armed) and stats-on (the same step with the per-layer stat
        # reduction fused in), each behind its own RecompileGuard
        self._health_cfg = None
        self._health = None
        self._health_param_names = ()
        self._health_act_names = ()
        self._train_step_stats = None
        self.stats_recompile_guard = None
        self._stats_warm_pending = False
        self._rebuild_train_step()
        self._eval_step = self._build_eval_step()
        # (recompile-guard rationale: a ragged corpus with unbucketed
        # shapes silently retraces the step per batch; the guards make
        # that loud — data/prefetch.py:RecompileGuard)
        from paddle_tpu.utils.profiler import StepBreakdown
        # the eval forward thrashes the same way on unbucketed test
        # corpora (graftlint PT104): guard it like the train step
        self.eval_recompile_guard = _prefetch.RecompileGuard(
            self._eval_step, warn_after=recompile_warn, name="eval_step")
        self.breakdown = StepBreakdown()

    def _cast_compute(self, tree):
        if self.compute_dtype is None:
            return tree
        dt = self.compute_dtype
        from paddle_tpu.core.argument import Argument
        from paddle_tpu.data.feeder import ROW_MASK_KEY
        if isinstance(tree, dict) and ROW_MASK_KEY in tree:
            # the row-validity mask is f32 COUNT data like every mask
            # (bf16 saturates at 256 rows) — exempt it by key, the same
            # invariant the structural mask exemption below enforces
            rest = {k: v for k, v in tree.items() if k != ROW_MASK_KEY}
            out = self._cast_compute(rest)
            out[ROW_MASK_KEY] = tree[ROW_MASK_KEY]
            return out

        def cast(x):
            if hasattr(x, "dtype") and x.dtype == jnp.float32:
                return x.astype(dt)
            return x

        def go(x):
            if isinstance(x, Argument):
                # masks are COUNT/index data: summed for token counts and
                # per-row lengths, where bf16 saturates at 256 — they must
                # stay f32. Only values (and carried state) compute in dt.
                # The recursion treats nested Arguments inside state as
                # leaves too, so a mask carried anywhere in state (e.g. a
                # group's state["nested"] Argument, layers/group.py) is
                # exempted structurally — by type, not by key name.
                # The runtime side of graftlint PT102/PT203: a mask that
                # arrives below f32 fails AT TRACE TIME, here, not as a
                # silently saturated denominator steps later.
                assert_mask_f32(x.mask, "_cast_compute")
                return x.replace(
                    value=jax.tree_util.tree_map(cast, x.value),
                    state=jax.tree_util.tree_map(
                        go, x.state,
                        is_leaf=lambda s: isinstance(s, Argument)))
            return cast(x)

        return jax.tree_util.tree_map(
            go, tree, is_leaf=lambda x: isinstance(x, Argument))

    def _cast_params(self, params):
        """``_cast_compute`` of a parameter table, but for the parameters
        whose spec says ``compute_f32`` (an MoE router's weight: its
        scores decide a discontinuous choice): those stay float32."""
        if self.compute_dtype is None:
            return params
        keep = {n for n in params
                if getattr(self.meta.get(n), "compute_f32", False)}
        out = self._cast_compute(
            {n: v for n, v in params.items() if n not in keep})
        out.update((n, params[n]) for n in keep)
        return out

    def _cast_f32(self, tree):
        if self.compute_dtype is None:
            return tree

        def cast(x):
            if hasattr(x, "dtype") and x.dtype == self.compute_dtype:
                return x.astype(jnp.float32)
            return x

        return jax.tree_util.tree_map(cast, tree)

    # ------------------------------------------------------------ builders
    @staticmethod
    def _row_mask(feed):
        """[B] f32 row-validity mask the bucketing feeder emits when it
        pads the batch dim (``data/feeder.py:ROW_MASK_KEY``); None for
        unpadded feeds. Read from the UNCAST feed — like every mask it
        is count data and must stay f32."""
        from paddle_tpu.data.feeder import ROW_MASK_KEY
        arg = feed.get(ROW_MASK_KEY) if feed is not None else None
        return arg.value if arg is not None else None

    def _total_cost(self, outputs, row_mask=None, accum_k=1,
                    total_live=None):
        """Sum of all cost layers' batch-mean — multi-task configs train
        on the sum (the reference's Argument::sum over outArgs). Reduces
        in f32 even under bf16 compute (batch sums need the mantissa).
        ``row_mask`` makes batch-bucket padding exact: dead rows are
        zeroed out of the sum AND out of the denominator, so the loss
        (and its gradient) equals the unpadded batch's.

        Under microbatch gradient accumulation the denominator must be the
        FULL batch's, not this microbatch's, so that summing the k partial
        losses (and their gradients) reproduces the single k×-batch step
        exactly: ``accum_k`` scales the unmasked per-layer denominator and
        ``total_live`` replaces the masked one with the whole batch's live
        row count."""
        total = 0.0
        for n in getattr(self.topology, "cost_names",
                         [self.topology.cost_name]):
            v = outputs[n].value.astype(jnp.float32)
            if row_mask is not None:
                denom = (total_live if total_live is not None
                         else jnp.sum(row_mask))
                rm = row_mask.reshape((-1,) + (1,) * (v.ndim - 1))
                total = total + jnp.sum(v * rm) / jnp.maximum(denom, 1.0)
            else:
                total = total + jnp.sum(v) / (v.shape[0] * accum_k)
        return total

    def _metrics(self, outputs, feed):
        cost_name = self.topology.cost_name
        cdef = self.topology.graph.layers[cost_name]
        row_mask = self._row_mask(feed)
        metrics = {"cost": self._total_cost(outputs, row_mask)}
        if cdef.type in _CLASSIFICATION_COSTS:
            out_l, lab_l = cdef.input_names()[0], cdef.input_names()[1]
            errs, cnt = classification_error(outputs[out_l], outputs[lab_l],
                                             row_mask=row_mask)
            metrics["classification_error"] = (errs, cnt)
        counters = {}
        for a in outputs.values():
            state = getattr(a, "state", None)
            if isinstance(state, dict):
                for name, v in state.get("counters", {}).items():
                    counters.setdefault(name, []).append(v)
        if counters:
            # what layers counted this step, by name (the mean over the
            # layers that report the name), fetched with the cost
            metrics["counters"] = {
                name: jnp.mean(jnp.stack(v).astype(jnp.float32))
                for name, v in counters.items()}
        if self._eval_layers:
            # layer outputs the config-declared evaluators consume; fetched
            # to host once per batch (dict values are skipped by
            # _accumulate's tuple protocol)
            metrics["eval_outputs"] = {
                n: (outputs[n].value, outputs[n].mask)
                if not (isinstance(outputs[n].state, dict)
                        and "ids" in outputs[n].state)
                else (outputs[n].value, outputs[n].mask,
                      outputs[n].state["ids"],
                      outputs[n].state.get("ids_mask"))
                for n in self._eval_layers}
        return metrics

    # ------------------------------------------------- health telemetry
    #: param-table columns (the [P, 6] packed layout — ONE jit output
    #: for the whole table; P separate scalar outputs cost ~30us of
    #: dispatch EACH on the 1-core host, which alone blew the <=5%
    #: overhead budget before packing)
    _HEALTH_PARAM_COLS = ("avg_abs", "max_abs", "norm", "grad_norm",
                          "update_ratio", "touched_rows")

    def _act_stat_table(self, outputs):
        """Per-layer activation (avg_abs, max_abs, live-weight) over
        the executed graph's outputs, packed as ONE [L, 3] array — the
        in-step half
        of ``--show_layer_stat`` (same mask-aware math as the
        standalone ``layer_stats`` jit, fused into the train step
        instead of a second forward). Records the layer-name order on
        the trainer at trace time; returns None when no output is
        inexact."""
        names = [n for n, a in outputs.items()
                 if hasattr(a.value, "dtype")
                 and jnp.issubdtype(a.value.dtype, jnp.inexact)]
        self._health_act_names = tuple(names)
        if not names:
            return None

        def fenced(a):
            # the reductions must read the MATERIALIZED layer outputs:
            # unfenced, XLA duplicates producer computation into the
            # stat consumers (measured ~20 ms/step on the bench model
            # vs ~3 ms for the reductions themselves) — and the fence
            # doubles as the bitwise-neutrality guarantee the param
            # side gets from its own barrier
            value = jax.lax.optimization_barrier(a.value)
            mask = (jax.lax.optimization_barrier(a.mask)
                    if a.mask is not None else None)
            return a.replace(value=value, mask=mask)

        rows = [jnp.stack([jnp.asarray(s, jnp.float32)
                           for s in _arg_abs_stats(fenced(outputs[n]))])
                for n in names]
        return jnp.stack(rows)

    @staticmethod
    def _poison_grads(grads, poison):
        """Chaos ``step_stats`` corrupt trigger: NaN into element 0 of
        the first (sorted) gradient leaf when ``poison > 0``. With
        ``poison == 0`` the ``.at[0].set`` writes the element's own
        value back — a bitwise no-op — so ONE compiled program serves
        both the poisoned and the clean step and the fault stays
        deterministic in the plan seed."""
        if poison is None:
            return grads
        name = sorted(grads)[0]
        g = grads[name]
        flat = g.reshape((-1,))
        bad = jnp.asarray(jnp.nan, flat.dtype)
        flat = flat.at[0].set(jnp.where(poison > 0, bad, flat[0]))
        out = dict(grads)
        out[name] = flat.reshape(g.shape)
        return out

    def _health_metrics(self, loss, params, grads, new_params, new_opt,
                        num_passes, act_table, with_stats):
        """The in-step training-health reduction (obs/health.py owns
        the host side). Returns extra metrics entries:

        - ``sentry`` (when the sentry is armed): the per-step
          finiteness+threshold scalars — ``trip``, the global
          ``grad_absmax``, and a [P] per-parameter grad-absmax vector
          (fetched only on a trip, for the postmortem bundle).
        - ``health`` (stats-on variant only): a packed [P, 6]
          per-parameter table (columns ``_HEALTH_PARAM_COLS``) plus
          the [L, 3] activation table — packed because P+L separate
          scalar outputs cost more in dispatch than the reductions
          themselves on the 1-core host.
        - ``health_lr``: the step's effective base learning rate.

        Name order rides ``self._health_param_names`` /
        ``self._health_act_names``, recorded at trace time (static
        per program variant).

        Everything reduces from ``optimization_barrier``-fenced views
        of params/grads/new_params so XLA cannot fuse the stat
        reductions back into the update path's producers — the
        stats-on and stats-off programs must round the TRAINED values
        identically (the bitwise-neutrality matrix,
        tests/test_health_matrix.py, is the enforcement)."""
        cfg = self._health_cfg
        out: Dict[str, Any] = {}
        if cfg is None or not cfg.armed:
            return out
        p_b, g_b, np_b = jax.lax.optimization_barrier(
            (params, grads, new_params))
        names = sorted(p_b)
        self._health_param_names = tuple(names)
        loss_f = jnp.asarray(loss, jnp.float32)
        if cfg.sentry:
            per = jnp.stack([jnp.max(jnp.abs(g_b[n])).astype(jnp.float32)
                             for n in names]) if names \
                else jnp.zeros((0,), jnp.float32)
            gmax = (jnp.max(per) if names
                    else jnp.zeros((), jnp.float32))
            trip = ~jnp.isfinite(loss_f) | ~jnp.isfinite(gmax)
            if cfg.grad_threshold > 0:
                trip = trip | (gmax > cfg.grad_threshold)
            out["sentry"] = {"trip": trip, "grad_absmax": gmax,
                             "layer_grad_absmax": per}
        opt = self.optimizer
        ns = (new_opt.get("num_samples")
              if isinstance(new_opt, dict) else None)
        if ns is not None and hasattr(opt, "learning_rate"):
            from paddle_tpu.optim.schedules import learning_rate_at
            out["health_lr"] = learning_rate_at(
                getattr(opt, "learning_rate_schedule", "constant"),
                opt.learning_rate,
                getattr(opt, "learning_rate_decay_a", 0.0),
                getattr(opt, "learning_rate_decay_b", 0.0), ns,
                args=getattr(opt, "learning_rate_args", ""),
                num_passes=num_passes)
        if with_stats:
            def l2(x):
                return jnp.sqrt(jnp.sum(
                    jnp.square(x.astype(jnp.float32))))

            nan = jnp.asarray(jnp.nan, jnp.float32)
            rows = []
            for n in names:
                p = p_b[n]
                g = g_b.get(n)
                npv = np_b.get(n)
                pn = l2(p)
                row = [jnp.mean(jnp.abs(p)).astype(jnp.float32),
                       jnp.max(jnp.abs(p)).astype(jnp.float32), pn]
                row.append(l2(g) if g is not None else nan)
                row.append(l2(npv - p) / jnp.maximum(pn, 1e-12)
                           if npv is not None else nan)
                if g is not None and g.ndim >= 2 \
                        and self.optimizer._is_sparse(self.meta.get(n)):
                    # sparse tables: rows this batch touched (the
                    # reference's per-row update bookkeeping made
                    # observable); -1 marks the non-sparse rows the
                    # host drops
                    row.append(jnp.sum(jnp.any(
                        g != 0, axis=tuple(range(1, g.ndim))
                    ).astype(jnp.float32)))
                else:
                    row.append(jnp.asarray(-1.0, jnp.float32))
                rows.append(jnp.stack(row))
            out["health"] = {
                "param_table": (jnp.stack(rows) if rows
                                else jnp.zeros((0, 6), jnp.float32)),
                "act_table": (act_table if act_table is not None
                              else jnp.zeros((0, 3), jnp.float32)),
            }
        return out

    def _apply_skip_select(self, health, params, opt_state, new_params,
                           new_opt):
        """``skip_batch`` policy, in-graph: a tripped sentry discards
        the whole update — params, optimizer slots AND schedule
        counters revert to the step's inputs — so the post-skip
        trajectory is bitwise the run that never saw the batch (the
        host side rolls the RNG split back). Donation-safe: the
        selects read the donated inputs elementwise, which XLA
        resolves with copies only where aliasing actually needs
        them."""
        cfg = self._health_cfg
        sentry = health.get("sentry") if health else None
        if sentry is None or cfg.policy != "skip_batch":
            return new_params, new_opt
        # ONE cond over the whole state, not a per-leaf where: the
        # untripped (hot) branch must not pay an elementwise select
        # over every param + slot (~10 ms/step of pure memory traffic
        # on the 1-core CPU host — the difference between passing and
        # blowing the <=5% overhead budget). The moving-stat merge keys
        # of new_params are a superset-safe dict: revert those to the
        # step's input params too.
        old_params = {k: params[k] for k in new_params}
        return jax.lax.cond(
            sentry["trip"],
            lambda: (old_params, opt_state),
            lambda: (new_params, new_opt))

    def _accum_k_for(self, batch_size: int) -> int:
        """Effective accumulation factor for one batch shape. The FIRST
        batch shape must be divisible by ``grad_accum_steps`` — a k the
        run's dominant batch size can't honor is a config error, raised
        before any training happens (a silent gcd there would quietly run
        at full activation memory, the OOM the flag exists to avoid).
        Once a conforming shape has been seen, a LATER shape k doesn't
        divide (the dataset-tail partial batch) must not abort a nearly-
        finished pass: accumulation is a memory knob, not a math knob, so
        that batch scans gcd(k, B) fewer (larger) microbatches, with a
        warning."""
        import math
        if batch_size % self.grad_accum_steps == 0:
            self._accum_shape_seen = True
            return self.grad_accum_steps
        if not getattr(self, "_accum_shape_seen", False):
            raise ValueError(
                f"grad_accum_steps={self.grad_accum_steps} does not divide "
                f"the batch size ({batch_size} rows): pick a k that "
                "divides the reader's batch size (or bucket batches with "
                "DataFeeder batch_buckets)")
        k = math.gcd(self.grad_accum_steps, batch_size)
        from paddle_tpu.utils import logger
        logger.warning(
            "grad_accum_steps=%d does not divide this batch's %d rows (a "
            "final partial batch) — using %d microbatches for this shape; "
            "bucket batch sizes (DataFeeder batch_buckets) or drop the "
            "remainder batch to keep k uniform",
            self.grad_accum_steps, batch_size, k)
        return k

    def _split_microbatches(self, feed, k: int):
        """Reshape every feed leaf (B, ...) -> (k, B/k, ...) for the
        ``lax.scan`` over microbatches; on a mesh the microbatch dim keeps
        the batch sharding (dim 1 over the data axes) so each scan slice
        is exactly a smaller sharded batch."""
        n_data = (mesh_lib.data_parallel_degree(self.mesh)
                  if self.mesh is not None else 1)

        def split(x):
            if not hasattr(x, "shape") or x.ndim == 0 or x.shape[0] % k:
                raise ValueError(
                    f"grad_accum_steps={k} must divide the batch dim of "
                    f"every feed entry; got shape "
                    f"{getattr(x, 'shape', None)}")
            y = x.reshape((k, x.shape[0] // k) + x.shape[1:])
            if self.mesh is not None and (x.shape[0] // k) % n_data == 0:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(self.mesh,
                                     P(None, mesh_lib.batch_axes(self.mesh))))
            return y

        return jax.tree_util.tree_map(split, feed)

    def _jit_step(self, step):
        """``jax.jit`` of a train step (params and optimizer state
        donated) whose whole trace — forward, backward, optimizer update
        — is declared partitioned over this trainer's mesh, which is how
        the Pallas kernels inside know to run per device
        (``ops/common.py:step_mesh``)."""
        from paddle_tpu.ops import common as kernel_common

        @functools.wraps(step)
        def traced(*args, **kwargs):
            with kernel_common.step_mesh(self.mesh):
                return step(*args, **kwargs)

        return jax.jit(traced, donate_argnums=(0, 1))

    def _build_pipe_step(self, with_stats=False):
        """The pipelined train step: body forward through the GPipe
        schedule (``PipelineTrainPlan.fwd`` — a shard_map'd scan whose
        ``jax.grad`` is the reverse-order backward pipeline), cost head
        replicated on the gathered body output, ONE optimizer update on
        the whole-batch gradient. Loss math is identical to the
        unpipelined step's (same denominators, same clip/decay point), so
        the step is gradient-exact on deterministic bodies — pinned by
        tests/test_pipeline_train.py. ``with_stats`` fuses the
        training-health stat reduction in (``_health_metrics``; the
        activation stats cover the head layers + gathered body output —
        the fetched surface of the pipelined graph)."""
        import math

        from paddle_tpu.core.argument import Argument
        plan = self._pipe
        head_net = self._pipe_head_net
        updater = self._fsdp or self._zero1 or self.optimizer
        fsdp = self._fsdp
        meta = self.meta
        cost_name = self.topology.cost_name
        body_names = list(plan.body_param_names())
        M_cfg = self._pipe_microbatches
        n_data = mesh_lib.data_parallel_degree(self.mesh)

        def step(params, opt_state, feed, rng, num_passes, carried=None,
                 poison=None):
            del carried  # rejected at enable time (no prev_batch_state)
            B = next(iter(feed.values())).value.shape[0]
            b_loc = B // n_data
            m_eff = math.gcd(M_cfg, b_loc)  # trace-time constant
            if m_eff != M_cfg:
                from paddle_tpu.utils import logger
                logger.warning(
                    "pipeline: %d microbatches do not divide the "
                    "per-device batch (%d rows) — using %d for this "
                    "shape (bubble fraction rises to %.3f)",
                    M_cfg, b_loc, m_eff,
                    (plan.S - 1) / (plan.S + m_eff - 1))
            fwd = plan.fwd(m_eff, train=True)

            def loss_fn(params, feed, rng):
                if fsdp is not None:
                    # gather-on-use: head parameters rebuild per layer
                    # from their fsdp shards (stage-stacked body keys
                    # are excluded from the plan by their P(pipe) pins)
                    params = fsdp.full_params(params)
                cast_params = self._cast_params(params)
                cast_feed = self._cast_compute(feed)
                x = cast_feed[plan.body_in].value
                body = {k: cast_params[k] for k in body_names}
                y = fwd(body, x, rng)
                head_feed = dict(cast_feed)
                head_feed[plan.body_out] = Argument(value=y)
                outputs, updates = head_net.apply_with_state(
                    cast_params, head_feed, train=True, rng=rng,
                    mesh=self.mesh)
                return (self._total_cost(outputs, self._row_mask(feed)),
                        (outputs, updates))

            (loss, (outputs, updates)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, feed, rng)
            grads = self._poison_grads(grads, poison)
            updates = self._cast_f32(updates)
            row_mask = self._row_mask(feed)
            bsz = (jnp.sum(row_mask) if row_mask is not None
                   else outputs[cost_name].value.shape[0])
            new_params, new_opt = updater.update(
                grads, opt_state, params, meta, batch_size=bsz,
                num_passes=num_passes)
            new_params.update(updates)
            health = self._health_metrics(
                loss, params, grads, new_params, new_opt, num_passes,
                self._act_stat_table(outputs) if with_stats else None,
                with_stats)
            new_params, new_opt = self._apply_skip_select(
                health, params, opt_state, new_params, new_opt)
            metrics = self._metrics(outputs, feed)
            metrics.update(health)
            return new_params, new_opt, metrics

        return self._jit_step(step)

    def _build_train_step(self, with_stats=False):
        if self._pipe is not None:
            # the schedule's microbatching subsumes grad_accum_steps
            # (absorbed in enable_pipeline); accum/carry paths don't apply
            return self._build_pipe_step(with_stats=with_stats)
        network, optimizer, meta = self.network, self.optimizer, self.meta
        # the ZeRO-1/FSDP updaters are drop-ins for the optimizer's
        # update protocol (optim/zero1.py); everything upstream of the
        # update — forward, backward, metrics — is shared. Under FSDP
        # the loss_fn additionally rebuilds each planned parameter from
        # its shards (full_params: one all-gather per layer) before the
        # forward, and the gradients flow back INTO the packed layout.
        updater = self._fsdp or self._zero1 or self.optimizer
        fsdp = self._fsdp
        accum_k = self.grad_accum_steps
        cost_name = self.topology.cost_name
        carry_layers = self._carry_layers
        # gradient_printer evaluators need d(cost)/d(layer output) FOR THE
        # BATCH BEING STEPPED (the reference prints Argument.grad during
        # that batch's backward). Probes ride the SAME backward pass, so
        # the printed grads belong to the pre-update parameters — a lazy
        # recompute after the update would be one step stale (and
        # pre-update params can't be kept around: they're donated).
        grad_watch = sorted({
            n for e, ins, _ in self._host_evals
            if getattr(e, "wants_grad", False) for n in ins
            if n in self.network.shape_infos})

        def loss_fn(params, feed, rng, carried, probes=None):
            if fsdp is not None:
                params = fsdp.full_params(params)
            outputs, updates = network.apply_with_state(
                self._cast_params(params), self._cast_compute(feed),
                train=True, rng=rng, carried=carried, probes=probes,
                mesh=self.mesh)
            return (self._total_cost(outputs, self._row_mask(feed)),
                    (outputs, updates))

        def step(params, opt_state, feed, rng, num_passes, carried=None,
                 poison=None):
            if carried is not None:
                # truncated BPTT: no gradient across the batch boundary
                carried = jax.lax.stop_gradient(carried)
            probe_grads = None
            if grad_watch:
                shapes = jax.eval_shape(
                    lambda p: loss_fn(p, feed, rng, carried)[1][0], params)
                probes = {n: jnp.zeros(shapes[n].value.shape,
                                       shapes[n].value.dtype)
                          for n in grad_watch}
                (loss, (outputs, updates)), (grads, probe_grads) = \
                    jax.value_and_grad(loss_fn, argnums=(0, 4),
                                       has_aux=True)(
                        params, feed, rng, carried, probes)
            else:
                (loss, (outputs, updates)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, feed, rng, carried)
            grads = self._poison_grads(grads, poison)
            # grads are already f32 (cotangents take the f32 params' dtype);
            # only the moving-stat updates computed in bf16 need casting
            updates = self._cast_f32(updates)
            row_mask = self._row_mask(feed)
            # LIVE rows drive the lr schedule's sample count, not the
            # padded shape (sum_gradients scaling likewise)
            bsz = (jnp.sum(row_mask) if row_mask is not None
                   else outputs[cost_name].value.shape[0])
            new_params, new_opt = updater.update(
                grads, opt_state, params, meta, batch_size=bsz,
                num_passes=num_passes)
            new_params.update(updates)  # moving statistics (batch_norm)
            health = self._health_metrics(
                loss, params, grads, new_params, new_opt, num_passes,
                self._act_stat_table(outputs) if with_stats else None,
                with_stats)
            new_params, new_opt = self._apply_skip_select(
                health, params, opt_state, new_params, new_opt)
            metrics = self._metrics(outputs, feed)
            metrics.update(health)
            if carry_layers:
                graph = self.topology.graph

                def final_state(n):
                    s = outputs[n].state
                    # a recurrent group's .state also holds extra outputs;
                    # only its final scan carry crosses the batch boundary
                    if graph.layers[n].type == "recurrent_layer_group":
                        return s["final"]
                    return s

                metrics["carried"] = jax.lax.stop_gradient(
                    {n: final_state(n) for n in carry_layers})
            if probe_grads is not None:
                metrics["probe_grads"] = {
                    n: g.astype(jnp.float32)
                    for n, g in probe_grads.items()}
            return new_params, new_opt, metrics

        def accum_step(params, opt_state, feed, rng, num_passes,
                       carried=None, poison=None):
            """Microbatch gradient accumulation: ``lax.scan`` over k
            equal slices of the batch, one forward+backward per slice (so
            only one microbatch's activations are ever live), gradients
            SUMMED with full-batch denominators baked into each partial
            loss — the sum is exactly the single k×-batch step's mean
            gradient. Clipping/decay/schedules then run ONCE, inside the
            optimizer, on that accumulated gradient."""
            del carried  # rejected in _configure_step (truncated-BPTT
            # state cannot cross microbatches of disjoint rows)
            row_mask_full = self._row_mask(feed)
            total_live = (jnp.sum(row_mask_full)
                          if row_mask_full is not None else None)
            full_bsz = next(iter(feed.values())).value.shape[0]
            # trace-time constant: a partial tail batch k doesn't divide
            # scans fewer microbatches instead of aborting the pass
            k_eff = self._accum_k_for(full_bsz)
            micro_feed = self._split_microbatches(feed, k_eff)
            rngs = jax.random.split(rng, k_eff)

            def loss_micro(params, mfeed, mrng):
                if fsdp is not None:
                    # per-microbatch gather: the scan body re-gathers,
                    # so only one microbatch's full params are live
                    params = fsdp.full_params(params)
                outputs, updates = network.apply_with_state(
                    self._cast_params(params), self._cast_compute(mfeed),
                    train=True, rng=mrng, mesh=self.mesh)
                return (self._total_cost(outputs, self._row_mask(mfeed),
                                         accum_k=k_eff,
                                         total_live=total_live),
                        (outputs, updates))

            def micro(g_acc, xs):
                mfeed, mrng = xs
                (loss, (outputs, updates)), grads = jax.value_and_grad(
                    loss_micro, has_aux=True)(params, mfeed, mrng)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
                acts = (self._act_stat_table(outputs)
                        if with_stats else None)
                return g_acc, (loss, self._cast_f32(updates),
                               self._metrics(outputs, mfeed),
                               acts if acts is not None
                               else jnp.zeros((0, 3), jnp.float32))

            g_zero = jax.tree_util.tree_map(jnp.zeros_like, params)
            grads, (losses, updates_k, metrics_k, acts_k) = jax.lax.scan(
                micro, g_zero, (micro_feed, rngs))
            grads = self._poison_grads(grads, poison)
            # moving statistics (batch_norm): mean over microbatches —
            # for equal-size unmasked microbatches this IS the k×-batch
            # update (the EMA is affine in the batch mean)
            updates = jax.tree_util.tree_map(
                lambda x: jnp.mean(x, axis=0), updates_k)
            # partial losses already carry full-batch denominators: the
            # sum is the k×-batch cost
            metrics = {"cost": jnp.sum(losses)}
            for key, val in metrics_k.items():
                if key == "cost":
                    continue
                if isinstance(val, tuple):
                    # (sum, count) accumulator pairs: sum the k partials
                    metrics[key] = tuple(jnp.sum(x, axis=0) for x in val)
                elif key == "eval_outputs":
                    # per-row fetches: merge (k, b, ...) back to (B, ...)
                    # — bucket-padded dead rows sat at the end of the
                    # batch and end up at the end again, so the host-side
                    # live-prefix slice stays exact
                    metrics[key] = jax.tree_util.tree_map(
                        lambda x: x.reshape((-1,) + x.shape[2:]), val)
                elif key == "counters":
                    metrics[key] = jax.tree_util.tree_map(
                        lambda x: jnp.mean(x, axis=0), val)
            bsz = total_live if total_live is not None else full_bsz
            new_params, new_opt = updater.update(
                grads, opt_state, params, meta, batch_size=bsz,
                num_passes=num_passes)
            new_params.update(updates)
            act_table = None
            if with_stats and acts_k.shape[1] > 0:
                # (k, L, 3)-stacked per-microbatch tables -> the
                # whole-batch view: max over microbatches is exact,
                # and the avg reweights each micro's masked mean by
                # its live-element count — the whole-batch masked mean
                # even when padded rows land unevenly across
                # microbatches (a plain mean-of-means would bias it)
                w = acts_k[:, :, 2]
                w_tot = jnp.maximum(jnp.sum(w, axis=0), 1.0)
                act_table = jnp.stack(
                    [jnp.sum(acts_k[:, :, 0] * w, axis=0) / w_tot,
                     jnp.max(acts_k[:, :, 1], axis=0), w_tot], axis=1)
            health = self._health_metrics(
                metrics["cost"], params, grads, new_params, new_opt,
                num_passes, act_table, with_stats)
            new_params, new_opt = self._apply_skip_select(
                health, params, opt_state, new_params, new_opt)
            metrics.update(health)
            return new_params, new_opt, metrics

        return self._jit_step(accum_step if accum_k > 1 else step)

    def _build_eval_step(self):
        network = self.network

        def step(params, feed):
            # under the pipeline the params arrive stage-stacked; the
            # eval forward runs the plain (unpipelined) graph on the flat
            # view — jnp slicing, free at trace time
            outputs = network.apply(
                self._cast_params(self._flat_params_view(params)),
                self._cast_compute(feed), train=False,
                mesh=self.mesh)
            return self._metrics(outputs, feed)

        return jax.jit(step)

    # ---------------------------------------------------------------- loop
    def enable_zero1(self):
        """Switch to the ZeRO-1 sharded optimizer update
        (``optim/zero1.py``): optimizer slots reshard to each device's 1/N
        partition of the data axis, the jitted step updates shard-wise and
        all-gathers the parameters. Bit-exact vs the replicated path; a
        no-op (with a warning) when there is no data-parallel axis to
        partition over. Parameters and the ``swig_api`` surface are
        untouched — only optimizer state changes layout."""
        if self._zero1 is not None:
            return
        from paddle_tpu.utils import logger
        if self._fsdp is not None:
            # subsumption, not composition-by-negotiation: the FSDP
            # updater already holds every planned slot at 1/N over the
            # fsdp axis — remember the request so disabling fsdp later
            # re-arms the plain zero1 layout instead of silently
            # dropping it
            logger.info(
                "zero1 requested with FSDP active — already subsumed "
                "(the fsdp updater partitions optimizer slots 1/N over "
                "the fsdp axis alongside the parameters)")
            self._zero1_subsumed = True
            return
        if self.mesh is None or mesh_lib.data_parallel_degree(self.mesh) <= 1:
            logger.warning(
                "zero1 requested but the mesh has no data-parallel axis "
                "to partition optimizer state over (mesh=%s) — keeping "
                "the replicated update", self.mesh)
            return
        from paddle_tpu.optim.zero1 import Zero1Updater
        self._zero1 = Zero1Updater(self.optimizer, self.mesh, self.params,
                                   self.meta, rules=self._shard_rules)
        self.opt_state = self._zero1.convert_state(self.opt_state)
        self._rebuild_train_step()

    def disable_zero1(self):
        """Back to the replicated update: gather the sharded slots to
        their full shapes, restore the rule-driven placement
        (``SpecLayout.place_opt_state``), drop the updater, rebuild the
        step. The inverse of :meth:`enable_zero1`, so A/B comparisons
        on one SGD instance measure what they claim to."""
        self._zero1_subsumed = False
        if self._zero1 is None:
            return
        self.opt_state = self._zero1.gather_opt_state(self.opt_state)
        self._zero1 = None
        if self.mesh is not None:
            self.opt_state = self.layout.place_opt_state(self.opt_state)
        self._rebuild_train_step()

    # ---------------------------------------------------------------- fsdp
    def enable_fsdp(self, overlap=None) -> bool:
        """Switch to full FSDP (``--fsdp``,
        ``optim/zero1.py:FsdpUpdater``): eligible parameters AND their
        optimizer slots reshard to flat-packed 1/N partitions of the
        mesh's ``fsdp`` axis, the jitted step gathers each parameter
        per layer on use, and the shard-wise update keeps everything
        sharded — a model ~N× one device's memory trains on an N-way
        fsdp axis. Eligibility comes from the canonical layout
        (``SpecLayout.fsdp_eligible``), so model-sharded tables and
        pipeline stage-stacked keys keep their own placement and the
        modes compose. ``overlap`` (``--fsdp_overlap``) picks the
        gather spelling: True (default) double-buffers the next
        parameter's all-gather behind the current layer's compute in
        the SpecLayout prefetch order, False keeps every gather
        synchronous, "force" stages the chain on any backend; None
        keeps the trainer's current mode. Returns True when FSDP is
        active; meshes without an fsdp axis (and models with model
        averaging) WARN and stand down — training continues with the
        replicated layout."""
        if overlap is not None:
            self._fsdp_overlap = overlap
        if self._fsdp is not None:
            if overlap is not None and \
                    self._fsdp.overlap_mode != self._fsdp_overlap:
                # same plan, different gather spelling: rebuild the
                # updater (cheap, no device ops) and re-jit
                self.disable_fsdp(_rearm_subsumed=False)
            else:
                return True
        from paddle_tpu.utils import logger
        if self.mesh is None or \
                dict(self.mesh.shape).get(mesh_lib.FSDP_AXIS, 1) <= 1:
            logger.warning(
                "fsdp requested but the mesh has no %r axis to "
                "partition parameters over (mesh=%s) — keeping the "
                "replicated parameter layout; build one with "
                "create_mesh(n_fsdp=N)", mesh_lib.FSDP_AXIS,
                dict(self.mesh.shape) if self.mesh is not None else None)
            return False
        if "avg" in self.opt_state:
            logger.warning(
                "fsdp requested but model averaging ('avg' optimizer "
                "state) is consumed whole at eval/save time and is not "
                "packed — keeping the replicated parameter layout")
            return False
        # zero1 composes by subsumption: unwind its batch-axis slot
        # layout first; the fsdp updater repartitions the same slots
        # over the fsdp axis next to their parameters
        if self._zero1 is not None:
            self.disable_zero1()
            self._zero1_subsumed = True
        from paddle_tpu.optim.zero1 import FsdpUpdater
        upd = FsdpUpdater(self.optimizer, self.mesh, self.params,
                          self.meta, rules=self._shard_rules,
                          overlap=self._fsdp_overlap, graph=self.network)
        self.params = upd.pack_params(self.params)
        self.opt_state = upd.convert_state(self.opt_state)
        self._fsdp = upd
        self.breakdown.set_fsdp(len(upd.plan), bool(upd.overlap_mode))
        logger.info(
            "fsdp enabled: %d parameters packed 1/%d over the %r axis "
            "(gather-on-use per layer, overlap=%s; slots follow)",
            len(upd.plan), upd.n, mesh_lib.FSDP_AXIS, upd.overlap_mode)
        self._rebuild_train_step()
        return True

    def disable_fsdp(self, _rearm_subsumed: bool = True):
        """Back to the replicated parameter layout: unpack every planned
        parameter and slot to full shapes, restore the rule-driven
        placement, drop the updater — and re-arm plain ZeRO-1 when it
        was subsumed by :meth:`enable_fsdp`. The inverse of
        ``enable_fsdp``, so A/B runs and checkpoint crossings measure
        what they claim to. ``_rearm_subsumed=False`` is the pipeline
        toggle's private spelling: fsdp re-enables right after the
        restack and re-subsumes directly, so the intermediate ZeRO-1
        repack/gather round trips of the whole slot state would be
        pure churn."""
        if self._fsdp is None:
            return
        self.opt_state = self._fsdp.gather_opt_state(self.opt_state)
        self.params = self._fsdp.unpack_params(self.params)
        resub, self._zero1_subsumed = self._zero1_subsumed, False
        self._fsdp = None
        self.breakdown.set_fsdp(0, False)
        if self.mesh is not None:
            self.params = self.layout.place_params(self.params)
            self.opt_state = self.layout.place_opt_state(self.opt_state)
        if resub and _rearm_subsumed:
            self.enable_zero1()
        self._rebuild_train_step()

    def _rebuild_train_step(self):
        self._train_step = self._build_train_step()
        self.recompile_guard = _prefetch.RecompileGuard(
            self._train_step, warn_after=self._recompile_warn)
        cfg = self._health_cfg
        if cfg is not None and cfg.period > 0:
            # the stats-on program variant: the SAME step with the
            # per-layer stat reduction fused in, pinned + guarded like
            # the hot variant; the loop warms it on the first batch so
            # no compile lands mid-run (warmed once, then zero growth)
            self._train_step_stats = self._build_train_step(
                with_stats=True)
            self.stats_recompile_guard = _prefetch.RecompileGuard(
                self._train_step_stats, warn_after=self._recompile_warn,
                name="train_step_stats")
            self._stats_warm_pending = True
        else:
            self._train_step_stats = None
            self.stats_recompile_guard = None
            self._stats_warm_pending = False

    # ------------------------------------------------------------ pipeline
    def enable_pipeline(self, microbatches: Optional[int] = None) -> bool:
        """Switch to the pipelined train step (``--parallel_nn``): stages
        derive from the config's per-layer ``device`` attrs
        (``parallel/pipeline.py:split_pipeline_graph``), body parameters
        and optimizer slots restructure to stage-stacked arrays sharded
        one stage per ``pipe`` mesh slot, and the jitted step runs the
        GPipe microbatch schedule with the cost head replicated on the
        body output. Gradient-exact vs the unpipelined step (full-batch
        denominators, clipping/decay once on the whole-batch gradient).

        Returns True when pipelining is active. Any config/mesh shape the
        schedule cannot honor WARNS and stands down (returns False,
        training continues unpipelined) — the reference's --parallel_nn
        likewise degrades to single-device execution when the config pins
        nothing."""
        from paddle_tpu.parallel.pipeline import PipelineTrainPlan
        from paddle_tpu.utils import logger
        if self._pipe is not None:
            if microbatches and microbatches != self._pipe_microbatches:
                self._pipe_microbatches = int(microbatches)
                self.breakdown.set_pipeline(self._pipe.S,
                                            self._pipe_microbatches)
                self._rebuild_train_step()
            return True

        def stand_down(msg, *args):
            logger.warning(
                "pipeline requested but " + msg +
                " — keeping the unpipelined step", *args)
            return False

        if self.mesh is None \
                or mesh_lib.PIPE_AXIS not in self.mesh.axis_names:
            return stand_down(
                "the mesh has no %r axis (mesh=%s); build one with "
                "create_mesh(n_pipe=<n_stages>)", mesh_lib.PIPE_AXIS,
                dict(self.mesh.shape) if self.mesh is not None else None)
        if self._carry_layers:
            return stand_down(
                "prev_batch_state carries recurrent state across batches; "
                "the pipeline scan cannot thread it")
        if "avg" in self.opt_state:
            return stand_down(
                "model averaging ('avg' optimizer state) is consumed "
                "whole at eval/save time and is not stage-stacked")
        if any(getattr(e, "wants_grad", False)
               for e, _, _ in self._host_evals):
            return stand_down(
                "gradient_printer evaluators probe layer-output gradients "
                "inside the body; probes do not thread through the "
                "pipeline scan")
        try:
            plan = PipelineTrainPlan(
                self.topology.graph, self.network, self.params, self.meta,
                self.mesh, mesh_lib.PIPE_AXIS,
                n_microbatches=microbatches)
        except ValueError as e:
            return stand_down("the config cannot pipeline: %s", e)
        head_set = set(plan.head)
        missing_cost = [c for c in self.topology.cost_names
                        if c not in head_set]
        if missing_cost:
            return stand_down(
                "cost layers %s carry device attrs (staged); the loss is "
                "not part of the repeated block — leave cost layers "
                "unpinned", missing_cost)
        off_head = [n for n in self._eval_layers
                    if n not in head_set and n != plan.body_out]
        if off_head:
            return stand_down(
                "evaluator inputs %s live inside the pipeline body; only "
                "the body output and head layers are fetched", off_head)
        ruled = [n for n in plan.body_pnames
                 if mesh_lib.rule_for(n, self._shard_rules) != P_spec()]
        if ruled:
            return stand_down(
                "body parameters %s carry shard rules; a stage owns its "
                "parameters whole (shard the head instead)", ruled[:3])
        sparse = [n for n in plan.body_pnames
                  if self.optimizer._is_sparse(self.meta.get(n))]
        if sparse:
            return stand_down(
                "body parameters %s take the sparse lazy update (per-row "
                "t_rows bookkeeping is not stage-stackable)", sparse[:3])

        # ZeRO-1/FSDP must wrap the STACKED layout: unwind them first,
        # re-arm after (their plans exclude the stacked keys via the
        # pipe pins the layout carries, and keep partitioning the
        # replicated head over their own axes). A SUBSUMED zero1 is
        # remembered, not re-armed: fsdp re-enables right after the
        # restack and subsumes it again — re-arming in between would
        # repack+gather the whole slot state twice for nothing.
        refsdp = self._fsdp is not None
        resub = refsdp and self._zero1_subsumed
        if refsdp:
            self.disable_fsdp(_rearm_subsumed=False)
        rezero = self._zero1 is not None
        if rezero:
            self.disable_zero1()
        needed = list(dict.fromkeys(
            list(self.topology.cost_names) + list(self._eval_layers)))
        self._pipe_head_net = plan.build_head_net(needed)
        self.params = plan.stack_params(self.params)
        self.opt_state = plan.stack_opt_state(self.opt_state)
        self._flat_meta = self.meta
        self.meta = plan.stacked_meta(self.meta)
        # the stage-stacked pins enter the CANONICAL layout, so every
        # downstream derivation (slot placement, ZeRO-1/FSDP
        # eligibility, PT505 hygiene) sees them through one table
        self.layout.pin(plan.shard_rules())
        self._pipe = plan
        if microbatches:
            self._pipe_microbatches = int(microbatches)
        elif self.grad_accum_steps > 1:
            # the pipeline's microbatching IS the gradient accumulation
            # (full-batch denominators, one clip/decay): absorb the knob
            logger.info(
                "pipeline: grad_accum_steps=%d absorbed as the microbatch "
                "count (the schedule accumulates per-microbatch gradients "
                "with full-batch denominators)", self.grad_accum_steps)
            self._pipe_microbatches = self.grad_accum_steps
        else:
            self._pipe_microbatches = plan.M  # plan default: M = S
        self.breakdown.set_pipeline(plan.S, self._pipe_microbatches)
        logger.info(
            "pipeline enabled: %d stages over the %r axis, %d "
            "microbatches, %s layout (bubble fraction %.3f)",
            plan.S, mesh_lib.PIPE_AXIS, self._pipe_microbatches,
            "stage-stacked" if plan.identical else
            "heterogeneous (replicated params)",
            (plan.S - 1) / (plan.S + self._pipe_microbatches - 1))
        if rezero:
            self.enable_zero1()
        if refsdp:
            self.enable_fsdp()
            self._zero1_subsumed = self._zero1_subsumed or resub
        self._rebuild_train_step()
        return True

    def disable_pipeline(self):
        """Back to the unpipelined step: unstack body parameters and
        slots to their flat per-stage names, restore rule-driven
        placement and the flat meta. The inverse of
        :meth:`enable_pipeline`, so resume and A/B runs cross pipeline
        on/off freely."""
        if self._pipe is None:
            return
        refsdp = self._fsdp is not None
        resub = refsdp and self._zero1_subsumed
        if refsdp:
            self.disable_fsdp(_rearm_subsumed=False)
        rezero = self._zero1 is not None
        if rezero:
            self.disable_zero1()
        plan = self._pipe
        self.layout.unpin(plan.shard_rules())
        self.params = plan.unstack_params(self.params)
        self.opt_state = plan.unstack_opt_state(self.opt_state)
        self.meta = self._flat_meta or self.meta
        self._flat_meta = None
        if self.mesh is not None:
            self.params = self.layout.place_params(self.params)
            self.opt_state = self.layout.place_opt_state(self.opt_state)
        self._pipe = None
        self._pipe_head_net = None
        self.breakdown.set_pipeline(0, 0)
        if rezero:
            self.enable_zero1()
        if refsdp:
            self.enable_fsdp()
            self._zero1_subsumed = self._zero1_subsumed or resub
        self._rebuild_train_step()

    def _flat_params_view(self, params=None):
        """Full flat view of the live params — fsdp-packed leaves
        gathered back to their parameter shapes and stage-stacked
        arrays unstacked to flat per-stage names. jnp ops, so it works
        both eagerly and under a trace; identity when neither mode is
        on. Eval, forward, merge, checkgrad and serving all read the
        model through this one view."""
        params = self.params if params is None else params
        if self._fsdp is not None:
            params = self._fsdp.unpack_params(params)
        if self._pipe is not None:
            params = self._pipe.unstack_params(params)
        return params

    def _configure_step(self, zero1: Optional[bool],
                        grad_accum_steps: Optional[int],
                        pipeline=None, fsdp: Optional[bool] = None,
                        fsdp_overlap=None):
        # pipeline first: zero1/fsdp must build their plans over the
        # final (possibly stage-stacked) parameter layout
        if pipeline is not None:
            if pipeline is False or pipeline == 0:
                # 0 (a CLI-derived int flag) means OFF, same as False —
                # not "enable with the default microbatch count"
                self.disable_pipeline()
            else:
                mb = None
                if isinstance(pipeline, dict):
                    mb = pipeline.get("microbatches")
                elif pipeline is not True and isinstance(pipeline, int):
                    mb = pipeline
                self.enable_pipeline(microbatches=mb)
        if grad_accum_steps is None:   # like zero1=None: keep current —
            # a later train() without the kwarg must not silently drop
            # accumulation (and 8x the activation memory)
            grad_accum_steps = self.grad_accum_steps
        if grad_accum_steps < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got "
                             f"{grad_accum_steps}")
        if grad_accum_steps > 1:
            if self._carry_layers:
                raise ValueError(
                    "grad_accum_steps > 1 is incompatible with "
                    "prev_batch_state: truncated-BPTT state cannot carry "
                    "across microbatches of disjoint rows")
            if any(getattr(e, "wants_grad", False)
                   for e, _, _ in self._host_evals):
                raise ValueError(
                    "grad_accum_steps > 1 is incompatible with "
                    "gradient_printer evaluators (per-batch output "
                    "gradients are not accumulated across microbatches)")
            bn = [n for n, ld in self.topology.graph.layers.items()
                  if ld.type in ("batch_norm", "cudnn_batch_norm",
                                 "batch_normalization")]
            if bn:
                from paddle_tpu.utils import logger
                logger.warning(
                    "grad_accum_steps > 1 with batch-stat layers %s: each "
                    "microbatch normalizes by ITS OWN batch statistics "
                    "(1/k of the rows), so the step is NOT exactly the "
                    "k×-batch step — the usual accumulation caveat, loud "
                    "here because the exactness claim holds only for "
                    "batch-stat-free models (moving averages are still "
                    "averaged across microbatches)", bn)
        if fsdp is True or (fsdp is None and fsdp_overlap is not None
                            and self._fsdp is not None):
            # fsdp on (or already on with a new overlap mode requested)
            self.enable_fsdp(overlap=fsdp_overlap)
        elif fsdp is False:
            self.disable_fsdp()    # None = keep the current mode
        elif fsdp_overlap is not None:
            self._fsdp_overlap = fsdp_overlap  # sticky for a later enable
        if zero1 is True:
            self.enable_zero1()
        elif zero1 is False:
            self.disable_zero1()   # None = keep the current mode
        if grad_accum_steps != self.grad_accum_steps:
            self.grad_accum_steps = grad_accum_steps
            self._rebuild_train_step()

    def _configure_health(self, health, show_parameter_stats_period=0):
        """Arm/disarm the training-health plane. Tri-state like zero1:
        ``None`` keeps the current mode, ``False`` disarms, a
        ``HealthConfig``/dict arms. A bare
        ``show_parameter_stats_period > 0`` arms the in-step telemetry
        on that period (the dedupe: the periodic parameter dump reads
        the fused reduction instead of running a second program), and
        fills the period of an explicit config that left it 0. A config
        change rebuilds the step variants; the monitor (and its
        counters/snapshot) survives unchanged configs across train()
        calls."""
        import dataclasses as _dc

        from paddle_tpu.obs.health import HealthConfig, HealthMonitor
        from paddle_tpu.utils import logger
        cfg = self._health_cfg
        if health is False:
            cfg = None
        elif health is not None:
            cfg = HealthConfig.coerce(health)
        if show_parameter_stats_period:
            if cfg is None:
                cfg = HealthConfig(
                    period=int(show_parameter_stats_period))
            elif cfg.period == 0:
                cfg = _dc.replace(
                    cfg, period=int(show_parameter_stats_period))
            elif cfg.period != int(show_parameter_stats_period):
                # the dump reads the telemetry's period-N snapshot:
                # with misaligned cadences a dump line can be up to
                # N-1 batches stale — loud, not silent
                logger.warning(
                    "show_parameter_stats_period=%d but the health "
                    "telemetry period is %d: the periodic parameter "
                    "dump reads the in-step snapshot, which refreshes "
                    "every %d batches — align the periods (or drop "
                    "the explicit health period) for current-step "
                    "dumps", show_parameter_stats_period, cfg.period,
                    cfg.period)

        def graph_sig(c):
            # the compiled-program-affecting subset: the sentry
            # scalars + threshold + skip-select policy, and WHETHER a
            # stats variant exists. Host-only fields (log_path,
            # log_clipping, service, the period VALUE) must not cost
            # a recompile of warmed variants.
            if c is None:
                return None
            return (c.sentry, c.grad_threshold, c.policy, c.period > 0)

        rebuild = graph_sig(cfg) != graph_sig(self._health_cfg)
        self._health_cfg = cfg
        if cfg is None:
            self._health = None
        elif self._health is None:
            self._health = HealthMonitor(cfg)
        else:
            # keep the monitor (counters, snapshots, timeline tail)
            # across config tweaks — one training session, one story;
            # open_timeline() picks up a changed log_path next train()
            self._health.cfg = cfg
        if rebuild:
            self._rebuild_train_step()

    def _health_step(self, hm, sentry_host, health_raw, health_lr, cost,
                     pass_id, batch_id, reader, prev_rng) -> bool:
        """Host side of one armed step: fetch the sentry scalars,
        convert the stats-on snapshot, apply the sentry policy, append
        the timeline record. Returns True when the batch was skipped
        (``skip_batch`` trip: the in-graph select already discarded the
        update; here the RNG split rolls back and the caller skips
        accumulation/carry, so the trajectory is bitwise the run that
        never saw the batch)."""
        bd = self.breakdown
        cfg = self._health_cfg
        param_snap = act_snap = None
        if health_raw is not None:
            # two packed tables -> the reader-facing dicts (name order
            # was recorded at trace time)
            table, act = jax.device_get((health_raw["param_table"],
                                         health_raw["act_table"]))
            param_snap = {}
            for i, n in enumerate(self._health_param_names):
                vals = table[i]
                d = {"avg_abs": float(vals[0]),
                     "max_abs": float(vals[1]),
                     "norm": float(vals[2]),
                     "grad_norm": float(vals[3]),
                     "update_ratio": float(vals[4]),
                     "size": int(self.params[n].size)}
                if vals[5] >= 0:
                    d["touched_rows"] = float(vals[5])
                param_snap[n] = d
            act_snap = {n: {"avg_abs": float(act[i, 0]),
                            "max_abs": float(act[i, 1])}
                        for i, n in enumerate(self._health_act_names)}
        grad_absmax = None
        tripped = False
        if sentry_host is not None:
            trip, gmax = jax.device_get((sentry_host["trip"],
                                         sentry_host["grad_absmax"]))
            tripped = bool(trip)
            grad_absmax = float(gmax)
        skipped = False
        if tripped:
            per_vec = jax.device_get(sentry_host["layer_grad_absmax"])
            per = {n: float(per_vec[i])
                   for i, n in enumerate(self._health_param_names)}
            policy = hm.on_divergence(
                pass_id=pass_id, batch_id=batch_id, loss=cost,
                grad_absmax=grad_absmax, layer_grad_absmax=per,
                rng=np.asarray(jax.device_get(prev_rng)).tolist(),
                ledger=getattr(reader, "ledger_state", None),
                param_stats=param_snap, act_stats=act_snap)
            skipped = policy == "skip_batch"
            if skipped:
                # the clean run never split a key for this batch
                self._rng = prev_rng
        hm.on_step(pass_id=pass_id, batch_id=batch_id, loss=cost,
                   lr=(float(health_lr) if health_lr is not None
                       else None),
                   grad_absmax=grad_absmax,
                   data_wait_ms=bd.last.get("data_wait", 0.0) * 1e3,
                   compute_ms=bd.last.get("compute", 0.0) * 1e3,
                   param_stats=param_snap, act_stats=act_snap,
                   skipped=skipped)
        if tripped and cfg.policy == "halt":
            from paddle_tpu.obs.health import DivergenceError
            raise DivergenceError(
                f"divergence sentry tripped at pass={pass_id} "
                f"batch={batch_id}: loss={cost!r} "
                f"max|grad|={grad_absmax!r} (postmortem: "
                f"{hm.last_postmortem})")
        return skipped

    def _opt_state_for_save(self):
        """Checkpoint view of the optimizer state: with ZeRO-1 active the
        sharded slots are gathered back to their parameters' full shapes,
        and with the pipeline active the stage-stacked slot dicts unstack
        to their flat per-stage names — the file format (keys AND array
        shapes) never depends on the update path, so resume crosses
        sharded<->replicated and pipelined<->unpipelined in any
        combination."""
        state = self.opt_state
        if self._fsdp is not None:
            state = self._fsdp.gather_opt_state(state)
        if self._zero1 is not None:
            state = self._zero1.gather_opt_state(state)
        if self._pipe is not None:
            state = self._pipe.unstack_opt_state(state)
        return state

    def _params_for_save(self):
        """Checkpoint view of the parameters: fsdp-packed leaves gather
        to full shapes and stage-stacked body params unstack to the
        flat per-stage names (``_blk3.w0`` etc.) — the on-disk format
        (keys AND shapes) never depends on the run's layout, so resume
        crosses fsdp/pipeline on/off in any combination."""
        return self._flat_params_view()

    def _trainer_state_for_save(self):
        """The exact-resume state inventory beyond params/opt_state: the
        step RNG key (split once per batch — a resumed run must continue
        the same key stream) and the truncated-BPTT carried state (the
        previous batch's final recurrent state, mid-pass only). The LR
        schedule's step/sample counters live inside opt_state and ride
        the normal save; the reader's position rides the ``ledger``.
        See docs/fault_tolerance.md for the full inventory."""
        state = {"rng": np.asarray(jax.device_get(self._rng))}
        if self._carried is not None:
            state["carried"] = self._carried
        return state

    def train(self, reader, *, feeder=None, num_passes: int = 1,
              event_handler: Optional[Callable] = None,
              log_period: int = 0, checkpointer=None,
              dot_period: int = 0, show_parameter_stats_period: int = 0,
              show_layer_stat: bool = False,
              async_load_data: bool = False, prefetch_depth: int = 2,
              show_step_breakdown: bool = False,
              zero1: Optional[bool] = None,
              grad_accum_steps: Optional[int] = None,
              pipeline=None, auto_resume: bool = True,
              health=None, fsdp: Optional[bool] = None,
              fsdp_overlap=None):
        """reader yields minibatches (lists of sample tuples); feeder
        converts them to Arguments (or pass feed dicts directly).
        ``log_period``>0 logs a TrainerStats-style line and dumps+resets the
        timer registry every N batches (``TrainerInternal.cpp:160-170``,
        ``Trainer.cpp:443-451``); ``dot_period``>0 prints a progress dot
        every N batches (``--dot_period``, ``Flags.cpp``);
        ``show_parameter_stats_period``>0 logs the parameter health dump
        every N batches (``showParameterStats``,
        ``TrainerInternal.cpp:81-88``); ``show_layer_stat`` logs every
        layer output's mean/abs-max at each log_period
        (``--show_layer_stat``, ``Flags.cpp:71``). ``checkpointer``
        (dist.Checkpointer) restores the newest intact checkpoint before
        training (``auto_resume=False`` makes it save-only, the
        ``--no-auto_resume`` CLI spelling) — resuming at the pass after
        the saved one, the ``--start_pass`` semantics of
        ``Trainer.cpp:229-250`` — and saves on its cadence at batch and
        pass boundaries. Resume is EXACT: checkpoints carry the step
        RNG key, carried BPTT state, LR-schedule counters (inside
        opt_state) and the data position — a plain deterministic reader
        is fast-forwarded to the checkpoint's batch (prepared batches
        discarded, not trained), a pass-aware master reader restores
        its task ledger through ``resume_lease`` and has its finishes
        committed only after each checkpoint is durable — so a
        killed-and-resumed run is bitwise the uninterrupted one
        (tests/test_exact_resume_matrix.py, docs/fault_tolerance.md).
        A pass-aware reader's ``sync_pass`` also reconciles the start
        pass with the master's authoritative pass, so a resumed trainer
        neither replays nor starves on passes the cluster already
        resolved.

        ``async_load_data`` (the reference's ``--use_async_load_data``,
        ``DataProvider.h:249``) runs decode → pad/bucket → shard →
        device_put in a background thread with ``prefetch_depth`` batches
        in flight (``data/prefetch.py``), overlapping host data work with
        device compute. A reader already wrapped by ``prefetch_reader``
        (``is_prefetched``) yields ready feeds and is consumed as such.
        ``show_step_breakdown`` logs the per-step host-time split
        {data_wait, h2d, compute, callback}, compute's own split into
        {dispatch, device_wait} and the prefetch thread's parts at each
        log_period and pass end (``utils/profiler.py:StepBreakdown``;
        always accumulated — the flag only controls logging — and
        always written as ``train.*`` / ``prefetch.*`` spans into a
        running profiler session and an armed ``obs.trace`` Tracer)
        plus the per-device
        parameter/optimizer-slot byte accounting
        (``utils/profiler.py:memory_stats``).

        ``zero1`` (the ``--use_zero1`` flag) partitions optimizer state
        over the mesh's data axis — each device holds 1/N of every slot,
        updates its shard, and all-gathers the parameters (ZeRO-1; the
        reference pserver's sharded update, ``ParameterServer2.cpp:362``).
        Tri-state: ``True`` enables, ``False`` disables (resharding the
        slots back), ``None`` (default) keeps the current mode.
        ``fsdp`` (the ``--fsdp`` flag) goes further: eligible
        PARAMETERS (not just slots) live flat-packed 1/N over the
        mesh's dedicated ``fsdp`` axis with one all-gather per layer on
        use and gradients reduce-scattered back into the packed layout
        (``optim/zero1.py:FsdpUpdater``; ``docs/spec_layout.md``), so a
        model ~N× one device's memory trains on the mesh. Same
        tri-state; composes with ``pipeline`` (stage-stacked body keys
        keep their pipe placement, the head shards over fsdp),
        seq-parallel, and ``zero1`` (subsumed: slots already ride the
        fsdp partition). Meshes without an fsdp axis
        (``create_mesh(n_fsdp=N)``) warn and stand down. Checkpoints
        stay format-compatible (gather-on-save, reshard-on-load), so
        resume crosses fsdp on/off in both directions.
        ``fsdp_overlap`` (the ``--fsdp_overlap`` flag) picks the fsdp
        gather spelling: ``True`` (the default mode) double-buffers
        each next parameter's all-gather behind the current layer's
        compute — and, by transposition, each backward reduce-scatter
        behind the previous layer's backward — in the SpecLayout
        prefetch order (``optim/zero1.py:FsdpUpdater.full_params``);
        ``False`` keeps every gather synchronous; ``"force"`` stages
        the overlap chain on any backend (tests/bench — normally the
        chain is TPU-only so CPU audit compiles pin one program);
        ``None`` keeps the current mode. Bitwise-identical training
        trajectory either way (the chain is an
        ``optimization_barrier``, identity on values;
        ``tests/test_fsdp_overlap_matrix.py``).
        ``grad_accum_steps`` (``--grad_accum_steps``) splits each batch
        into k microbatches scanned inside the jitted step, applying the
        optimizer (and clipping/decay) once on the accumulated gradient —
        effective batch size decouples from per-device activation
        memory. Like ``zero1``, sticky: ``None`` (default) keeps the
        previously configured value.

        ``health`` arms the training-health plane
        (``obs/health.py:HealthConfig`` or a kwargs dict; tri-state
        like ``zero1``: ``None`` keeps, ``False`` disarms). While the
        telemetry period is armed — explicitly, or implicitly by
        ``show_parameter_stats_period`` — per-layer param/grad/update/
        activation stats fold INTO the compiled step every Nth batch
        (no second forward: the periodic dumps and
        ``parameter_stats()``/``layer_stats()`` read the in-step
        values), each step appends to the JSONL event timeline when
        ``log_path`` is set, and the divergence sentry (finiteness +
        ``grad_threshold`` on loss/grads, the reference's
        ``--error_clipping_threshold``) applies its policy on a trip:
        ``halt`` | ``skip_batch`` (discard the batch's update in-graph
        and roll the RNG split back — bitwise the run that never saw
        the batch) | ``dump``; every trip writes a postmortem bundle
        and a ``train.divergence`` flight event
        (docs/observability.md, pillar 4).

        ``pipeline`` (the reference-spelled ``--parallel_nn`` flag,
        ``Flags.cpp:23`` / ``ParallelNeuralNetwork.h:23-62``) runs the
        config's device-attr-staged body through the GPipe microbatch
        schedule on the mesh's ``pipe`` axis (``enable_pipeline``).
        ``True`` enables with the default microbatch count (S, or the
        configured grad_accum_steps), an int or ``{"microbatches": k}``
        sets it, ``False`` disables (unstacking the body back to flat
        parameters), ``None`` keeps the current mode. Configs or meshes
        the schedule cannot honor warn and stand down cleanly."""
        from paddle_tpu.utils import global_stat, logger
        self._configure_step(zero1, grad_accum_steps, pipeline, fsdp,
                             fsdp_overlap)
        self._configure_health(health, show_parameter_stats_period)
        hm = self._health
        if hm is not None:
            hm.open_timeline()
        if async_load_data and getattr(reader, "pass_aware", False):
            # the prefetch worker would advance the master reader's task
            # ledger (finishes, in-flight offset) ahead of training by
            # the queue depth; a mid-pass checkpoint would then record
            # prefetched-but-untrained records as consumed and resume
            # would skip them — breaking exact resume AND at-least-once.
            logger.warning(
                "async_load_data: pass-aware master readers are consumed "
                "synchronously (the task ledger must track TRAINED "
                "position, not prefetch position) — ignoring the flag "
                "for this reader")
            async_load_data = False
        start_pass = 0
        resume_base = 0       # batch_id numbering continues here
        resume_skip = 0       # prepared batches to discard, not train
        resume_carried = None
        if checkpointer is not None:
            # commit the master's task ledger only once the checkpoint
            # holding that work is DURABLE (the writer calls on_save
            # after fsync+rename — possibly from its background thread)
            commit = getattr(reader, "commit_ledger", None)
            # couple when the slot is free OR holds a previous train()
            # call's coupling (same Checkpointer reused across runs: the
            # stale closure would commit to the old run's — likely
            # closed — master client and this reader would never couple);
            # a user-provided callback is never clobbered
            if commit is not None and (
                    getattr(checkpointer, "on_save", None) is None or
                    getattr(checkpointer.on_save, "_reader_coupled",
                            False)):
                def _commit_on_save(meta):
                    commit(meta.get("ledger"))
                _commit_on_save._reader_coupled = True
                checkpointer.on_save = _commit_on_save
                # the reader must NOT also commit at its pass end: the
                # durable-save callback owns commits now
                reader.checkpoint_coupled = True
                # the master's durability-gated pass roll waits on this
                # trainer's parked finishes; if the background writer
                # died no on_save will ever commit them, and each poll
                # of the wait renews our liveness so even the lease
                # timeout cannot free the work. Let the wait loop see
                # the writer's error instead of spinning forever.
                if hasattr(reader, "health_check") and \
                        hasattr(checkpointer, "poll_error"):
                    reader.health_check = checkpointer.poll_error
        else:
            commit = None
        # what this process can prove it trained of the pass it is
        # about to (re)start: nothing, until a mid-pass checkpoint
        # says otherwise. A pass-aware reader sends this to the
        # master (resume_lease) so work a previous life finished
        # beyond the restored checkpoint is requeued, its stale
        # lease voided, and dispatch order restored — without it a
        # crashed-then-restarted trainer starves on (or replays out
        # of order) its own requeued tasks.
        ledger = {"pass": 0, "done": [], "inflight": None, "offset": 0}
        restored_from_disk = False
        if checkpointer is not None and auto_resume:
            restored = checkpointer.restore()
            if restored is not None:
                restored_from_disk = True
                r_params, r_opt, meta = restored
                self.load_state(r_params, r_opt)
                tstate = meta.get("trainer_state") or {}
                if "rng" in tstate:
                    # continue the uninterrupted run's key stream, not a
                    # fresh seed's (dropout etc. stay bitwise on track)
                    self._rng = jnp.asarray(np.asarray(tstate["rng"]))
                pid = int(meta.get("pass_id", -1))
                if meta.get("end_of_pass", meta.get("batch_id", 0) == 0):
                    start_pass = pid + 1
                    led = meta.get("ledger")
                    if led:
                        # the completed pass's ledger: its commit may
                        # have been lost between the fsync and the
                        # commit RPC — the reader re-marks that work
                        # done on the master (no-op if the pass
                        # already rolled)
                        ledger = led
                    else:
                        ledger["pass"] = start_pass
                else:
                    # mid-pass (batch-cadence) checkpoint: resume INSIDE
                    # that pass at the exact batch. A pass-aware master
                    # reader restores its task ledger (resume_lease
                    # re-marks consumed tasks done and requeues this
                    # trainer's post-checkpoint work — the old "remaining
                    # tasks only" caveat is gone); a plain deterministic
                    # reader is fast-forwarded past the already-trained
                    # batches instead.
                    start_pass = pid
                    resume_base = int(meta.get("batch_id", 0))
                    if getattr(reader, "pass_aware", False):
                        ledger = meta.get("ledger") or dict(
                            ledger, **{"pass": start_pass})
                    else:
                        resume_skip = resume_base
                        logger.warning(
                            "mid-pass resume fast-forwards %d batches of "
                            "a plain reader: this assumes the reader "
                            "replays the SAME batch order as the "
                            "interrupted run — one that shuffles "
                            "differently per process silently drops "
                            "untrained records. Seed the shuffle, use a "
                            "master reader (task-ledger resume), or save "
                            "only at pass boundaries", resume_base)
                    carried = tstate.get("carried")
                    if carried is not None:
                        resume_carried = jax.tree_util.tree_map(
                            jnp.asarray, carried)
        if getattr(reader, "pass_aware", False) and \
                hasattr(reader, "restore_ledger") and \
                (restored_from_disk or
                 not getattr(reader, "_ledger_reconciled", False)):
            # armed on a FRESH start too (not just an actual restore —
            # and regardless of auto_resume or a checkpointer at all):
            # a previous life under the same trainer id that died
            # before its first durable checkpoint leaves finishes
            # parked on the master — invisible to this process, yet
            # its own polling renews the liveness that would otherwise
            # expire them. Gated behind auto_resume, a
            # --no-auto_resume restart with a stable trainer id would
            # livelock the durability-gated pass roll on exactly that
            # parked work. The empty-ledger reconcile requeues the
            # lost work (it was trained into parameters that no longer
            # exist) and no-ops on a genuine first boot; it re-sorts
            # only its own requeued slice, so queue state other
            # trainers depend on keeps its order. ONCE per reader: a
            # later train() on the same reader is a continuation, not
            # a previous life — an empty re-reconcile would requeue
            # (and silently retrain) everything this very process
            # already finished in the current pass. Only an actual
            # disk restore re-arms, with the restored ledger.
            reader.restore_ledger(ledger)
            reader._ledger_reconciled = True
        if getattr(reader, "sync_pass", None):
            # the master's pass counter is authoritative: a resumed
            # trainer whose cluster moved on must neither replay passes
            # that are fully resolved nor starve through them one empty
            # reader call at a time
            synced = int(reader.sync_pass(start_pass))
            if synced != start_pass:
                logger.info(
                    "resume: master is at pass %d (checkpoint suggested "
                    "%d) — following the master", synced, start_pass)
                start_pass = synced
                resume_base = resume_skip = 0
                resume_carried = None
        event_handler = event_handler or (lambda e: None)
        acc = Accumulator()
        bd = self.breakdown
        bd.reset()
        # a prefetch_reader-wrapped reader already yields prepared,
        # device-placed feeds; async_load_data wraps a plain reader here
        pre_prepared = bool(getattr(reader, "is_prefetched", False))
        if pre_prepared and feeder is not None:
            raise ValueError(
                "feeder would be silently ignored: this reader is already "
                "prefetched — pass the feeder to prefetch_reader(...) "
                "instead")
        loop_ok = False
        unwind_exc = None
        try:
            for pass_id in range(start_pass, num_passes):
                event_handler(ev.BeginPass(pass_id))
                acc.reset()
                self._start_host_evaluators()
                # reference resets RNN state per pass; a mid-pass resume
                # reinstates the checkpointed carry instead
                resuming = pass_id == start_pass and resume_base > 0
                self._carried = resume_carried if resuming else None
                window_cost, window_n = 0.0, 0
                dots_pending = False
                pipe = None
                if async_load_data and not pre_prepared:
                    from paddle_tpu.data.prefetch import PrefetchPipeline
                    pipe = PrefetchPipeline(
                        lambda: _call_reader(reader, pass_id), feeder=feeder,
                        mesh=self.mesh, depth=prefetch_depth, breakdown=bd)
                    stream = iter(pipe)
                else:
                    stream = iter(_call_reader(reader, pass_id))
                batch_id = -1
                # the batch's place in this pass's stream: the `step` of
                # the trainer's spans and of the prefetch thread's
                seq = -1
                if resuming:
                    # exact-resume replay: discard the already-trained prefix
                    # (plain readers; a ledger-restored master reader yields
                    # only untrained records, so resume_skip is 0) and keep
                    # the uninterrupted run's batch numbering so checkpoint
                    # cadence and logs stay aligned
                    for _ in range(resume_skip):
                        if next(stream, _END_OF_PASS) is _END_OF_PASS:
                            break
                        seq += 1
                    batch_id = resume_base - 1
                try:
                    while True:
                        seq += 1
                        bd.step_begin(seq)
                        # blocked-on-data time: the sync reader's own cost, or
                        # the prefetch queue wait (near zero once it keeps up)
                        with bd.measure("data_wait"):
                            data = next(stream, _END_OF_PASS)
                        if data is _END_OF_PASS:
                            bd.step_abandon()
                            break
                        batch_id += 1
                        event_handler(ev.BeginIteration(pass_id, batch_id))
                        if pipe is not None or pre_prepared:
                            feed = data  # decoded + sharded by the worker thread
                        else:
                            with bd.measure("h2d"):
                                feed = feeder(data) if feeder is not None else data
                                # the feeder's leaves are host arrays: the
                                # copy starts here, under its own bracket,
                                # not inside the step's dispatch
                                if self.mesh is not None:
                                    feed = mesh_lib.shard_batch(feed, self.mesh)
                                else:
                                    feed = jax.device_put(feed)
                        with bd.measure("dispatch"):
                            prev_rng = self._rng  # skip_batch rolls back here
                            self._rng, step_rng = jax.random.split(self._rng)
                            if self._carried is not None:
                                # a batch-size change (e.g. smaller final
                                # batch) makes the carried state unusable:
                                # reset, like the reference's resetState on
                                # shape change
                                b_feed = next(
                                    iter(feed.values())).value.shape[0]
                                b_carry = jax.tree_util.tree_leaves(
                                    self._carried)[0].shape[0]
                                if b_carry != b_feed:
                                    self._carried = None
                            stats_on = self._train_step_stats is not None and (
                                (batch_id + 1) % self._health_cfg.period == 0
                                or self._stats_warm_pending)
                            self._stats_warm_pending = False
                            poison = None
                            if hm is not None and self._health_cfg.sentry:
                                fired = ()
                                if _chaos._ACTIVE is not None:
                                    # the health plane's own chaos site: a
                                    # `corrupt` fault here poisons one
                                    # gradient leaf IN-GRAPH (the traced
                                    # `poison` scalar), the divergence-
                                    # sentry drill
                                    fired = _chaos._ACTIVE.hit(
                                        "step_stats", pass_id=pass_id,
                                        batch_id=batch_id) or ()
                                poison = jnp.float32(
                                    1.0 if "corrupt" in fired else 0.0)
                            t_compute = time.perf_counter()
                            step_fn = (self._train_step_stats if stats_on
                                       else self._train_step)
                            if hm is not None:
                                self.params, self.opt_state, metrics = \
                                    step_fn(self.params, self.opt_state,
                                            feed, step_rng,
                                            jnp.int32(pass_id),
                                            self._carried, poison)
                            else:
                                self.params, self.opt_state, metrics = \
                                    step_fn(self.params, self.opt_state,
                                            feed, step_rng,
                                            jnp.int32(pass_id),
                                            self._carried)
                        # the host fetch waits for the step: the trainer's
                        # thread blocked on the device, and the `compute`
                        # bracket (the reference's trainBatch) closes on
                        # finished work
                        with bd.measure("device_wait") as waited:
                            # the layers' counters come in the same fetch
                            cost, counters = jax.device_get(
                                (metrics["cost"],
                                 metrics.pop("counters", {})))
                            cost = float(cost)
                        bd.add_counters(counters)
                        bd.add("compute",
                               waited.t0 + waited.seconds - t_compute)
                        guard = (self.stats_recompile_guard if stats_on
                                 else self.recompile_guard)
                        guard.check()
                        if guard.grew:
                            # a slow step in a dump says that it compiled
                            bd.mark_step(recompiled=True)
                        in_callback = bd.measure("callback").start()
                        sentry_host = metrics.pop("sentry", None)
                        health_raw = metrics.pop("health", None)
                        health_lr = metrics.pop("health_lr", None)
                        skipped = False
                        if hm is not None:
                            skipped = self._health_step(
                                hm, sentry_host, health_raw, health_lr,
                                cost, pass_id, batch_id, reader,
                                prev_rng)
                        if self._carry_layers:
                            carried_new = metrics.pop("carried")
                            if not skipped:
                                self._carried = carried_new
                        if skipped:
                            # the clean run never saw this batch:
                            # nothing accumulates, the log window and
                            # host evaluators stay untouched
                            evals = acc.result()
                        else:
                            evals = self._accumulate(acc, metrics)
                            self._feed_host_evaluators(metrics, feed=feed,
                                                       rng=step_rng)
                            window_cost += cost
                            window_n += 1
                        if dot_period and (batch_id + 1) % dot_period == 0:
                            print(".", end="", flush=True)
                            dots_pending = True
                        stats_due = show_parameter_stats_period and \
                            (batch_id + 1) % show_parameter_stats_period == 0
                        log_due = log_period and (batch_id + 1) % log_period == 0
                        if dots_pending and (stats_due or log_due):
                            print(flush=True)  # newline before the periodic lines
                            dots_pending = False
                        if stats_due:
                            for pname, st in self.parameter_stats().items():
                                logger.info(
                                    "Param %s: %s", pname,
                                    " ".join(f"{k}={v:.5g}"
                                             for k, v in st.items()))
                        if log_due:
                            # Cost is windowed (reset each log_period); AvgEval is
                            # cumulative since pass start, like the reference's
                            # "Eval:" vs "CurrentEval:" split (TrainerInternal.cpp).
                            logger.info(
                                "Pass=%d Batch=%d Cost=%.5f AvgEval: %s", pass_id,
                                batch_id + 1,
                                window_cost / max(window_n, 1),
                                " ".join(f"{k}={v:.5g}" for k, v in
                                         {**evals, **self.host_eval_values(
                                             include_printers=False)}.items()))
                            if show_step_breakdown:
                                from paddle_tpu.utils.profiler import \
                                    memory_status
                                logger.info("%s", bd.status())
                                logger.info("%s", memory_status(
                                    self.params, self.opt_state,
                                    gather_peak=self._gather_peak()))
                            logger.info("\n%s", global_stat.status(reset=True))
                            window_cost, window_n = 0.0, 0
                            if show_layer_stat:
                                for lname, st in self.layer_stats(feed).items():
                                    logger.info(
                                        "Layer %s: avg_abs=%.5g max_abs=%.5g",
                                        lname, st["avg_abs"], st["max_abs"])
                        event_handler(ev.EndIteration(pass_id, batch_id, cost, evals))
                        if _chaos._ACTIVE is not None:
                            # a kill here dies BEFORE this batch could
                            # checkpoint → resume replays it
                            _chaos._ACTIVE.hit("step", pass_id=pass_id,
                                               batch_id=batch_id)
                        if checkpointer is not None:
                            # the callables defer the (device-op) ZeRO-1 slot
                            # gather / pipeline unstack to saves actually due
                            checkpointer.maybe_save(
                                self._params_for_save,
                                self._opt_state_for_save,
                                pass_id=pass_id, batch_id=batch_id + 1,
                                trainer_state=self._trainer_state_for_save,
                                ledger=getattr(reader, "ledger_state", None))
                        if _chaos._ACTIVE is not None:
                            # a kill here dies AFTER the cadence ran → resume
                            # restores the generation just written
                            _chaos._ACTIVE.hit("step_done", pass_id=pass_id,
                                               batch_id=batch_id)
                        in_callback.stop()
                        # true wall denominator: work outside the brackets
                        # (BeginIteration handlers, the guard's check) shows
                        # as a shortfall from 1.0 instead of inflating steps/s
                        bd.step_done()
                finally:
                    # the worker must not outlive this pass — a raising
                    # event handler / step / checkpointer (or Ctrl-C)
                    # would otherwise leak a thread holding `depth`
                    # device batches until GC (and a traceback pinning the
                    # frame defeats GC entirely)
                    bd.step_abandon()   # a step that raised is no step
                    if pipe is not None:
                        pipe.close()
                    close = getattr(stream, "close", None)
                    if close is not None:
                        close()  # a prefetch_reader stream: its generator's
                        # finally closes the pipeline it owns; harmless on
                        # plain generators
                if dots_pending:
                    print(flush=True)  # close the dot line at pass end
                # apply deferred sparse-row updates so the pass ends with
                # current tables (reference catchUpWith before eval/save);
                # routed through the active updater so a zero1 state always
                # goes through the delegate that understands its layout
                self.params, self.opt_state = (
                    self._fsdp or self._zero1 or self.optimizer).catch_up(
                    self.params, self.opt_state, self.meta,
                    num_passes=pass_id)
                if show_step_breakdown:
                    from paddle_tpu.utils.profiler import memory_status
                    logger.info("%s", bd.status())
                    logger.info("%s", memory_status(
                        self.params, self.opt_state,
                        gather_peak=self._gather_peak()))
                event_handler(ev.EndPass(
                    pass_id, {**acc.result(), **self.host_eval_values()}))
                if checkpointer is not None:
                    # the pass-boundary save carries the COMPLETED pass's
                    # ledger: if the crash lands in the durable-but-
                    # uncommitted window (fsync done, commit RPC lost)
                    # the restarted trainer re-marks that work done via
                    # resume_lease — without it the finishes sit parked
                    # under a liveness the restarted process itself keeps
                    # renewing (stable trainer id), holding the
                    # durability-gated roll of a pass its restored
                    # parameters fully contain
                    saved = checkpointer.maybe_save(
                        self._params_for_save, self._opt_state_for_save,
                        pass_id=pass_id, end_of_pass=True,
                        trainer_state=self._trainer_state_for_save,
                        ledger=getattr(reader, "ledger_state", None))
                    if not saved and commit is not None and \
                            getattr(reader, "checkpoint_coupled", False):
                        # no checkpoint was due this pass, so no on_save
                        # will ever commit its finishes — commit now or the
                        # master's durability-gated pass roll waits forever.
                        # Recovery for this pass falls back to the older
                        # generation (plain at-least-once, the cadence the
                        # user chose with saving_period>1).
                        commit(None)
            loop_ok = True
        except BaseException as e:
            unwind_exc = e
            raise
        finally:
            if self._health is not None:
                # drain the event timeline's background writer so the
                # run's JSONL artifact is complete even when the loop
                # unwinds; the monitor (counters, stat snapshots)
                # stays armed for the next train()/reader calls
                self._health.close()
            flush_exc = None
            if checkpointer is not None:
                try:
                    if hasattr(checkpointer, "flush"):
                        # drain background writes even when the loop
                        # unwinds (chaos kill, NaN anomaly,
                        # KeyboardInterrupt): every generation
                        # maybe_save() queued must become durable — a
                        # sync run would have had them on disk already.
                        # When ALREADY unwinding, a writer error must
                        # not replace the exception that actually
                        # killed the run (finally semantics would also
                        # downgrade a chaos-kill BaseException to a
                        # plain RuntimeError). The flag, not
                        # sys.exc_info(), decides: train() called
                        # inside a caller's except block has ambient
                        # exc_info even on a clean run, and a clean run
                        # must NOT swallow the error.
                        try:
                            checkpointer.flush()
                        except Exception as flush_err:
                            if loop_ok:
                                # a clean run's flush error IS the
                                # surfaced failure — but it must not
                                # skip the lease release below: this
                                # process (and its heartbeat) lives
                                # on, so nothing else can ever free
                                # the parked finishes whose commit the
                                # dead writer just lost. Park the
                                # error, release, then re-raise.
                                flush_exc = flush_err
                            else:
                                logger.error(
                                    "checkpoint flush failed while the "
                                    "training loop was unwinding: %r",
                                    flush_err)
                finally:
                    # even when a clean-run flush() raised (the
                    # surfacing path for a dead background writer)
                    if getattr(getattr(checkpointer, "on_save", None),
                               "_reader_coupled", False):
                        # unwire this run's coupling so the
                        # Checkpointer can be reused with a fresh
                        # reader/client — and the READER too: left
                        # True, a reader reused in a later train()
                        # without (re)coupling would never self-commit
                        # at pass end and the master's durability-gated
                        # pass roll would wait forever; the stale
                        # health_check would poll the OLD run's writer
                        # and never surface the hang
                        checkpointer.on_save = None
                        reader.checkpoint_coupled = False
                        if hasattr(reader, "health_check"):
                            reader.health_check = None
            if (isinstance(unwind_exc, Exception) or
                    flush_exc is not None) and \
                    getattr(reader, "release_lease", None) is not None:
                # the loop unwound on a plain Exception (user callback,
                # NaN anomaly) — or a clean run's final flush() raised
                # (dead background writer) — but the process and the
                # master client's heartbeat thread live on: liveness
                # expiry can never free this trainer's in-flight lease
                # or parked uncommitted finishes, so the master's
                # durability-gated pass roll would wait on them
                # forever. Release them explicitly. Runs AFTER the
                # flush above, so generations made durable there have
                # already committed their finishes via on_save — only
                # genuinely uncommittable work requeues. BaseException
                # unwinds (chaos kill, KeyboardInterrupt)
                # emulate/precede process death and must NOT release:
                # the heartbeat dies with the process and the
                # expiry/resume_lease path owns recovery.
                try:
                    reader.release_lease()
                except Exception as release_err:
                    logger.warning(
                        "release_lease failed while the training loop "
                        "was unwinding: %r", release_err)
            if flush_exc is not None:
                raise flush_exc

    def step_breakdown(self) -> Dict[str, float]:
        """Summary of the last train() call's per-step host-time split
        (plus the prefetch worker's queue-wait total): the bench's
        ``input_pipeline_steps_per_sec`` / ``data_wait_frac`` source.
        Under fsdp it carries the ``fsdp_exposed_*`` collective
        accounting (``utils/profiler.py:fsdp_overlap_stats``)."""
        return self.breakdown.summary()

    def _gather_peak(self):
        """FSDP transient gathered-buffer peak for memory reports
        (None when fsdp is off): two layers live under the overlap
        double-buffer, one under the sync spelling."""
        return (self._fsdp.gather_peak_bytes()
                if self._fsdp is not None else None)

    def load_state(self, params: Dict[str, Any], opt_flat=None):
        """Install restored parameters (+ optionally a flattened optimizer
        state as produced by checkpoint.load_params): values are cast and
        re-placed with each current array's sharding, so resuming under a
        mesh keeps tables sharded. Checkpoints always arrive in the flat
        per-stage format (``_params_for_save``); a pipelined run restacks
        them into its stage-stacked layout here — resume crosses pipeline
        on/off in both directions."""
        if self._pipe is not None:
            params, opt_flat = self._pipe.restack_checkpoint(params,
                                                             opt_flat)
        if self._fsdp is not None:
            # checkpoints always store full-shape parameters
            # (_params_for_save gathers): repack the planned ones into
            # this run's (N, chunk) fsdp partition on the host so the
            # placement below sees matching shapes
            params = self._fsdp.pack_params_host(params)

        def place(new, old):
            arr = jnp.asarray(new, dtype=old.dtype)
            if self.mesh is not None and hasattr(old, "sharding"):
                return jax.device_put(arr, old.sharding)
            return arr

        missing = sorted(set(self.params) - set(params))
        unknown = sorted(set(params) - set(self.params))
        if missing or unknown:
            raise ValueError(
                "restored checkpoint does not match the model's parameters"
                + (f"; missing: {missing}" if missing else "")
                + (f"; unknown: {unknown}" if unknown else ""))
        self.params = {k: place(v, self.params[k]) for k, v in params.items()}

        if opt_flat:
            def restore(tree, prefix=""):
                if isinstance(tree, dict):
                    return {k: restore(v, f"{prefix}{k}/")
                            for k, v in tree.items()}
                key = prefix.rstrip("/")
                if key not in opt_flat:
                    return tree
                new = opt_flat[key]
                upd = self._fsdp or self._zero1
                if upd is not None:
                    # checkpoints always store full-shape slots
                    # (_opt_state_for_save gathers): reshard a planned
                    # slot into this run's (N, chunk) partition
                    new = upd.pack_for_load(key, new, tree)
                return place(new, tree)

            self.opt_state = restore(self.opt_state)

    def test(self, reader, *, feeder=None) -> ev.TestResult:
        acc = Accumulator()
        self._start_host_evaluators()
        total_cost, batches = 0.0, 0
        for data in reader():
            feed = feeder(data) if feeder is not None else data
            if self.mesh is not None:
                feed = mesh_lib.shard_batch(feed, self.mesh)
            metrics = self._eval_step(self.params, feed)
            self.eval_recompile_guard.check()
            total_cost += float(metrics["cost"])
            batches += 1
            self._accumulate(acc, metrics)
            self._feed_host_evaluators(metrics, feed=feed)
        return ev.TestResult(0, total_cost / max(batches, 1),
                             {**acc.result(), **self.host_eval_values()})

    def _accumulate(self, acc: Accumulator, metrics) -> Dict[str, float]:
        for k, v in metrics.items():
            if isinstance(v, tuple):
                acc.add(k, *(jax.device_get(x) for x in v))
        return acc.result()

    # -------------------------------------------- config-driven evaluators
    def _start_host_evaluators(self):
        for e, _, _ in self._host_evals:
            e.start()

    def _feed_host_evaluators(self, metrics, feed=None, rng=None):
        """Per-batch accumulation into the config-declared evaluators.
        Inputs bind by the roles the DSL recorded — [outputs..., label?,
        weight?, query_id?] — so e.g. pnpair's query_id lands on its
        keyword, not on ``weight``. gradient_printer evaluators
        additionally receive d(cost)/d(layer output), computed via zero
        probes at the watched layers (the reference prints
        ``Argument.grad``, Evaluator.cpp:1046)."""
        outs = metrics.get("eval_outputs")
        if not outs or not self._host_evals:
            return
        host = jax.device_get(outs)
        row_mask = self._row_mask(feed) if feed is not None else None
        if row_mask is not None:
            # batch-bucket padding appends dead rows at the END of the
            # batch (feeder.py): slice every fetched array to the live
            # prefix so host evaluators never see padding — exact for
            # sequence AND non-sequence metrics alike
            n_live = int(np.asarray(jax.device_get(row_mask)).sum())
            host = {k: tuple(v[:n_live] if v is not None else None
                             for v in tup) for k, tup in host.items()}
        probe_grads = metrics.get("probe_grads")
        if probe_grads is not None:
            # d(cost)/d(layer output) computed in the SAME backward as the
            # batch's step (pre-update params, reference semantics)
            pg = jax.device_get(probe_grads)
            if row_mask is not None:
                pg = {k: v[:n_live] for k, v in pg.items()}
            for e, ins, _ in self._host_evals:
                if getattr(e, "wants_grad", False) and ins and ins[0] in pg:
                    e.last = pg[ins[0]]
        for e, ins, roles in self._host_evals:
            if not ins or ins[0] not in host:
                continue
            vals = [host[n][0] if n in host else None for n in ins]
            n_out = roles.get("n_outputs", 1)
            rest = vals[n_out:]
            kwargs = {"mask": host[ins[0]][1]}
            if getattr(e, "wants_ids", False) and len(host[ins[0]]) > 2:
                # the layer exposes a decoded-ids view alongside its
                # value (crf_decoding with label: value = error
                # indicator, ids = the path — ChunkEvaluator reads ids,
                # Evaluator.cpp / CRFDecodingLayer.cpp semantics)
                vals[0] = host[ins[0]][2]
                kwargs["mask"] = host[ins[0]][3]
            if getattr(e, "wants_grad", False):
                kwargs["grad"] = None  # supplied at print time
            if roles.get("has_label") and rest:
                kwargs["label"] = rest.pop(0)
            if roles.get("has_weight") and rest:
                kwargs["weight"] = rest.pop(0)
            if roles.get("has_query") and rest:
                kwargs["query_id"] = rest.pop(0)
            e.eval_batch(vals[0], **kwargs)

    def host_eval_values(self, include_printers: bool = True
                         ) -> Dict[str, float]:
        return {e.name: e.value() for e, _, _ in self._host_evals
                if include_printers or not e.prints_on_value}

    def parameter_stats(self) -> Dict[str, Dict[str, float]]:
        """Parameter health dump — per-parameter mean |v| and max |v|
        (``showParameterStats``, ``TrainerInternal.cpp:186+``). With the
        in-step telemetry armed (``train(health=...)`` /
        ``show_parameter_stats_period``) this READS the last fused
        reduction's snapshot — no extra program runs, and the table
        additionally carries norm/grad_norm/update_ratio (and sparse
        touched_rows). The standalone jit below remains only for the
        stats-off cold path (a dump requested before any armed step)."""
        hm = self._health
        if hm is not None and hm.param_stats is not None:
            # a COPY: the monitor's dict is also queued for timeline
            # serialization — a caller reformatting the returned rows
            # must not corrupt the JSONL record behind it
            return {n: dict(d) for n, d in hm.param_stats.items()}
        raw = jax.device_get(_param_stats_jit(self.params))
        _param_stats_guard.check()
        return {n: {"avg_abs": float(a), "max_abs": float(m),
                    "size": int(self.params[n].size)}
                for n, (a, m) in raw.items()}

    def layer_stats(self, feed) -> Dict[str, Dict[str, float]]:
        """Per-layer output stats on one batch (``--show_layer_stat``,
        ``Flags.cpp:71``). With the in-step telemetry armed this READS
        the last stats-on step's activation snapshot (the fused
        reduction already saw the executed forward — no second
        forward); the jitted standalone forward below remains only for
        the stats-off cold path (compiled once, cached)."""
        hm = self._health
        if hm is not None and hm.act_stats is not None:
            # same copy rationale as parameter_stats above
            return {n: dict(d) for n, d in hm.act_stats.items()}
        if not hasattr(self, "_layer_stat_fn"):
            # the EXECUTED subgraph only (self.network): off-path layers
            # have no parameters in self.params and possibly no feeds.
            # Same compute dtype as training so the stats reflect the
            # numerics the step actually sees (bf16 range problems are
            # exactly what this flag exists to surface).
            net = self.network

            @jax.jit
            def stat_fn(params, feed):
                outs = net.apply(self._cast_params(params),
                                 self._cast_compute(feed), train=False)
                return {n: _arg_abs_stats(a)[:2]
                        for n, a in outs.items()
                        if hasattr(a.value, "dtype")
                        and jnp.issubdtype(a.value.dtype, jnp.inexact)}

            self._layer_stat_fn = stat_fn
            self._layer_stat_guard = _prefetch.RecompileGuard(
                stat_fn, warn_after=8, name="layer_stats")
        raw = jax.device_get(self._layer_stat_fn(self._flat_params_view(),
                                                 feed))
        self._layer_stat_guard.check()
        return {n: {"avg_abs": float(a), "max_abs": float(m)}
                for n, (a, m) in raw.items()}

    # ------------------------------------------------------------ forward
    def forward(self, feed, output_names: Optional[List[str]] = None):
        outputs = self.network.apply(self._flat_params_view(), feed,
                                     train=False, mesh=self.mesh)
        if output_names is None:
            return outputs
        return {n: outputs[n] for n in output_names}


def _arg_abs_stats(a):
    """(avg |out|, max |out|) of one layer output Argument — mask-aware
    (padded positions excluded from both). Shared by the standalone
    ``layer_stats`` jit and the in-step telemetry's fused activation
    reduction (``SGD._act_stat_table``), so both paths report the same
    numbers. Reduces the contiguous trailing feature axes FIRST (a
    vectorizable row reduce, ~2x the throughput of XLA:CPU's
    whole-tensor reduce on the big [B, T, H] sequences) and applies
    the mask to the [B, T] partials — masked positions contribute 0
    to the sum and are max'd against 0 exactly as the elementwise
    form did (|out| >= 0).

    Returns ``(avg_abs, max_abs, weight)`` — the weight is the live
    element count the avg divided by, so a consumer combining PARTIAL
    batches (the grad-accum microbatch scan) can reweight the avgs
    into the exact whole-batch masked mean instead of a biased mean
    of means."""
    v = jnp.abs(a.value)
    if a.mask is not None and v.ndim >= 2 \
            and a.mask.shape == v.shape[:a.mask.ndim]:
        feat_axes = tuple(range(a.mask.ndim, v.ndim))
        s = jnp.sum(v, axis=feat_axes) if feat_axes else v
        mx = jnp.max(v, axis=feat_axes) if feat_axes else v
        n = jnp.maximum(jnp.sum(a.mask), 1.0) * (
            v.size / max(1, a.mask.size))
        return (jnp.sum(s * a.mask) / n, jnp.max(mx * a.mask), n)
    return (jnp.mean(v), jnp.max(v),
            jnp.asarray(float(v.size), jnp.float32))


@jax.jit
def _param_stats_jit(params):
    return {n: (jnp.mean(jnp.abs(v)), jnp.max(jnp.abs(v)))
            for n, v in params.items()}


# module-level jit = one cache across every SGD instance in the
# process; the guard makes per-topology cache growth loud (each
# distinct param-dict structure is one legitimate variant)
_param_stats_guard = _prefetch.RecompileGuard(
    _param_stats_jit, warn_after=32, name="param_stats")
