"""Optimizers with reference v1 semantics, as pure pytree transforms.

Update formulas match the fused kernels in
``paddle/math/TrainingAlgorithmOp.cu`` (adadelta ``:43``, adagrad ``:66``,
rmsprop ``:86``, decayed-adagrad ``:117``, adam ``:146``, adamax ``:166``)
and the optimizer classes in ``paddle/parameter/FirstOrderOptimizer.h``.
L2 regularization enters the update as ``decayRate`` exactly as there
(``grad + value*decayRate``); L1 is a post-update shrink
(``OptimizerWithRegularizer``). Per-parameter lr multipliers and static
params mirror ``ParameterConfig.learning_rate`` / ``is_static``.

The whole update is one jitted pytree map — the TPU replacement for the
reference's per-block pserver/threaded updaters.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import ParamSpec


@dataclasses.dataclass
class Optimizer:
    """Base: shared hyper-parameters (``OptimizationConfig`` in
    proto/TrainerConfig.proto)."""

    learning_rate: float = 1e-3
    learning_rate_schedule: str = "constant"
    learning_rate_decay_a: float = 0.0
    learning_rate_decay_b: float = 0.0
    learning_rate_args: str = ""
    l1_rate: float = 0.0
    l2_rate: float = 0.0
    gradient_clipping_threshold: float = 0.0
    # model averaging (``AverageOptimizer``): fraction of updates kept in
    # the average (TrainerConfig.proto:74); >= 1 acts as an absolute window
    average_window: float = 0.0
    max_average_window: float = float("inf")
    # Reference v1 gradient semantics (compat configs): parameter grads
    # are the batch SUM (sgdUpdateCpu applies learning_rate to the
    # accumulated gradient; ParameterUpdateFunctions.cpp:25-36, no batch
    # normalization). The engine differentiates the batch-MEAN cost, so
    # with this flag the update multiplies grads by the ACTUAL batch
    # size before clipping/decay — keeping learning_rate, clipping
    # thresholds, L1/L2 rates, and schedules at their reference values.
    sum_gradients: bool = False

    # -- per-subclass ---------------------------------------------------
    def slot_names(self):
        return []

    def _apply_one(self, p, g, slots, lr, decay, t):
        raise NotImplementedError

    # -- public ---------------------------------------------------------
    def create_local_updater(self):
        """The v2-on-SWIG idiom (``optimizer.py:45-56`` →
        ``api.ParameterUpdater::createLocalUpdater``): an updater driving
        this optimizer through the startBatch/update/finishBatch
        protocol."""
        from paddle_tpu.compat.swig_api import ParameterUpdater
        return ParameterUpdater(self)

    def enable_types(self):
        """Parameter buffer types this optimizer maintains
        (``ParameterOptimizer::getParameterTypes``: always VALUE and
        GRADIENT, plus one slot type per optimizer state buffer); the api
        surface passes this to createFromConfigProto."""
        return [0, 1] + [i + 2 for i, _ in enumerate(self.slot_names())]

    def _is_sparse(self, spec) -> bool:
        # the lazy touched-rows path implements the PLAIN momentum
        # recurrence; nesterov's lookahead has no closed-form row
        # catch-up, so those parameters take the dense path (correct,
        # just not lazy) to keep the documented dense==sparse property
        return (spec is not None and getattr(spec, "sparse_grad", False)
                and hasattr(self, "_apply_sparse")
                and not getattr(self, "nesterov", False))

    def init(self, params: Dict[str, jnp.ndarray],
             meta: Optional[Dict[str, ParamSpec]] = None) -> Dict[str, Any]:
        slots = {}
        for name, p in params.items():
            spec = meta.get(name) if meta else None
            if spec is not None and spec.is_static:
                continue
            d = {s: jnp.zeros_like(p) for s in self.slot_names()}
            if spec is not None and spec.sparsity_ratio:
                # StaticPruningHook (ParameterUpdaterHook.cpp:39): mask the
                # smallest-|w| fraction at init; update() keeps them zero
                thresh = jnp.quantile(jnp.abs(p), spec.sparsity_ratio)
                d["prune_mask"] = (jnp.abs(p) >= thresh).astype(p.dtype)
            if self._is_sparse(spec):
                # per-row last-processed step for lazy (touched-rows-only)
                # updates — the SparseRowMatrix/catchUpWith bookkeeping
                # (SparseRowMatrix.h:204, OptimizerWithRegularizer.h)
                d["t_rows"] = jnp.zeros((p.shape[0],), jnp.int32)
            slots[name] = d
        state = {"slots": slots, "t": jnp.zeros((), jnp.int32),
                 "num_samples": jnp.zeros((), jnp.float32)}
        if self.average_window > 0:
            state["avg"] = {n: jnp.zeros_like(p) for n, p in params.items()
                            if n in slots}
        return state

    def _update_param(self, g, p, slots, spec, lr_t, t):
        """One parameter's update: clipping, l1/l2 resolution, the dense or
        sparse apply, and the prune mask. Shape-agnostic and elementwise
        (except the sparse lazy path), so the ZeRO-1 updater
        (``optim/zero1.py``) runs the same code on each device's 1/N flat
        shard — one source of truth for update semantics. Clipping happens
        HERE, on whatever gradient the caller accumulated: under microbatch
        gradient accumulation that is the accumulation-averaged gradient,
        never a per-microbatch one (the reference clips the full batch's
        accumulated gradient, ``FirstOrderOptimizer.h``)."""
        lr_mult = spec.learning_rate if spec else 1.0
        l2 = spec.l2_rate if spec and spec.l2_rate is not None else self.l2_rate
        l1 = spec.l1_rate if spec and spec.l1_rate is not None else self.l1_rate
        if self.gradient_clipping_threshold > 0:
            # reference clips per-parameter by value threshold
            # (FirstOrderOptimizer.h, clipping in SgdOptimizer variants)
            th = self.gradient_clipping_threshold
            g = jnp.clip(g, -th, th)
        mask = slots.get("prune_mask")
        if self._is_sparse(spec):
            # touched-rows-only update with momentum/decay catch-up;
            # l1/l2 handled inside (deferred per-row)
            p_new, slots_new = self._apply_sparse(
                p, g, slots, lr_t * lr_mult, l1, l2, t)
        else:
            # the dense chain is plain jnp in the leaf's own shape: the
            # TPU compiler makes ONE loop fusion of it (with the clip
            # above, the gradient's convert, the l1 shrink and the mask
            # below), every output aliased, nothing re-laid-out
            # (tests/test_tpu_compile.py pins that)
            p_new, slots_new = self._apply_one(
                p, g, slots, lr_t * lr_mult, l2, t)
            if l1 > 0:
                shrink = l1 * lr_t * lr_mult
                p_new = jnp.sign(p_new) * jnp.maximum(
                    jnp.abs(p_new) - shrink, 0.0)
        if mask is not None:
            p_new = p_new * mask          # pruned weights stay zero
            slots_new["prune_mask"] = mask
        return p_new, slots_new

    def update(self, grads, state, params,
               meta: Optional[Dict[str, ParamSpec]] = None,
               batch_size=1, num_passes=0):
        """(grads, state, params) -> (new_params, new_state). meta carries
        per-param lr multipliers / static flags / l1-l2 overrides;
        ``num_passes`` (current pass id) drives the pass_manual schedule."""
        from paddle_tpu.optim.schedules import learning_rate_at

        t = state["t"] + 1
        num_samples = state["num_samples"] + batch_size
        lr_t = learning_rate_at(
            self.learning_rate_schedule, self.learning_rate,
            self.learning_rate_decay_a, self.learning_rate_decay_b,
            num_samples, args=self.learning_rate_args,
            num_passes=num_passes)

        new_params = dict(params)
        # parameters whose gradient is absent this call keep their slots
        # untouched (an API caller updating a subset must not erase
        # momentum history / prune masks / t_rows for the rest)
        new_slots = {n: s for n, s in state["slots"].items()
                     if n not in grads}
        if self.sum_gradients:
            bsz = jnp.asarray(batch_size, jnp.float32)
            grads = {n: g * bsz for n, g in grads.items()}
        for name, g in grads.items():
            if name not in state["slots"]:
                new_params[name] = params[name]
                continue
            spec = meta.get(name) if meta else None
            p_new, slots_new = self._update_param(
                g, params[name], state["slots"][name], spec, lr_t, t)
            new_params[name] = p_new
            new_slots[name] = slots_new

        new_state = {"slots": new_slots, "t": t, "num_samples": num_samples}
        if "avg" in state:
            new_state["avg"] = self._update_avg(state["avg"], t, new_params,
                                                new_slots)
        return new_params, new_state

    def _update_avg(self, avg, t, new_params, new_slots):
        """AverageOptimizer: the window is a FRACTION of all updates so
        far — about average_window * numUpdates parameters are averaged
        (TrainerConfig.proto:70-74), capped by max_average_window
        (AverageOptimizer.h:83-88). Running average with the growing
        effective window W_t = clip(average_window * t, 1,
        max_average_window); values >= 1 behave as an absolute window.
        Shared by the replicated update and the ZeRO-1 updater (which
        keeps ``avg`` replicated) — one source of truth for the window
        semantics."""
        tf32 = t.astype(jnp.float32)
        w = jnp.clip(jnp.float32(self.average_window) * tf32,
                     1.0, jnp.float32(self.max_average_window))
        w = jnp.minimum(tf32, w)
        return {n: avg[n] + (new_params[n] - avg[n]) / w
                for n in new_slots}

    def prune_params(self, params, state):
        """Zero the masked weights immediately — the reference's
        StaticPruningHook::init dotMul's the mask into the value before
        any step runs, so forwards/checkpoints before the first update
        already see pruned weights."""
        out = dict(params)
        for name, slots in state["slots"].items():
            if "prune_mask" in slots and name in out:
                out[name] = out[name] * slots["prune_mask"]
        return out

    def catch_up(self, params, state,
                 meta: Optional[Dict[str, ParamSpec]] = None,
                 num_passes: int = 0):
        """Apply deferred sparse-row updates to ALL rows (the reference's
        ``catchUpWith``, ``OptimizerWithRegularizer.h``): run at pass end
        and before checkpoints so lazily-updated tables are current. Uses
        the current learning rate for the missed steps, as the reference
        does; ``num_passes`` keeps pass-based schedules on the right rate."""
        if not any("t_rows" in s for s in state["slots"].values()):
            return params, state
        from paddle_tpu.optim.schedules import learning_rate_at
        lr_t = learning_rate_at(
            self.learning_rate_schedule, self.learning_rate,
            self.learning_rate_decay_a, self.learning_rate_decay_b,
            state["num_samples"], args=self.learning_rate_args,
            num_passes=num_passes)
        new_params = dict(params)
        new_slots = dict(state["slots"])
        for name, slots in state["slots"].items():
            if "t_rows" not in slots:
                continue
            spec = meta.get(name) if meta else None
            lr_mult = spec.learning_rate if spec else 1.0
            l2 = (spec.l2_rate if spec and spec.l2_rate is not None
                  else self.l2_rate)
            l1 = (spec.l1_rate if spec and spec.l1_rate is not None
                  else self.l1_rate)
            p2, s2 = self._sparse_catch_up_one(
                params[name], slots, lr_t * lr_mult, l1, l2, state["t"])
            if "prune_mask" in slots:
                p2 = p2 * slots["prune_mask"]
                s2["prune_mask"] = slots["prune_mask"]
            new_params[name] = p2
            new_slots[name] = s2
        return new_params, {**state, "slots": new_slots}

    def averaged_params(self, state, params):
        """``AverageOptimizer::apply`` (AverageOptimizer.h:23): swap in the
        windowed average of each learnable parameter for evaluation; the raw
        trained values stay in ``params`` (≡ ``restore``)."""
        if "avg" not in state:
            return params
        out = dict(params)
        out.update(state["avg"])
        return out


@dataclasses.dataclass
class Momentum(Optimizer):
    """Classic v1 SGD+momentum (``sgdUpdate``):
    mom = momentum*mom - lr*(grad + decayRate*value); value += mom.
    ``nesterov`` mirrors ``SparseMomentumParameterOptimizer``'s
    lookahead formulation (FirstOrderOptimizer.h:64-122) collapsed to its
    dense equivalent."""

    momentum: float = 0.0
    nesterov: bool = False

    def slot_names(self):
        return ["mom"]

    def _apply_one(self, p, g, slots, lr, decay, t):
        mom = self.momentum * slots["mom"] - lr * (g + decay * p)
        if self.nesterov:
            return p + self.momentum * mom - lr * (g + decay * p), \
                {"mom": mom}
        return p + mom, {"mom": mom}

    # ---------------------------------------------------- sparse (lazy) path
    # Touched-rows-only updates for sparse_grad tables, with closed-form
    # catch-up. For a row with zero grad the dense recurrence is
    # mom *= mu; p += mom — over k missed steps p += mom*(mu+...+mu^k) and
    # mom *= mu^k, applied lazily when the row is next touched (or at
    # catch_up). Exactly equal to the dense updater when l1=l2=0 (the
    # test_CompareSparse property); with regularization the decay is
    # deferred per-row as (1-lr*l2)^k / k-scaled l1 shrink, the reference's
    # OptimizerWithRegularizerSparse approximation.

    def _geo_sum(self, k):
        """mu + mu^2 + ... + mu^k, elementwise over int k."""
        mu = self.momentum
        kf = k.astype(jnp.float32)
        if mu == 1.0:
            return kf
        if mu == 0.0:
            return jnp.zeros_like(kf)
        return mu * (1.0 - jnp.power(mu, kf)) / (1.0 - mu)

    def _catch_up_rows(self, p, mom, lr, l1, l2, k):
        kf = k.astype(p.dtype).reshape(k.shape + (1,) * (p.ndim - 1))
        if l2 > 0:
            p = p * jnp.power(1.0 - lr * l2, kf)
        if l1 > 0:
            shrink = lr * l1 * kf
            p = jnp.sign(p) * jnp.maximum(jnp.abs(p) - shrink, 0.0)
        geo = self._geo_sum(k).reshape(kf.shape)
        p = p + mom * geo
        mom = mom * jnp.power(self.momentum, kf) if self.momentum > 0 \
            else jnp.where(kf > 0, 0.0, mom)
        return p, mom

    def _apply_sparse(self, p, g, slots, lr, l1, l2, t):
        t_rows = slots["t_rows"]
        touched = jnp.any(g != 0, axis=tuple(range(1, g.ndim)))
        k = (t - 1) - t_rows  # steps missed before this one
        cp, cmom = self._catch_up_rows(p, slots["mom"], lr, l1, l2, k)
        mom_new = self.momentum * cmom - lr * (g + l2 * cp)
        p_new = cp + mom_new
        if l1 > 0:
            # the live step's shrink (catch-up covered only missed steps)
            p_new = jnp.sign(p_new) * jnp.maximum(
                jnp.abs(p_new) - lr * l1, 0.0)
        tb = touched.reshape(touched.shape + (1,) * (p.ndim - 1))
        return (jnp.where(tb, p_new, p),
                {"mom": jnp.where(tb, mom_new, slots["mom"]),
                 "t_rows": jnp.where(touched, t, t_rows)})

    def _sparse_catch_up_one(self, p, slots, lr, l1, l2, t):
        k = t - slots["t_rows"]
        p2, mom2 = self._catch_up_rows(p, slots["mom"], lr, l1, l2, k)
        return p2, {"mom": mom2,
                    "t_rows": jnp.full_like(slots["t_rows"], t)}


@dataclasses.dataclass
class AdaGrad(Optimizer):
    """``adagradApply`` (TrainingAlgorithmOp.cu:66)."""

    momentum: float = 0.0
    epsilon: float = 1e-6

    def slot_names(self):
        return ["mom", "accum"]

    def _apply_one(self, p, g, slots, lr, decay, t):
        accum = slots["accum"] + jnp.square(g)
        scale = jax.lax.rsqrt(accum + self.epsilon)
        mom = self.momentum * slots["mom"] - lr * scale * (g + decay * p)
        return p + mom, {"mom": mom, "accum": accum}


@dataclasses.dataclass
class AdaDelta(Optimizer):
    """``adadeltaApply`` (TrainingAlgorithmOp.cu:43)."""

    rou: float = 0.95
    epsilon: float = 1e-6
    momentum: float = 0.0

    def slot_names(self):
        return ["mom", "accum", "accum_update"]

    def _apply_one(self, p, g, slots, lr, decay, t):
        accum = self.rou * slots["accum"] + (1 - self.rou) * jnp.square(g)
        lr_vec = jnp.sqrt((slots["accum_update"] + self.epsilon)
                          / (accum + self.epsilon))
        accum_update = (self.rou * slots["accum_update"]
                        + (1 - self.rou) * jnp.square(g * lr_vec))
        mom = self.momentum * slots["mom"] - lr * lr_vec * (g + decay * p)
        return p + mom, {"mom": mom, "accum": accum,
                         "accum_update": accum_update}


@dataclasses.dataclass
class RMSProp(Optimizer):
    """``rmspropApply`` (TrainingAlgorithmOp.cu:86): centered RMSProp with
    mean-subtracted second moment."""

    rou: float = 0.95
    epsilon: float = 1e-6
    momentum: float = 0.0

    def slot_names(self):
        return ["mom", "g", "f"]

    def _apply_one(self, p, g, slots, lr, decay, t):
        acc_g = self.rou * slots["g"] + (1 - self.rou) * jnp.square(g)
        acc_f = self.rou * slots["f"] + (1 - self.rou) * g
        scale = jax.lax.rsqrt(acc_g - jnp.square(acc_f) + self.epsilon)
        mom = self.momentum * slots["mom"] - lr * scale * (g + decay * p)
        return p + mom, {"mom": mom, "g": acc_g, "f": acc_f}


@dataclasses.dataclass
class DecayedAdaGrad(Optimizer):
    """``decayedAdagradApply`` (TrainingAlgorithmOp.cu:117)."""

    rou: float = 0.95
    epsilon: float = 1e-6
    momentum: float = 0.0

    def slot_names(self):
        return ["mom", "accum"]

    def _apply_one(self, p, g, slots, lr, decay, t):
        accum = self.rou * slots["accum"] + (1 - self.rou) * jnp.square(g)
        scale = jax.lax.rsqrt(accum + self.epsilon)
        mom = self.momentum * slots["mom"] - lr * scale * (g + decay * p)
        return p + mom, {"mom": mom, "accum": accum}


@dataclasses.dataclass
class Adam(Optimizer):
    """``adamApply`` (TrainingAlgorithmOp.cu:146). decay enters via grad as
    in ``AdamOptimizer::update`` (FirstOrderOptimizer.h)."""

    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def slot_names(self):
        return ["mom", "v"]

    def _apply_one(self, p, g, slots, lr, decay, t):
        g = g + decay * p
        mom = self.beta1 * slots["mom"] + (1 - self.beta1) * g
        v = self.beta2 * slots["v"] + (1 - self.beta2) * jnp.square(g)
        tf = t.astype(jnp.float32)
        alpha = lr * jnp.sqrt(1 - jnp.power(self.beta2, tf)) \
            / (1 - jnp.power(self.beta1, tf))
        return p - alpha * mom / (jnp.sqrt(v) + self.epsilon), \
            {"mom": mom, "v": v}


@dataclasses.dataclass
class Adamax(Optimizer):
    """``adamaxApply`` (TrainingAlgorithmOp.cu:166)."""

    beta1: float = 0.9
    beta2: float = 0.999

    def slot_names(self):
        return ["mom", "u"]

    def _apply_one(self, p, g, slots, lr, decay, t):
        g = g + decay * p
        mom = self.beta1 * slots["mom"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * slots["u"], jnp.abs(g))
        tf = t.astype(jnp.float32)
        step = lr / (1 - jnp.power(self.beta1, tf))
        return p - step * mom / jnp.maximum(u, 1e-12), {"mom": mom, "u": u}


_BY_NAME = {
    "momentum": Momentum, "sgd": Momentum, "adagrad": AdaGrad,
    "adadelta": AdaDelta, "rmsprop": RMSProp,
    "decayed_adagrad": DecayedAdaGrad, "adam": Adam, "adamax": Adamax,
}


def create_optimizer(name: str, **kwargs) -> Optimizer:
    """Factory mirroring ``ParameterOptimizer::create``
    (``paddle/parameter/ParameterOptimizer.cpp``)."""
    if name not in _BY_NAME:
        raise KeyError(f"unknown optimizer {name!r}; have {sorted(_BY_NAME)}")
    return _BY_NAME[name](**kwargs)
