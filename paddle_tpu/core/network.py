"""Network: the proto-driven graph executor.

TPU-native replacement for ``NeuralNetwork`` (``paddle/gserver/
gradientmachines/NeuralNetwork.cpp``): where the reference walks a layer list
calling virtual ``forward``/``backward`` per layer (hot loops at ``:235`` and
``:285``), here the *whole* forward (and loss) is built as one pure function
``(params, feed, rng) -> outputs`` which is jitted once and differentiated by
``jax.grad`` — no hand-written backward, and XLA fuses across layer
boundaries instead of materializing every intermediate in HBM.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.config.model_config import LayerDef, ModelDef, ParamAttr
from paddle_tpu.core.argument import Argument
from paddle_tpu.core.initializers import init_param
from paddle_tpu.core.registry import ParamSpec, ShapeInfo, get_layer_impl


@dataclasses.dataclass
class Context:
    """Per-apply execution context handed to layer impls."""

    train: bool = False
    rng: Optional[jax.Array] = None
    # device mesh for layers with sharded compute paths (e.g. the
    # seq_parallel attention); None = single-device semantics
    mesh: Any = None
    in_infos: List[ShapeInfo] = dataclasses.field(default_factory=list)
    out_info: Optional[ShapeInfo] = None
    outputs: Dict[str, Argument] = dataclasses.field(default_factory=dict)
    # functional side-channel for moving statistics (batch_norm): param name
    # -> new value; applied by the train step after the gradient update.
    state_updates: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # cross-batch recurrent state (--prev_batch_state truncated BPTT,
    # Trainer.cpp:396-418): layer name -> initial state for this batch
    carried: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def layer_rng(self, layer_name: str) -> jax.Array:
        if self.rng is None:
            raise ValueError("this apply needs an rng (dropout/sampling)")
        return jax.random.fold_in(self.rng, zlib.crc32(layer_name.encode()))


def _resolve_param_name(layer: LayerDef, suffix: str, spec: ParamSpec,
                        attr: Optional[ParamAttr]) -> str:
    if spec.absolute_name:
        return spec.absolute_name
    if attr is not None and attr.name:
        return attr.name
    # a layer that uses another layer's parameters resolves every suffix
    # to that layer's name: the table then holds one leaf, one master and
    # one pair of optimizer slots, and the gradient is the sum over uses
    return f"_{layer.params_of or layer.name}.{suffix}"


def _apply_attr(spec: ParamSpec, attr: Optional[ParamAttr]) -> ParamSpec:
    if attr is None:
        return spec
    if getattr(attr, "from_defaults", False) and spec.init in ("const",
                                                               "zeros"):
        # parse-wide defaults don't override deliberate constant inits
        return spec
    # an attr carrying NO explicit init values (just lr/static/name/...)
    # keeps the layer's deliberate init — e.g. batch-norm gamma's const
    # 1.0 must survive ParamAttr(learning_rate=...) (init_explicit is set
    # by to_param_attr; raw ParamAttr objects count std as the marker)
    init_explicit = getattr(attr, "init_explicit",
                            attr.initial_std is not None
                            or attr.init != "normal")
    keep_init = (not init_explicit) and spec.init in ("const", "zeros")
    return dataclasses.replace(
        spec,
        init=spec.init if keep_init else (
            attr.init if attr.init != "normal"
            or attr.initial_std is not None else spec.init),
        initial_mean=spec.initial_mean if keep_init else attr.initial_mean,
        initial_std=attr.initial_std if attr.initial_std is not None
        else spec.initial_std,
        is_static=attr.is_static or spec.is_static,
        learning_rate=attr.learning_rate,
        sparse_grad=attr.sparse_grad or spec.sparse_grad,
        user_sparse=attr.sparse_grad or spec.user_sparse,
        l1_rate=attr.l1_rate,
        l2_rate=attr.l2_rate,
        sparsity_ratio=(attr.sparsity_ratio
                        if attr.sparsity_ratio is not None
                        else spec.sparsity_ratio),
    )


class Network:
    """Compiled view of a ModelDef: shape inference, parameter table, and a
    pure ``apply``. Construction = the work ``GradientMachine::create`` +
    config_parser shape inference do in the reference."""

    def __init__(self, model: ModelDef,
                 outputs: Optional[List[str]] = None):
        self.model = model
        self.order = model.topo_order(outputs)
        self.shape_infos: Dict[str, ShapeInfo] = {}
        # param name -> (spec, owning layer, suffix)
        self.param_specs: Dict[str, ParamSpec] = {}
        self._layer_params: Dict[str, Dict[str, str]] = {}  # layer -> suffix -> pname

        for name in self.order:
            layer = model.layers[name]
            impl = get_layer_impl(layer.type)
            in_infos = [self.shape_infos[i] for i in layer.input_names()]
            self.shape_infos[name] = impl.infer(layer, in_infos)
            specs = impl.params(layer, in_infos)
            self._layer_params[name] = {}
            for suffix, spec in specs.items():
                if spec.is_bias:
                    attr = layer.bias if isinstance(layer.bias, ParamAttr) else None
                else:
                    # weight i takes input i's param_attr
                    idx = _weight_index(suffix)
                    attr = (layer.inputs[idx].param_attr
                            if idx is not None and idx < len(layer.inputs) else None)
                pname = _resolve_param_name(layer, suffix, spec, attr)
                spec = _apply_attr(spec, attr)
                if pname in self.param_specs:
                    if self.param_specs[pname].shape != spec.shape:
                        raise ValueError(
                            f"shared parameter {pname!r} shape mismatch: "
                            f"{self.param_specs[pname].shape} vs {spec.shape}")
                else:
                    self.param_specs[pname] = spec
                self._layer_params[name][suffix] = pname
        for name in self.order:
            owner = model.layers[name].params_of
            if owner is None:
                continue
            lent = set(self._layer_params.get(owner, {}).values())
            stray = {p for p in self._layer_params[name].values()
                     if p.startswith(f"_{owner}.")} - lent
            if stray:
                raise ValueError(
                    f"layer {name!r} uses the parameters of {owner!r}, "
                    f"which has no {sorted(stray)}")

    # ------------------------------------------------------------------ init
    def init_params(self, key: jax.Array, dtype=jnp.float32,
                    shardings: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, jnp.ndarray]:
        # One jitted program for the whole table: per-parameter eager init
        # would trigger hundreds of tiny XLA compilations. With shardings
        # (name -> NamedSharding), each parameter is created directly in its
        # final placement — a model-sharded embedding table never
        # materializes whole on one device.
        def _init(key):
            params = {}
            for i, (pname, spec) in enumerate(sorted(self.param_specs.items())):
                params[pname] = init_param(
                    jax.random.fold_in(key, i), spec.shape, init=spec.init,
                    initial_mean=spec.initial_mean, initial_std=spec.initial_std,
                    dtype=dtype)
            return params

        out_shardings = (
            {name: shardings[name] for name in self.param_specs}
            if shardings else None)
        # partitionable threefry ONLY for init: with the default
        # (non-partitionable) impl, jitted random values DEPEND on the
        # out_sharding, so a model-sharded table initializes to different
        # numbers than the same table replicated — breaking every
        # sharded-vs-unsharded parity claim at step 0 (observed on the
        # (dcn, data, model) mesh, tests/test_multislice.py). Scoped here
        # so existing dropout/sampling streams are untouched.
        with jax.threefry_partitionable(True):
            return jax.jit(_init, out_shardings=out_shardings)(key)

    # ----------------------------------------------------------------- apply
    def apply(self, params: Dict[str, jnp.ndarray],
              feed: Dict[str, Argument], *, train: bool = False,
              rng: Optional[jax.Array] = None,
              carried: Optional[Dict[str, Any]] = None,
              mesh: Any = None,
              ) -> Dict[str, Argument]:
        outs, _ = self.apply_with_state(params, feed, train=train, rng=rng,
                                        carried=carried, mesh=mesh)
        return outs

    def apply_with_state(
            self, params: Dict[str, jnp.ndarray],
            feed: Dict[str, Argument], *, train: bool = False,
            rng: Optional[jax.Array] = None,
            carried: Optional[Dict[str, Any]] = None,
            mesh: Any = None,
            probes: Optional[Dict[str, jnp.ndarray]] = None,
    ) -> Tuple[Dict[str, Argument], Dict[str, jnp.ndarray]]:
        """Pure forward over the whole graph. ``feed`` maps data-layer names
        to Arguments. Returns (every layer's output keyed by layer name,
        state updates for moving statistics). ``carried`` maps recurrent
        layer names to cross-batch initial state (--prev_batch_state).
        ``probes`` maps layer names to zero-valued perturbations added to
        that layer's output — differentiating the cost w.r.t. a probe
        yields d(cost)/d(layer output), the quantity the reference's
        ``gradient_printer`` evaluator prints (``Argument.grad``)."""
        ctx = Context(train=train, rng=rng, carried=carried or {},
                      mesh=mesh)
        from paddle_tpu.layers.activations import apply_activation  # cycle-free
        from paddle_tpu.ops.common import (  # cycle-free
            KEPT_RESIDUAL, step_mesh)
        from paddle_tpu.utils.error_context import layer_scope

        for name in self.order:
            layer = self.model.layers[name]
            impl = get_layer_impl(layer.type)
            if layer.type == "data" or (
                    getattr(impl, "feed_slot", False) and not layer.inputs):
                # data layers and input-less agents (scatter_agent / memory
                # agents of an expanded recurrent sub-model) are fed by name
                if name not in feed:
                    what = ("data layer" if layer.type == "data"
                            else f"{layer.type} feed slot")
                    raise KeyError(f"missing feed for {what} {name!r}")
                ctx.outputs[name] = feed[name]
                continue
            ins = [ctx.outputs[i] for i in layer.input_names()]
            lparams = {s: params[p] for s, p in self._layer_params[name].items()}
            ctx.in_infos = [self.shape_infos[i] for i in layer.input_names()]
            ctx.out_info = self.shape_infos[name]
            # layer_scope = CustomStackTrace push/pop + HLO named_scope
            # (NeuralNetwork.cpp:244-252); step_mesh tells the Pallas
            # kernels under this layer the mesh they are traced into
            # (with no mesh given, the caller's declaration stands)
            with layer_scope(name), step_mesh(mesh):
                def compute(lp, ins_t, layer=layer, impl=impl, name=name):
                    # state updates thread through as explicit outputs so
                    # this stays pure enough for jax.checkpoint below
                    saved = ctx.state_updates
                    ctx.state_updates = {}
                    try:
                        out = impl.apply(layer, lp, ins_t, ctx)
                        if layer.act and layer.act not in ("linear", ""):
                            out = out.with_value(apply_activation(
                                layer.act, out.value, out.mask))
                        if layer.drop_rate > 0.0:
                            out = out.with_value(_dropout(
                                out.value, layer.drop_rate, ctx, name))
                        return out, ctx.state_updates
                    finally:
                        ctx.state_updates = saved

                if layer.attrs.get("recompute") and train:
                    # per-layer rematerialization: trade recompute FLOPs
                    # for activation HBM (jax.checkpoint; the TPU-native
                    # render of memory-pressure knobs). Kept across the
                    # forward pass: the layer's inputs, and whatever a
                    # kernel's forward rule named common.KEPT_RESIDUAL
                    # (the attention core's output and log-sum-exp, so
                    # its forward kernel is not run again); a layer in
                    # which nothing is named keeps its inputs only. The
                    # kernel decides, where its cost is known. Static Python
                    # metadata in Argument.state (e.g. a nested group's
                    # shape ints) must NOT pass through checkpoint as
                    # pytree leaves — it would come back as tracers and
                    # break downstream shape arithmetic — so array leaves
                    # go through and statics rejoin outside (the cell is
                    # filled at trace time).
                    cell = {}

                    def arrays_only(lp, ins_t):
                        res = compute(lp, ins_t)
                        leaves, td = jax.tree_util.tree_flatten(res)
                        is_arr = [isinstance(v, jax.Array) for v in leaves]
                        cell["td"] = td
                        cell["static"] = [None if a else v
                                          for v, a in zip(leaves, is_arr)]
                        cell["is_arr"] = is_arr
                        return [v for v, a in zip(leaves, is_arr) if a]

                    arrs = jax.checkpoint(
                        arrays_only,
                        policy=jax.checkpoint_policies.save_only_these_names(
                            KEPT_RESIDUAL))(lparams, ins)
                    it = iter(arrs)
                    leaves = [next(it) if a else s
                              for a, s in zip(cell["is_arr"],
                                              cell["static"])]
                    out, new_state = jax.tree_util.tree_unflatten(
                        cell["td"], leaves)
                else:
                    out, new_state = compute(lparams, ins)
                ctx.state_updates.update(new_state)
            if probes and name in probes:
                out = out.with_value(out.value + probes[name])
            ctx.outputs[name] = out
        return ctx.outputs, ctx.state_updates

    def param_meta(self) -> Dict[str, ParamSpec]:
        return dict(self.param_specs)


def _weight_index(suffix: str) -> Optional[int]:
    if suffix.startswith("w") and suffix[1:].isdigit():
        return int(suffix[1:])
    return None


def _dropout(x: jnp.ndarray, rate: float, ctx: Context, layer_name: str):
    """Reference-style (non-inverted) dropout: train multiplies by a 0/1
    keep mask; test scales by (1-rate). See ``Layer::forwardDropOut``
    (``paddle/gserver/layers/Layer.cpp``)."""
    if not ctx.train:
        return x * (1.0 - rate)
    keep = jax.random.bernoulli(
        ctx.layer_rng(layer_name + "/drop"), 1.0 - rate, x.shape)
    return x * keep.astype(x.dtype)
