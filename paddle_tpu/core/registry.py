"""Layer registry keyed by the reference's ``LayerConfig.type`` strings.

Mirrors ``REGISTER_LAYER`` / ``Layer::create`` (``paddle/gserver/layers/
Layer.h:31,231``, ``Layer.cpp:109``): a class registrar mapping type names
("fc", "exconv", "lstmemory", ...) to implementations. Here an implementation
is a *pure-function bundle* — shape inference, parameter spec, and an apply
function differentiated by ``jax.grad`` — rather than a stateful object with
hand-written forward/backward.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp


@dataclasses.dataclass
class ShapeInfo:
    """Static shape metadata flowing through config-time shape inference
    (the reference does this in ``config_parser.py:159-177``).

    size: feature dimension (LayerConfig.size).
    channels/height/width: image geometry for conv/pool/norm layers.
    is_sequence: whether the layer emits per-timestep values.
    """

    size: int
    channels: Optional[int] = None
    height: Optional[int] = None
    width: Optional[int] = None
    is_sequence: bool = False

    def img(self) -> Tuple[int, int, int]:
        if self.channels is None:
            raise ValueError("layer input has no image geometry")
        return self.channels, self.height, self.width


@dataclasses.dataclass
class ParamSpec:
    """What to allocate for one learnable parameter.

    Mirrors ``ParameterConfig`` (``proto/ParameterConfig.proto``): shape,
    init strategy, per-parameter lr multiplier, static flag, sparsity.
    """

    shape: Tuple[int, ...]
    init: str = "normal"  # normal | uniform | zeros | const
    initial_mean: float = 0.0
    initial_std: Optional[float] = None
    is_static: bool = False
    learning_rate: float = 1.0
    is_bias: bool = False
    sparse_grad: bool = False  # embedding-style row-sparse gradients
    l1_rate: Optional[float] = None  # per-param regularizer overrides
    l2_rate: Optional[float] = None
    sparsity_ratio: Optional[float] = None  # StaticPruningHook mask
    # when set, the parameter keeps this exact global name instead of the
    # `_{layer}.{suffix}` convention — used by recurrent groups to hoist
    # sub-network parameters (shared across timesteps like the reference's
    # frame-shared weights, RecurrentGradientMachine.cpp:294-346)
    absolute_name: Optional[str] = None
    # wire-format ParameterConfig.is_sparse: emitted explicitly (even
    # when False) for layer types whose reference handler writes it
    # (selective_fc's create_input_parameter with a sparse format)
    wire_sparse: Optional[bool] = None
    # wire-format ParameterConfig.is_shared (batch-norm moving stats are
    # marked shared in the reference)
    wire_shared: Optional[bool] = None
    # wire-format dims override where the reference's recorded layout
    # differs from the physical shape (conv shared biases: [size, 1])
    wire_dims: Optional[Tuple[int, ...]] = None
    # the parameter stays float32 under a lower ``compute_dtype`` (the
    # trainer's cast leaves it alone): an MoE router's scores decide a
    # discontinuous choice
    compute_f32: bool = False
    # True only when the USER requested sparse_update (ParamAttr); the
    # engine's sparse_grad default (embedding touched-rows updates) is an
    # internal optimization the reference wire format doesn't record
    user_sparse: bool = False


class LayerImpl:
    """Base for registered layer implementations. Subclasses override:

    - infer(cfg, in_infos)  -> ShapeInfo  (config-time shape inference)
    - params(cfg, in_infos) -> {suffix: ParamSpec}
    - apply(cfg, params, ins, ctx) -> Argument (pre-activation; the executor
      applies cfg.act afterwards, matching Layer::forwardActivation)
    """

    type_name: str = ""
    needs_rng: bool = False

    def infer(self, cfg, in_infos: List[ShapeInfo]) -> ShapeInfo:
        raise NotImplementedError

    def params(self, cfg, in_infos: List[ShapeInfo]) -> Dict[str, ParamSpec]:
        return {}

    def apply(self, cfg, params, ins, ctx):
        raise NotImplementedError


_LAYER_REGISTRY: Dict[str, LayerImpl] = {}


def register_layer(*type_names: str):
    """Class decorator: ``@register_layer("fc")``. Multiple aliases allowed
    (the reference registers e.g. both "exconv" and "cudnn_conv" for conv)."""

    def deco(cls):
        impl = cls()
        impl.type_name = type_names[0]
        for t in type_names:
            if t in _LAYER_REGISTRY:
                raise ValueError(f"duplicate layer type {t!r}")
            _LAYER_REGISTRY[t] = impl
        return cls

    return deco


def get_layer_impl(type_name: str) -> LayerImpl:
    if type_name not in _LAYER_REGISTRY:
        raise KeyError(
            f"unknown layer type {type_name!r}; registered: "
            f"{sorted(_LAYER_REGISTRY)}")
    return _LAYER_REGISTRY[type_name]


def registered_layer_types() -> List[str]:
    return sorted(_LAYER_REGISTRY)
