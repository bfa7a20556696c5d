"""Model configuration graph — the contract between the DSL and the executor.

Plays the role of the reference's ``ModelConfig`` protobuf (``proto/
ModelConfig.proto``: ``LayerConfig`` + per-type sub-configs), produced there
by ``config_parser.py`` and consumed by ``GradientMachine::create``. Here the
config is plain Python dataclasses: the DSL builds a ``ModelDef``; the
``Network`` executor (core/network.py) turns it into a jittable function.

Parameter naming follows the reference convention so checkpoints are
recognizable: input weight i of layer L is ``_L.w{i}``, bias is ``_L.wbias``
(see ``python/paddle/trainer/config_parser.py`` Layer.create_input_parameter).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Union


@dataclasses.dataclass
class ParamAttr:
    """Per-parameter attributes (``proto/ParameterConfig.proto``)."""

    name: Optional[str] = None  # explicit name => parameter sharing
    init: str = "normal"
    initial_mean: float = 0.0
    initial_std: Optional[float] = None
    is_static: bool = False
    learning_rate: float = 1.0
    l1_rate: Optional[float] = None
    l2_rate: Optional[float] = None
    sparse_grad: bool = False
    # StaticPruningHook (ParameterUpdaterHook.cpp:39): fraction of weights
    # masked to zero (smallest |w| at init) and kept zero by the optimizer
    sparsity_ratio: Optional[float] = None
    # True when this attr was synthesized from parse-wide defaults
    # (default_initial_std()...) rather than written at the layer: such
    # attrs must not clobber const-initialized specs (batch-norm gamma)
    from_defaults: bool = False


@dataclasses.dataclass
class Input:
    """One input connection of a layer (``LayerConfig.inputs``)."""

    layer_name: str
    param_attr: Optional[ParamAttr] = None
    # projection/operator spec for mixed layers, conv spec for conv layers...
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class LayerDef:
    """One layer (``LayerConfig`` in ``proto/ModelConfig.proto``)."""

    name: str
    type: str
    inputs: List[Input] = dataclasses.field(default_factory=list)
    size: Optional[int] = None
    act: str = "linear"
    bias: Union[bool, ParamAttr] = True
    drop_rate: float = 0.0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # the layer whose whole parameter set this one uses: every suffix
    # resolves to ``_<params_of>.<suffix>`` (parameter sharing by name,
    # as ``ParamAttr(name=...)`` shares one weight; a stack run several
    # times over one copy of its weights)
    params_of: Optional[str] = None

    def input_names(self) -> List[str]:
        return [i.layer_name for i in self.inputs]


@dataclasses.dataclass
class ModelDef:
    """The full graph (``ModelConfig``)."""

    layers: Dict[str, LayerDef] = dataclasses.field(default_factory=dict)
    input_layer_names: List[str] = dataclasses.field(default_factory=list)
    output_layer_names: List[str] = dataclasses.field(default_factory=list)
    # EvaluatorConfig-shaped dicts ({"type", "name", "input_layers", ...});
    # consumed by the trainer's metric wiring (SGD._host_evals)
    evaluators: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def add(self, layer: LayerDef) -> LayerDef:
        if layer.name in self.layers:
            raise ValueError(f"duplicate layer name {layer.name!r}")
        self.layers[layer.name] = layer
        if layer.type == "data":
            self.input_layer_names.append(layer.name)
        return layer

    def topo_order(self, targets: Optional[List[str]] = None) -> List[str]:
        """Topological order of the sub-graph reaching ``targets`` (defaults
        to output_layer_names, else all layers). Mirrors the layer ordering
        the config parser emits for ``NeuralNetwork``'s forward loop
        (``paddle/gserver/gradientmachines/NeuralNetwork.cpp:235``)."""
        if targets is None:
            targets = self.output_layer_names or list(self.layers)
        order: List[str] = []
        seen: Dict[str, int] = {}  # 0=visiting, 1=done

        def visit(name: str):
            st = seen.get(name)
            if st == 1:
                return
            if st == 0:
                raise ValueError(f"cycle through layer {name!r}")
            if name not in self.layers:
                raise KeyError(f"layer {name!r} referenced but not defined")
            seen[name] = 0
            for dep in self.layers[name].input_names():
                visit(dep)
            seen[name] = 1
            order.append(name)

        for t in targets:
            visit(t)
        return order
