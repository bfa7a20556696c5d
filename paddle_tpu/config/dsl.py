"""Layer-construction DSL — the user API for building model graphs.

Role of ``python/paddle/trainer_config_helpers/layers.py`` (the v1 DSL) and
``python/paddle/v2/layer.py`` (its v2 graph-object wrapper): each function
appends a ``LayerDef`` to the active ``ModelDef`` and returns a
``LayerOutput`` handle usable as ``input=`` of later calls. Auto-generated
names follow the reference convention (``__fc_layer_0__``).

Unlike the reference there is no protobuf round-trip: the ModelDef *is* the
config; ``Topology``/``Network`` consume it directly.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Sequence, Union

from paddle_tpu.config.model_config import (Input, LayerDef, ModelDef,
                                            ParamAttr)

_GRAPH = ModelDef()
_COUNTERS: Dict[str, itertools.count] = {}


# modules holding per-build state keyed to this graph (e.g. the compat
# layer helpers' implicit ConfigContext) register a hook so reset() clears
# them too — names/counters must not leak across rebuilds
_RESET_HOOKS = []


def on_reset(fn):
    _RESET_HOOKS.append(fn)
    return fn


def reset():
    """Start a fresh graph (the reference resets config_parser globals per
    parse_config call)."""
    global _GRAPH, _COUNTERS, _GROUP_CTX, _DEVICE_SCOPE
    _GRAPH = ModelDef()
    _COUNTERS = {}
    _SHAPES.clear()
    # a build that raised inside a recurrent_group step must not leave the
    # group context armed for the next build (nor a pipeline_stage scope)
    _GROUP_CTX = None
    _DEVICE_SCOPE = None
    for fn in _RESET_HOOKS:
        fn()


def current_graph() -> ModelDef:
    return _GRAPH


def _auto_name(type_name: str) -> str:
    c = _COUNTERS.setdefault(type_name, itertools.count())
    return f"__{type_name}_layer_{next(c)}__"


@dataclasses.dataclass(frozen=True)
class LayerOutput:
    name: str
    size: int
    # the graph this layer belongs to, so consumers (Inference, Topology)
    # keep working after dsl.reset() starts a new one
    graph: Any = dataclasses.field(default=None, repr=False, compare=False)

    def __repr__(self):
        return f"LayerOutput({self.name!r}, size={self.size})"


def _in(x) -> List[LayerOutput]:
    if isinstance(x, LayerOutput):
        return [x]
    return list(x)


def _add(ldef: LayerDef) -> LayerOutput:
    if (_DEVICE_SCOPE is not None and ldef.type != "data"
            and ldef.attrs.get("device") is None):
        # pipeline_stage(s) scope: the --parallel_nn placement spelling
        ldef.attrs["device"] = _DEVICE_SCOPE
    _GRAPH.add(ldef)
    from paddle_tpu.core.registry import get_layer_impl
    # resolve output size via the impl's shape inference
    net_order = [i.layer_name for i in ldef.inputs]
    infos = []
    for n in net_order:
        infos.append(_shape_of(n))
    info = get_layer_impl(ldef.type).infer(ldef, infos)
    _SHAPES[ldef.name] = info
    return LayerOutput(ldef.name, info.size, graph=_GRAPH)


_SHAPES: Dict[str, Any] = {}


def _shape_of(name: str):
    return _SHAPES[name]


def _param(attr) -> Optional[ParamAttr]:
    if attr is None or isinstance(attr, ParamAttr):
        return attr
    if isinstance(attr, dict):
        return ParamAttr(**attr)
    raise TypeError(f"bad param attr {attr!r}")


def _owner(params_of) -> Optional[str]:
    """``params_of=``: the layer (handle or name) whose whole parameter
    set the new layer uses (``LayerDef.params_of``)."""
    return getattr(params_of, "name", params_of)


# ----------------------------------------------------------------- layers
def data(name: str, size: int, *, height: int = None, width: int = None,
         channels: int = None, is_sequence: bool = False) -> LayerOutput:
    ldef = LayerDef(name=name, type="data", size=size, bias=False,
                    attrs={"height": height, "width": width,
                           "channels": channels, "is_sequence": is_sequence})
    return _add(ldef)


def fc(input, size: int, *, act: str = "tanh", name: str = None,
       bias_attr=True, param_attr=None, layer_attr: dict = None) -> LayerOutput:
    ins = [Input(i.name, param_attr=_param(param_attr)) for i in _in(input)]
    ldef = LayerDef(name=name or _auto_name("fc"), type="fc", inputs=ins,
                    size=size, act=act, bias=_bias(bias_attr),
                    **_layer_attr(layer_attr))
    return _add(ldef)


def moe(input, *, expert_hidden: int, num_experts: int, top_k: int,
        experts_held: int = None, expert_offset: int = 0,
        shared_hidden: int = 0, routed_scaling_factor: float = 1.0,
        norm_eps: float = 0.0, score: str = "sigmoid", name: str = None,
        layer_attr: dict = None) -> LayerOutput:
    """Mixture-of-experts FFN (TPU-native capability-add; output size =
    input size): sigmoid top-``top_k`` routing over ``num_experts``
    (the chosen scores normalised by their sum plus ``norm_eps``),
    SwiGLU experts, a shared expert of width ``shared_hidden`` (0: none).
    The layer holds experts ``expert_offset .. + experts_held`` (all of
    them by default) and computes their part of the sum: the chip's share
    of an expert-parallel group (`parallel/moe.py`). ``score="softmax"``
    routes by a softmax over all the experts instead (weights normalised
    to sum 1, no bias, no scale) and hands the router's statistics on for
    ``moe_balance_cost``."""
    src = _in(input)[0]
    extra = _layer_attr(layer_attr)
    attrs = {"num_experts": num_experts, "expert_hidden": expert_hidden,
             "top_k": top_k, "experts_held": experts_held or num_experts,
             "expert_offset": expert_offset, "shared_hidden": shared_hidden,
             "routed_scaling_factor": routed_scaling_factor,
             **extra.pop("attrs", {})}
    if norm_eps:
        attrs["norm_eps"] = norm_eps
    if score != "sigmoid":
        attrs["score"] = score
    ldef = LayerDef(name=name or _auto_name("moe"), type="moe",
                    inputs=[Input(src.name)], bias=False, attrs=attrs,
                    **extra)
    return _add(ldef)


def moe_balance_cost(layers, *, coeff: float,
                     name: str = None) -> LayerOutput:
    """The load-balancing term of every expert layer in ``layers`` (each
    built with ``score="softmax"``) together, times ``coeff``: one cost for
    the step, to be added to the model's loss (`layers/moe.py`)."""
    ldef = LayerDef(name=name or _auto_name("moe_balance_cost"),
                    type="moe_balance_cost",
                    inputs=[Input(x.name) for x in _in(layers)], bias=False,
                    attrs={"coeff": coeff})
    return _add(ldef)


def swiglu(input, *, hidden: int, name: str = None,
           layer_attr: dict = None, params_of=None) -> LayerOutput:
    """``(silu(x W_g) * (x W_u)) W_d``, no bias: a decoder block's dense
    feed-forward half. ``params_of`` names a layer whose weights this one
    uses instead of its own."""
    extra = _layer_attr(layer_attr)
    ldef = LayerDef(name=name or _auto_name("swiglu"), type="swiglu",
                    inputs=[Input(_in(input)[0].name)], bias=False,
                    attrs={"hidden": hidden, **extra.pop("attrs", {})},
                    params_of=_owner(params_of), **extra)
    return _add(ldef)


def rms_norm(input, *, epsilon: float = 1e-6, name: str = None,
             param_attr=None, layer_attr: dict = None,
             params_of=None) -> LayerOutput:
    """``x / sqrt(mean(x^2) + epsilon) * g`` over the feature dim;
    ``params_of`` names a layer whose scale this one uses."""
    extra = _layer_attr(layer_attr)
    ldef = LayerDef(name=name or _auto_name("rms_norm"), type="rms_norm",
                    inputs=[Input(_in(input)[0].name,
                                  param_attr=_param(param_attr))],
                    bias=False,
                    attrs={"epsilon": epsilon, **extra.pop("attrs", {})},
                    params_of=_owner(params_of), **extra)
    return _add(ldef)


def embedding(input, size: int, *, vocab_size: int = None, name: str = None,
              param_attr=None) -> LayerOutput:
    src = _in(input)[0]
    vocab = vocab_size or _shape_of(src.name).size
    ldef = LayerDef(name=name or _auto_name("embedding"), type="embedding",
                    inputs=[Input(src.name, param_attr=_param(param_attr))],
                    size=size, bias=False, attrs={"vocab_size": vocab})
    return _add(ldef)


def mixed(inputs: Sequence, size: int, *, projections: Sequence[dict],
          act: str = "linear", name: str = None, bias_attr=False) -> LayerOutput:
    ins = [Input(i.name, param_attr=_param(p.pop("param_attr", None)))
           for i, p in zip(_in(inputs), [dict(p) for p in projections])]
    ldef = LayerDef(name=name or _auto_name("mixed"), type="mixed",
                    inputs=ins, size=size, act=act, bias=_bias(bias_attr),
                    attrs={"projections": list(projections)})
    return _add(ldef)


def conv(input, *, num_filters: int, filter_size: int, stride: int = 1,
         padding: int = 0, groups: int = 1, channels: int = None,
         act: str = "relu", name: str = None, bias_attr=True,
         param_attr=None, layer_type: str = "exconv") -> LayerOutput:
    src = _in(input)[0]
    extra = {"filter_size": filter_size, "stride": stride,
             "padding": padding, "groups": groups}
    if channels:
        extra["channels"] = channels
    ldef = LayerDef(name=name or _auto_name("conv"), type=layer_type,
                    inputs=[Input(src.name, param_attr=_param(param_attr),
                                  extra=extra)],
                    act=act, bias=_bias(bias_attr),
                    attrs={"num_filters": num_filters})
    return _add(ldef)


def img_pool(input, *, pool_size: Optional[int] = None, stride: int = 1,
             padding: int = 0, pool_type: str = "max-projection",
             name: str = None) -> LayerOutput:
    """pool_size=None pools over the full spatial extent (global pooling)."""
    src = _in(input)[0]
    if pool_size is None:
        info = _shape_of(src.name)
        extra = {"filter_size": info.width, "size_y": info.height,
                 "stride": info.width, "stride_y": info.height,
                 "padding": 0, "pool_type": pool_type}
        ldef = LayerDef(name=name or _auto_name("pool"), type="pool",
                        bias=False, inputs=[Input(src.name, extra=extra)])
        return _add(ldef)
    extra = {"filter_size": pool_size, "stride": stride, "padding": padding,
             "pool_type": pool_type}
    ldef = LayerDef(name=name or _auto_name("pool"), type="pool", bias=False,
                    inputs=[Input(src.name, extra=extra)])
    return _add(ldef)


def batch_norm(input, *, act: str = "linear", name: str = None,
               use_global_stats: bool = None,
               moving_average_fraction: float = 0.9,
               epsilon: float = 1e-5, bias_attr=True,
               layer_attr: dict = None) -> LayerOutput:
    src = _in(input)[0]
    attrs = {"use_global_stats": use_global_stats,
             "moving_average_fraction": moving_average_fraction,
             "epsilon": epsilon}
    attrs.update(_layer_attr(layer_attr).get("attrs", {}))
    ldef = LayerDef(name=name or _auto_name("batch_norm"), type="batch_norm",
                    inputs=[Input(src.name)], act=act, bias=_bias(bias_attr),
                    attrs=attrs)
    return _add(ldef)


def img_cmrnorm(input, *, size: int = 5, scale: float = 1e-4,
                power: float = 0.75, name: str = None) -> LayerOutput:
    src = _in(input)[0]
    ldef = LayerDef(name=name or _auto_name("norm"), type="norm", bias=False,
                    inputs=[Input(src.name, extra={"size": size,
                                                   "scale": scale,
                                                   "pow": power})])
    return _add(ldef)


def addto(inputs, *, act: str = "linear", name: str = None,
          bias_attr=False) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("addto"), type="addto",
                    inputs=[Input(i.name) for i in _in(inputs)], act=act,
                    bias=_bias(bias_attr))
    return _add(ldef)


def concat(inputs, *, name: str = None, act: str = "linear") -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("concat"), type="concat",
                    inputs=[Input(i.name) for i in _in(inputs)], act=act,
                    bias=False)
    return _add(ldef)


def dropout(input, rate: float, *, name: str = None) -> LayerOutput:
    """Reference expresses dropout as a layer attr; standalone helper adds
    an identity addto carrying drop_rate."""
    src = _in(input)[0]
    ldef = LayerDef(name=name or _auto_name("dropout"), type="addto",
                    inputs=[Input(src.name)], bias=False, drop_rate=rate)
    return _add(ldef)


def lstmemory(input, *, name: str = None, reverse: bool = False,
              act: str = "tanh", gate_act: str = "sigmoid",
              state_act: str = "tanh", bias_attr=True,
              param_attr=None) -> LayerOutput:
    src = _in(input)[0]
    ldef = LayerDef(name=name or _auto_name("lstmemory"), type="lstmemory",
                    inputs=[Input(src.name, param_attr=_param(param_attr))],
                    bias=_bias(bias_attr),
                    attrs={"reversed": reverse, "active_type": act,
                           "active_gate_type": gate_act,
                           "active_state_type": state_act})
    return _add(ldef)


def grumemory(input, *, name: str = None, reverse: bool = False,
              act: str = "tanh", gate_act: str = "sigmoid",
              bias_attr=True, param_attr=None) -> LayerOutput:
    src = _in(input)[0]
    ldef = LayerDef(name=name or _auto_name("gru"), type="gated_recurrent",
                    inputs=[Input(src.name, param_attr=_param(param_attr))],
                    bias=_bias(bias_attr),
                    attrs={"reversed": reverse, "active_type": act,
                           "active_gate_type": gate_act})
    return _add(ldef)


def multi_head_attention(query, key_value=None, *, size: int = None,
                         num_heads: int = 1, causal: bool = False,
                         seq_parallel: str = None, seq_axis: str = "seq",
                         name: str = None, bias_attr=True,
                         param_attr=None) -> LayerOutput:
    """Fused multi-head attention (flash kernel on TPU); self-attention
    when key_value is omitted. Capability-add over the reference's
    composite simple_attention.

    ``seq_parallel="ring"|"ulysses"`` turns on sequence parallelism for
    long contexts: when the trainer runs with a mesh carrying
    ``seq_axis`` (``create_mesh(n_seq=...)``), the attention shards the
    time dimension over it (ring = KV rotation over ICI, ulysses =
    heads<->sequence all-to-all; ulysses needs num_heads divisible by
    the axis size). Without such a mesh the layer runs dense."""
    q = _in(query)[0]
    inputs = [Input(q.name, param_attr=_param(param_attr))]
    if key_value is not None:
        inputs.append(Input(_in(key_value)[0].name))
    if seq_parallel not in (None, "ring", "ulysses"):
        raise ValueError(f"seq_parallel must be ring/ulysses, "
                         f"got {seq_parallel!r}")
    ldef = LayerDef(name=name or _auto_name("mha"),
                    type="multi_head_attention", inputs=inputs,
                    size=size or q.size, act="linear",
                    bias=_bias(bias_attr),
                    attrs={"num_heads": num_heads, "causal": causal,
                           "seq_parallel": seq_parallel,
                           "seq_axis": seq_axis})
    return _add(ldef)


def mla_attention(input, *, num_heads: int, q_lora_rank: int,
                  kv_lora_rank: int, qk_nope_head_dim: int,
                  qk_rope_head_dim: int, v_head_dim: int,
                  rope_theta: float = 10000.0, epsilon: float = 1e-6,
                  name: str = None, layer_attr: dict = None) -> LayerOutput:
    """Causal multi-head latent attention (low-rank q and kv paths with an
    RMSNorm inside, a decoupled rotary part, one rotary key for all the
    heads; `layers/attention.py`); no bias, output size = input size."""
    extra = _layer_attr(layer_attr)
    attrs = {"num_heads": num_heads, "q_lora_rank": q_lora_rank,
             "kv_lora_rank": kv_lora_rank,
             "qk_nope_head_dim": qk_nope_head_dim,
             "qk_rope_head_dim": qk_rope_head_dim, "v_head_dim": v_head_dim,
             "rope_theta": rope_theta, "epsilon": epsilon,
             **extra.pop("attrs", {})}
    ldef = LayerDef(name=name or _auto_name("mla"), type="mla_attention",
                    inputs=[Input(_in(input)[0].name)], bias=False,
                    attrs=attrs, **extra)
    return _add(ldef)


def gqa_attention(input, *, num_heads: int, num_kv_heads: int,
                  head_dim: int, window: int = None, rotary_dim: int = None,
                  rope_theta: float = 10000.0, yarn: dict = None,
                  gate: bool = True, block: int = 512,
                  qk_norm: bool = False, qk_norm_eps: float = 1e-6,
                  name: str = None, layer_attr: dict = None,
                  params_of=None) -> LayerOutput:
    """Causal grouped-query self-attention (`layers/attention.py`):
    ``num_heads`` query heads over ``num_kv_heads`` key-value heads of
    ``head_dim``, a sliding ``window`` (None: the whole sequence), rotary
    by halves over the first ``rotary_dim`` of a head (None: all of it)
    with ``yarn``'s frequencies where given (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``), a per-head sigmoid ``gate`` on the core's
    output, the flash kernels' tiles ``block`` x ``block``; no bias,
    output size = input size. ``qk_norm`` puts an RMS normalisation
    (``qk_norm_eps``) over each head of q and of k before the rotary
    turn, one learned scale of ``head_dim`` for all the query heads and
    one for the key heads. ``params_of`` names a layer whose projections
    this one uses (a stack run several times)."""
    extra = _layer_attr(layer_attr)
    attrs = {"num_heads": num_heads, "num_kv_heads": num_kv_heads,
             "head_dim": head_dim, "window": window,
             "rotary_dim": rotary_dim, "rope_theta": rope_theta,
             "yarn": yarn, "gate": gate, "block": block,
             **extra.pop("attrs", {})}
    if qk_norm:
        attrs.update(qk_norm=True, qk_norm_eps=qk_norm_eps)
    ldef = LayerDef(name=name or _auto_name("gqa"), type="gqa_attention",
                    inputs=[Input(_in(input)[0].name)], bias=False,
                    attrs=attrs, params_of=_owner(params_of), **extra)
    return _add(ldef)


def short_conv(input, *, kernel: int = 3, name: str = None,
               layer_attr: dict = None) -> LayerOutput:
    """The gated short convolution (`layers/short_conv.py`): ``[B | C |
    X] = u W_in``, ``y = (C * conv(B * X)) W_out`` with a depthwise
    causal convolution of ``kernel`` taps over time; no bias, output
    size = input size."""
    extra = _layer_attr(layer_attr)
    ldef = LayerDef(name=name or _auto_name("short_conv"),
                    type="short_conv", inputs=[Input(_in(input)[0].name)],
                    bias=False,
                    attrs={"kernel": kernel, **extra.pop("attrs", {})},
                    **extra)
    return _add(ldef)


def seq_shift(input, *, offset: int, name: str = None) -> LayerOutput:
    """Position i of the output holds position ``i + offset`` of the
    input (zeros and a dead mask on the last ``offset``)."""
    return _simple("seq_shift", input, name, attrs={"offset": offset})


def lm_cost(input, ids, *, vocab_size: int, shift: int = 1,
            coeff: float = 1.0, chunk: int = 2048, name: str = None,
            param_attr=None, tied_to=None) -> LayerOutput:
    """The output head fused with its cross-entropy: position i's logits
    against the id at ``i + shift``, each row's mean over the positions
    that have a target, times ``coeff`` (`layers/lm.py`). The head's
    weight is ``param_attr``'s to share (``ParamAttr(name=...)``).
    ``tied_to`` names an embedding layer (handle or name) whose table
    ``[V, d]`` is the head, used transposed: one leaf in the parameter
    table, its gradient the sum of the lookup's and the head's (an
    inference output shares it as a ``trans_full_matrix`` projection of
    the same name)."""
    attrs = {"vocab_size": vocab_size, "shift": shift, "coeff": coeff,
             "chunk": chunk}
    if tied_to is not None:
        param_attr = ParamAttr(name=f"_{_owner(tied_to)}.w0")
        attrs["tied"] = True
    ldef = LayerDef(name=name or _auto_name("lm_cost"), type="lm_cost",
                    inputs=[Input(_in(input)[0].name,
                                  param_attr=_param(param_attr)),
                            Input(_in(ids)[0].name)], bias=False,
                    attrs=attrs)
    return _add(ldef)


def looped_lm_cost(states, ids, *, vocab_size: int, shift: int = 1,
                   beta: float = 0.1, chunk: int = 2048, name: str = None,
                   param_attr=None) -> LayerOutput:
    """The cost of a stack run ``R = len(states)`` times (`layers/lm.py`):
    the head on each pass's state, an exit gate ``sigmoid(x w + b)`` on
    each, the exit distribution ``p`` over the passes, and each row's
    mean of ``sum_t p_t CE_t - beta H(p)`` over the positions that have
    a target. The head's weight is ``param_attr``'s to share, the gate
    is the layer's own (float32)."""
    first, *rest = _in(states)
    ldef = LayerDef(name=name or _auto_name("looped_lm_cost"),
                    type="looped_lm_cost",
                    inputs=[Input(first.name, param_attr=_param(param_attr))]
                    + [Input(s.name) for s in rest]
                    + [Input(_in(ids)[0].name)], bias=False,
                    attrs={"vocab_size": vocab_size, "shift": shift,
                           "beta": beta, "chunk": chunk})
    return _add(ldef)


def recurrent(input, *, name: str = None, reverse: bool = False,
              act: str = "tanh", bias_attr=True, param_attr=None) -> LayerOutput:
    src = _in(input)[0]
    ldef = LayerDef(name=name or _auto_name("recurrent"), type="recurrent",
                    inputs=[Input(src.name, param_attr=_param(param_attr))],
                    bias=_bias(bias_attr), act="linear",
                    attrs={"reversed": reverse, "active_type": act})
    return _add(ldef)


_POOL_TYPES = {"max": "max", "avg": "average", "average": "average",
               "sum": "average", "sqrt": "average", "last": "seqlastins",
               "first": "seqlastins"}


def pooling(input, *, pooling_type: str = "max", name: str = None) -> LayerOutput:
    """Sequence pooling (``pooling_layer`` in the reference DSL)."""
    src = _in(input)[0]
    ltype = _POOL_TYPES[pooling_type]
    attrs = {}
    if pooling_type == "sum":
        attrs["average_strategy"] = "sum"
    if pooling_type == "sqrt":
        attrs["average_strategy"] = "squarerootn"
    if pooling_type == "first":
        attrs["select_first"] = True
    ldef = LayerDef(name=name or _auto_name(f"seq_{pooling_type}"),
                    type=ltype, inputs=[Input(src.name)], bias=False,
                    attrs=attrs)
    return _add(ldef)


def last_seq(input, **kw):
    return pooling(input, pooling_type="last", **kw)


def first_seq(input, **kw):
    return pooling(input, pooling_type="first", **kw)


def expand(input, expand_as, *, name: str = None) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("expand"), type="expand",
                    inputs=[Input(_in(input)[0].name),
                            Input(_in(expand_as)[0].name)], bias=False)
    return _add(ldef)


def maxid(input, *, name: str = None) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("maxid"), type="maxid",
                    inputs=[Input(_in(input)[0].name)], bias=False)
    return _add(ldef)


def cos_sim(a, b, *, scale: float = 1.0, name: str = None) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("cos"), type="cos",
                    inputs=[Input(_in(a)[0].name), Input(_in(b)[0].name)],
                    bias=False, attrs={"cos_scale": scale})
    return _add(ldef)


# ------------------------------------------------------------------ costs
def classification_cost(input, label, *, name: str = None) -> LayerOutput:
    """Cross-entropy on post-softmax input (the reference's
    ``classification_cost`` attaches a classification-error evaluator too —
    the trainer does that by layer type)."""
    ldef = LayerDef(name=name or _auto_name("cost"),
                    type="multi-class-cross-entropy",
                    inputs=[Input(_in(input)[0].name),
                            Input(_in(label)[0].name)], bias=False)
    return _add(ldef)


cross_entropy_cost = classification_cost


def square_error_cost(input, label, *, name: str = None) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("cost"), type="square_error",
                    inputs=[Input(_in(input)[0].name),
                            Input(_in(label)[0].name)], bias=False)
    return _add(ldef)


mse_cost = square_error_cost


def rank_cost(left, right, label, *, name: str = None) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("cost"), type="rank-cost",
                    inputs=[Input(_in(left)[0].name),
                            Input(_in(right)[0].name),
                            Input(_in(label)[0].name)], bias=False)
    return _add(ldef)


# ---------------------------------------------------------------- helpers
def _bias(bias_attr):
    if bias_attr is True or bias_attr is None:
        return True
    if bias_attr is False:
        return False
    return _param(bias_attr) or True


def _layer_attr(layer_attr: Optional[dict]):
    out = {}
    if layer_attr:
        if "drop_rate" in layer_attr:
            out["drop_rate"] = layer_attr["drop_rate"]
        attrs = {}
        if "device" in layer_attr:
            # per-layer placement (--parallel_nn); consumed by
            # parallel.mesh.device_attr_rules as a model-axis shard hint
            # or, all-layers-contiguous, as GPipe stage ids
            # (parallel/pipeline.py)
            attrs["device"] = layer_attr["device"]
        if "recompute" in layer_attr:
            # per-layer rematerialization (jax.checkpoint in the executor)
            attrs["recompute"] = bool(layer_attr["recompute"])
        if attrs:
            out["attrs"] = attrs
    return out


_DEVICE_SCOPE: Optional[int] = None


@contextlib.contextmanager
def pipeline_stage(stage: int):
    """``with dsl.pipeline_stage(s): ...`` — every non-data layer built
    inside carries ``device=s``, the reference's ``--parallel_nn``
    placement spelling (``ParallelNeuralNetwork.h:23-62``) without
    repeating ``layer_attr={"device": s}`` per layer. An explicit
    per-layer ``device`` wins; scopes nest (innermost wins). Contiguous
    stage ids 0..S-1 across the body make the config trainable through
    ``SGD.train(pipeline=True)`` / ``--parallel_nn``
    (``docs/pipeline_parallel.md``)."""
    global _DEVICE_SCOPE
    prev = _DEVICE_SCOPE
    _DEVICE_SCOPE = int(stage)
    try:
        yield
    finally:
        _DEVICE_SCOPE = prev


# ------------------------------------------------- recurrent groups (§3.5)
@dataclasses.dataclass
class StaticInput:
    """Non-time-varying input to a recurrent_group (the reference's
    StaticInput: read whole each timestep, not sliced)."""

    input: LayerOutput


@dataclasses.dataclass
class SubsequenceInput:
    """Two-level (nested) sequence input to a recurrent_group: the outer
    group steps over SUB-sequences; each step sees one whole sub-sequence
    as a sequence Argument (the reference's SubsequenceInput +
    ``RecurrentGradientMachine`` nested frames, ``:294-346``). Nested
    batches flow as [B, S, T_sub, D] with mask [B, S, T_sub] — the padded
    static-shape spelling of ``subSequenceStartPositions``."""

    input: LayerOutput


@dataclasses.dataclass
class GeneratedInput:
    """Generation-mode input: at each step the previous step's generated
    word id is embedded and fed (reference GeneratedInput in
    trainer_config_helpers/layers.py; consumed by beam search,
    RecurrentGradientMachine.cpp:964+)."""

    size: int                      # vocabulary size
    embedding_name: str            # shared embedding parameter name
    embedding_size: int
    bos_id: int = 0
    eos_id: int = 1


_GROUP_CTX: Optional[Dict[str, Any]] = None


def memory(*, name: str, size: int, boot_layer: Optional[LayerOutput] = None,
           boot_with_const_value: float = 0.0,
           agent_name: Optional[str] = None) -> LayerOutput:
    """Declare a recurrent memory inside a recurrent_group step function:
    the previous timestep's output of the layer called ``name`` (zero /
    constant / boot-layer initialized). Mirrors the DSL ``memory()`` that
    becomes an in_link on the reference's recurrent sub-model."""
    global _GROUP_CTX
    if _GROUP_CTX is None:
        raise RuntimeError(
            "memory() must be called inside a recurrent_group step function")
    if name is None:
        # anonymous memory: the link target is bound later via
        # .set_input(layer) (the reference DSL's memory.set_input)
        name = f"__anon_mem_{len(_GROUP_CTX['memories'])}__"
    bname = f"{_GROUP_CTX['name']}@mem_{name}"
    out = _add(LayerDef(name=bname, type="data", size=size, bias=False))
    _GROUP_CTX["memories"].append(
        {"boundary": bname, "link": name, "boot_layer": boot_layer,
         "init": boot_with_const_value, "agent_name": agent_name})
    return out


def _memory_set_input(self, layer):
    """The reference DSL's ``memory.set_input``: bind an anonymous memory
    to its producing layer after the fact."""
    if _GROUP_CTX is not None:
        for entry in _GROUP_CTX["memories"]:
            if entry["boundary"] == self.name:
                entry["link"] = layer.name
                return
    raise RuntimeError("set_input() is only valid on a memory created "
                       "inside the active recurrent_group")


LayerOutput.set_input = _memory_set_input


def recurrent_group(step, input, *, reverse: bool = False,
                    name: str = None, target_inlink=None):
    """Unroll a user step network over the timesteps of the sequence
    inputs (the TPU-native ``RecurrentGradientMachine`` training path —
    see paddle_tpu/layers/group.py). ``input`` items: sequence
    LayerOutputs (sliced per step), StaticInput (whole every step).
    The step function may call memory() and returns one LayerOutput or a
    tuple (first = main out_link)."""
    global _GRAPH, _GROUP_CTX
    from paddle_tpu.config.model_config import ModelDef as _ModelDef
    inputs = [input] if isinstance(
        input, (LayerOutput, StaticInput, SubsequenceInput)) else list(input)
    # reference auto-name convention: __recurrent_group_0__ (config_parser
    # RecurrentLayerGroupBegin), not the generic __X_layer_0__ pattern
    c = _COUNTERS.setdefault("recurrent_group", itertools.count())
    gname = name or f"__recurrent_group_{next(c)}__"
    outer = _GRAPH
    sub = _ModelDef()
    ins_meta: List[Dict[str, Any]] = []
    outer_in_names: List[str] = []
    proxies: List[LayerOutput] = []
    prev_ctx = _GROUP_CTX
    _GRAPH = sub
    _GROUP_CTX = {"name": gname, "memories": []}
    try:
        for i, x in enumerate(inputs):
            if isinstance(x, StaticInput):
                src = x.input
                bname = f"{gname}@static{i}"
                kind = "static"
                ldef = LayerDef(name=bname, type="data", size=src.size,
                                bias=False)
            elif isinstance(x, SubsequenceInput):
                # outer step sees one whole sub-sequence: the boundary
                # data layer is itself a sequence inside the step net
                src = x.input
                bname = f"{gname}@subseq{i}"
                kind = "subseq"
                ldef = LayerDef(name=bname, type="data", size=src.size,
                                bias=False,
                                attrs={"is_sequence": True})
            else:
                src = x
                bname = f"{gname}@seq{i}"
                # a plain input whose source the graph KNOWS is a
                # sequence steps per timestep; otherwise the level is
                # only knowable from the fed data (the reference infers
                # it from the provider's slot types), so defer to the
                # executor's runtime resolution ("auto": 3-D mask ->
                # sub-sequence, maskless flat -> static broadcast)
                try:
                    is_seq = _shape_of(src.name).is_sequence
                except KeyError:
                    is_seq = False
                kind = "seq" if is_seq else "auto"
                # NOTE: the boundary stays a plain (non-sequence) data
                # layer even for kind="seq" — the step sees ONE frame
                # per timestep, not a sequence
                ldef = LayerDef(name=bname, type="data", size=src.size,
                                bias=False)
            proxies.append(_add(ldef))
            ins_meta.append({"boundary": bname, "kind": kind})
            outer_in_names.append(src.name)
        traced = step(*proxies)
        memories = _GROUP_CTX["memories"]
    finally:
        _GRAPH = outer
        _GROUP_CTX = prev_ctx

    out_handles = list(traced) if isinstance(traced, (tuple, list)) \
        else [traced]
    for mem in memories:
        if mem["link"] not in sub.layers:
            raise ValueError(
                f"memory(name={mem['link']!r}) has no matching layer "
                f"inside recurrent_group {gname!r}")
        bl = mem.pop("boot_layer")
        if bl is not None:
            ins_meta.append({"boundary": mem["boundary"], "kind": "boot"})
            outer_in_names.append(bl.name)
    # targetInlink (config_parser target_inlinkname): which in-link's
    # sub-sequence boundaries define the group's OUTPUT structure
    target_idx = 0
    if target_inlink is not None:
        for i, x in enumerate(inputs):
            src_in = getattr(x, "input", x)
            if getattr(src_in, "name", None) == target_inlink.name:
                target_idx = i
                break
    ldef = LayerDef(
        name=gname, type="recurrent_layer_group",
        inputs=[Input(n) for n in outer_in_names], bias=False,
        attrs={"sub_model": sub, "ins": ins_meta, "memories": memories,
               "outputs": [h.name for h in out_handles],
               "reverse": reverse,
               "target_boundary": ins_meta[target_idx]["boundary"]})
    main = _add(ldef)
    if len(out_handles) == 1:
        return main
    extras = []
    for h in out_handles[1:]:
        odef = LayerDef(name=f"{gname}@out_{h.name}", type="group_output",
                        inputs=[Input(main.name)], size=h.size, bias=False,
                        attrs={"sub_name": h.name})
        extras.append(_add(odef))
    return (main, *extras)



def evaluator(type: str, input, *, label=None, weight=None, name: str = None,
              **kwargs):
    """Attach a metric evaluator to the graph (the native spelling of the
    reference's evaluator config funcs, `trainer_config_helpers/
    evaluators.py`); the trainer wires it to the metric registry
    (paddle_tpu/trainer/metrics.py) each pass."""
    ins = [input] if isinstance(input, LayerOutput) else list(input)
    names = [i.name for i in ins]
    n_outputs = len(names)
    for extra in (label, weight):
        if extra is not None:
            names.append(extra.name)
    cfg = {"type": type,
           "name": name or _auto_name(f"{type}_evaluator").replace(
               "_layer_", "_"),
           "input_layers": names,
           "_roles": {"n_outputs": n_outputs,
                      "has_label": label is not None,
                      "has_weight": weight is not None}}
    cfg.update({k: v for k, v in kwargs.items() if v is not None})
    current_graph().evaluators.append(cfg)
    return cfg

def slope_intercept(input, *, slope: float = 1.0, intercept: float = 0.0,
                    name: str = None) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("slope_intercept"),
                    type="slope_intercept", inputs=[Input(_in(input)[0].name)],
                    bias=False, attrs={"slope": slope, "intercept": intercept})
    return _add(ldef)


def beam_search(step, input, *, bos_id: int = None, eos_id: int = None,
                beam_size: int = 5, max_length: int = 100,
                candidate_adjust=None, drop_callback=None,
                norm_or_drop=None, stop_beam_search=None,
                decode_chunk: int = None, full_scan: bool = False,
                name: str = None) -> LayerOutput:
    """Generation-mode recurrent group (``beam_search`` in the reference
    DSL; executed by ``RecurrentGradientMachine::generateSequence``). The
    step function receives the embedding of the previously generated word
    for the GeneratedInput slot and must return post-softmax probabilities
    over the vocabulary. Run it with
    ``paddle_tpu.core.generation.SequenceGenerator``.

    The four beam-control hooks (``candidate_adjust``, ``drop_callback``,
    ``norm_or_drop``, ``stop_beam_search`` —
    ``RecurrentGradientMachine.h:92-145``, signatures in
    ``core/generation.py:SequenceGenerator.generate``) pinned here become
    the defaults for every ``generate`` call on this config, including
    the SWIG surface and the serving generation endpoint. They are traced
    into the jitted search; use module-level functions (not lambdas) if
    the model will be merged for deployment (``--job=merge`` pickles the
    graph).

    ``decode_chunk`` / ``full_scan`` pin the early-exit decode policy
    (``docs/generation.md``): the search runs ``decode_chunk`` steps per
    compiled chunk and exits as soon as every beam finished (byte-
    identical to the full scan, cost proportional to actual output
    length); ``full_scan=True`` pins the single length-``max_length``
    scan."""
    global _GRAPH, _GROUP_CTX
    from paddle_tpu.config.model_config import ModelDef as _ModelDef
    inputs = list(input) if isinstance(input, (list, tuple)) else [input]
    gname = name or _auto_name("beam_search")
    outer = _GRAPH
    sub = _ModelDef()
    ins_meta: List[Dict[str, Any]] = []
    outer_in_names: List[str] = []
    proxies: List[LayerOutput] = []
    gen_spec = None
    prev_ctx = _GROUP_CTX
    _GRAPH = sub
    _GROUP_CTX = {"name": gname, "memories": []}
    try:
        for i, x in enumerate(inputs):
            if isinstance(x, GeneratedInput):
                if gen_spec is not None:
                    raise ValueError("only one GeneratedInput allowed")
                bname = f"{gname}@gen{i}"
                proxies.append(_add(LayerDef(
                    name=bname, type="data", size=x.embedding_size,
                    bias=False)))
                gen_spec = {"boundary": bname, "size": x.size,
                            "embedding_name": x.embedding_name,
                            "embedding_size": x.embedding_size,
                            "bos_id": bos_id if bos_id is not None else x.bos_id,
                            "eos_id": eos_id if eos_id is not None else x.eos_id}
            elif isinstance(x, StaticInput):
                bname = f"{gname}@static{i}"
                proxies.append(_add(LayerDef(
                    name=bname, type="data", size=x.input.size, bias=False)))
                ins_meta.append({"boundary": bname, "kind": "static"})
                outer_in_names.append(x.input.name)
            else:
                raise TypeError(
                    "beam_search inputs must be GeneratedInput/StaticInput")
        traced = step(*proxies)
        memories = _GROUP_CTX["memories"]
    finally:
        _GRAPH = outer
        _GROUP_CTX = prev_ctx
    if gen_spec is None:
        raise ValueError("beam_search needs a GeneratedInput")
    out_handles = list(traced) if isinstance(traced, (tuple, list)) \
        else [traced]
    for mem in memories:
        if mem["link"] not in sub.layers:
            raise ValueError(
                f"memory(name={mem['link']!r}) has no matching layer "
                f"inside beam_search group {gname!r}")
        bl = mem.pop("boot_layer")
        if bl is not None:
            ins_meta.append({"boundary": mem["boundary"], "kind": "boot"})
            outer_in_names.append(bl.name)
    ldef = LayerDef(
        name=gname, type="beam_search_group",
        inputs=[Input(n) for n in outer_in_names], bias=False,
        attrs={"sub_model": sub, "ins": ins_meta, "memories": memories,
               "outputs": [h.name for h in out_handles], "gen": gen_spec,
               "beam_size": beam_size, "max_length": max_length,
               "candidate_adjust": candidate_adjust,
               "drop_callback": drop_callback,
               "norm_or_drop": norm_or_drop,
               "stop_beam_search": stop_beam_search,
               "decode_chunk": decode_chunk, "full_scan": full_scan})
    return _add(ldef)


def crf_layer(input, label, *, size: int = None, weight=None,
              param_attr=None, name: str = None) -> LayerOutput:
    ins = [Input(_in(input)[0].name, param_attr=_param(param_attr)),
           Input(_in(label)[0].name)]
    if weight is not None:
        ins.append(Input(_in(weight)[0].name))
    ldef = LayerDef(name=name or _auto_name("crf"), type="crf",
                    inputs=ins, bias=False)
    return _add(ldef)


def crf_decoding_layer(input, *, size: int = None, label=None,
                       param_attr=None, name: str = None) -> LayerOutput:
    ins = [Input(_in(input)[0].name, param_attr=_param(param_attr))]
    if label is not None:
        ins.append(Input(_in(label)[0].name))
    ldef = LayerDef(name=name or _auto_name("crf_decoding"),
                    type="crf_decoding", inputs=ins, bias=False)
    return _add(ldef)


def ctc_layer(input, label, *, size: int = None, norm_by_times: bool = False,
              blank: int = None, name: str = None) -> LayerOutput:
    attrs = {"norm_by_times": norm_by_times}
    if blank is not None:
        attrs["blank"] = blank
    ldef = LayerDef(name=name or _auto_name("ctc"), type="ctc",
                    inputs=[Input(_in(input)[0].name),
                            Input(_in(label)[0].name)],
                    bias=False, attrs=attrs)
    return _add(ldef)


def warp_ctc_layer(input, label, *, size: int = None,
                   norm_by_times: bool = False, blank: int = 0,
                   name: str = None) -> LayerOutput:
    ldef = LayerDef(name=name or _auto_name("warp_ctc"), type="warp_ctc",
                    inputs=[Input(_in(input)[0].name),
                            Input(_in(label)[0].name)],
                    bias=False,
                    attrs={"norm_by_times": norm_by_times, "blank": blank})
    return _add(ldef)


# ------------------------------------------------ long-tail layer wrappers
def _simple(type_name, input, name=None, *, attrs=None, size=None,
            extra_inputs=(), act="linear", bias=False, param_attr=None):
    ins = [Input(_in(input)[0].name, param_attr=_param(param_attr))]
    ins += [Input(_in(e)[0].name) for e in extra_inputs]
    ldef = LayerDef(name=name or _auto_name(type_name), type=type_name,
                    inputs=ins, size=size, act=act, bias=bias,
                    attrs=attrs or {})
    return _add(ldef)


def clip_layer(input, *, min: float, max: float, name=None):
    return _simple("clip", input, name, attrs={"min": min, "max": max})


def scaling_layer(input, weight, *, name=None):
    """Row-wise scale: out[i] = weight[i] * input[i] (weight is [B, 1] or
    per-timestep [B, T, 1]); the attention-weighting primitive."""
    ldef = LayerDef(name=name or _auto_name("scaling"), type="scaling",
                    inputs=[Input(_in(weight)[0].name),
                            Input(_in(input)[0].name)], bias=False)
    return _add(ldef)


def power_layer(input, weight, *, name=None):
    ldef = LayerDef(name=name or _auto_name("power"), type="power",
                    inputs=[Input(_in(weight)[0].name),
                            Input(_in(input)[0].name)], bias=False)
    return _add(ldef)


def prelu_layer(input, *, partial_sum: int = 1, name=None, param_attr=None):
    return _simple("prelu", input, name, attrs={"partial_sum": partial_sum},
                   param_attr=param_attr)


def maxout_layer(input, *, groups: int, name=None):
    return _simple("maxout", input, name, attrs={"groups": groups})


def multiplex_layer(index, inputs, *, name=None):
    ins = [Input(_in(index)[0].name)] + [Input(_in(i)[0].name)
                                         for i in inputs]
    return _add(LayerDef(name=name or _auto_name("multiplex"),
                         type="multiplex", inputs=ins, bias=False))


def eos_id_layer(input, *, eos_id: int, name=None):
    return _simple("eos_id", input, name, attrs={"eos_id": eos_id})


def sampling_id_layer(input, *, name=None):
    return _simple("sampling_id", input, name)


def print_layer(input, *, name=None):
    return _simple("print", input, name)


def resize_layer(input, *, size: int, name=None):
    return _simple("resize", input, name, size=size)


def rotate_layer(input, *, name=None):
    return _simple("rotate", input, name)


def bilinear_interp_layer(input, *, out_size_x: int, out_size_y: int,
                          name=None):
    return _simple("bilinear_interp", input, name,
                   attrs={"out_size_x": out_size_x, "out_size_y": out_size_y})


def pad_layer(input, *, pad_c=(0, 0), pad_h=(0, 0), pad_w=(0, 0), name=None):
    return _simple("pad", input, name,
                   attrs={"pad_c": list(pad_c), "pad_h": list(pad_h),
                          "pad_w": list(pad_w)})


def crop_layer(input, *, axis: int = 2, offset=None, shape=None,
               reference=None, name=None):
    attrs = {"axis": axis}
    if offset is not None:
        attrs["offset"] = list(offset)
    if shape is not None:
        attrs["shape"] = list(shape)
    extra = [reference] if reference is not None else []
    return _simple("crop", input, name, attrs=attrs, extra_inputs=extra)


def conv_shift_layer(a, b, *, name=None):
    ldef = LayerDef(name=name or _auto_name("conv_shift"), type="conv_shift",
                    inputs=[Input(_in(a)[0].name), Input(_in(b)[0].name)],
                    bias=False)
    return _add(ldef)


def row_conv_layer(input, *, context_length: int, name=None,
                   param_attr=None):
    return _simple("row_conv", input, name,
                   attrs={"context_length": context_length},
                   param_attr=param_attr)


def tensor_layer(a, b, *, size: int, act: str = "linear", name=None,
                 bias_attr=True, param_attr=None):
    ldef = LayerDef(name=name or _auto_name("tensor"), type="tensor",
                    inputs=[Input(_in(a)[0].name, param_attr=_param(param_attr)),
                            Input(_in(b)[0].name)],
                    size=size, act=act, bias=_bias(bias_attr))
    return _add(ldef)


def selective_fc_layer(input, *, size: int, select=None, act: str = "tanh",
                       name=None, bias_attr=True, param_attr=None):
    # the layer consumes the activation itself (mask applied post-act)
    extra = [select] if select is not None else []
    return _simple("selective_fc", input, name, size=size, act="linear",
                   bias=_bias(bias_attr), extra_inputs=extra,
                   param_attr=param_attr, attrs={"active_type": act})


def mdlstm_layer(input, *, name=None, act: str = "tanh",
                 gate_act: str = "sigmoid", state_act: str = "tanh",
                 bias_attr=True, param_attr=None):
    """2-D multi-dimensional LSTM over an image-shaped gate projection
    (input channels = 5*size)."""
    return _simple("mdlstmemory", input, name, bias=_bias(bias_attr),
                   param_attr=param_attr,
                   attrs={"active_type": act, "active_gate_type": gate_act,
                          "active_state_type": state_act})


def block_expand_layer(input, *, block_x: int, block_y: int,
                       stride_x: int = 1, stride_y: int = 1,
                       padding_x: int = 0, padding_y: int = 0, name=None):
    return _simple("blockexpand", input, name,
                   attrs={"block_x": block_x, "block_y": block_y,
                          "stride_x": stride_x, "stride_y": stride_y,
                          "padding_x": padding_x, "padding_y": padding_y})


def sub_nested_seq_layer(input, selection, *, name=None):
    return _simple("sub_nested_seq", input, name, extra_inputs=[selection])


def get_output_layer(input, *, arg_name: str = "state", size: int = None,
                     name=None):
    return _simple("get_output", input, name, size=size,
                   attrs={"arg_name": arg_name})


def gru_step_layer(input, output_mem, *, size: int = None, act: str = "tanh",
                   gate_act: str = "sigmoid", name=None, bias_attr=True,
                   param_attr=None):
    ldef = LayerDef(name=name or _auto_name("gru_step"), type="gru_step",
                    inputs=[Input(_in(input)[0].name,
                                  param_attr=_param(param_attr)),
                            Input(_in(output_mem)[0].name)],
                    bias=_bias(bias_attr),
                    attrs={"active_type": act,
                           "active_gate_type": gate_act})
    return _add(ldef)


def lstm_step_layer(input, state_mem, *, size: int = None, act: str = "tanh",
                    gate_act: str = "sigmoid", state_act: str = "tanh",
                    name=None, bias_attr=True):
    ldef = LayerDef(name=name or _auto_name("lstm_step"), type="lstm_step",
                    inputs=[Input(_in(input)[0].name),
                            Input(_in(state_mem)[0].name)],
                    bias=_bias(bias_attr),
                    attrs={"active_type": act, "active_gate_type": gate_act,
                           "active_state_type": state_act})
    return _add(ldef)


def nce_layer(input, label, *, num_classes: int, num_neg_samples: int = 10,
              weight=None, name=None, bias_attr=True, param_attr=None):
    ins = [Input(_in(input)[0].name, param_attr=_param(param_attr)),
           Input(_in(label)[0].name)]
    if weight is not None:
        ins.append(Input(_in(weight)[0].name))
    ldef = LayerDef(name=name or _auto_name("nce"), type="nce", inputs=ins,
                    bias=_bias(bias_attr),
                    attrs={"num_classes": num_classes,
                           "num_neg_samples": num_neg_samples})
    return _add(ldef)


def hsigmoid(input, label, *, num_classes: int, name=None, bias_attr=True,
             param_attr=None):
    srcs = _in(input)
    ins = [Input(s.name, param_attr=_param(param_attr)) for s in srcs]
    ins.append(Input(_in(label)[0].name))
    ldef = LayerDef(name=name or _auto_name("hsigmoid"), type="hsigmoid",
                    inputs=ins, bias=_bias(bias_attr),
                    attrs={"num_classes": num_classes})
    return _add(ldef)


def priorbox_layer(input, image, *, min_size, max_size=(), aspect_ratio=(1.0,),
                   variance=(0.1, 0.1, 0.2, 0.2), name=None):
    ldef = LayerDef(name=name or _auto_name("priorbox"), type="priorbox",
                    inputs=[Input(_in(input)[0].name),
                            Input(_in(image)[0].name)], bias=False,
                    attrs={"min_size": list(min_size),
                           "max_size": list(max_size),
                           "aspect_ratio": list(aspect_ratio),
                           "variance": list(variance)})
    return _add(ldef)


def multibox_loss_layer(priorbox, label, conf, loc, *, num_classes: int,
                        overlap_threshold: float = 0.5,
                        neg_pos_ratio: float = 3.0, neg_overlap: float = 0.5,
                        background_id: int = 0, name=None):
    ldef = LayerDef(name=name or _auto_name("multibox_loss"),
                    type="multibox_loss",
                    inputs=[Input(_in(priorbox)[0].name),
                            Input(_in(label)[0].name),
                            Input(_in(loc)[0].name),
                            Input(_in(conf)[0].name)], bias=False,
                    attrs={"num_classes": num_classes,
                           "overlap_threshold": overlap_threshold,
                           "neg_pos_ratio": neg_pos_ratio,
                           "neg_overlap": neg_overlap,
                           "background_id": background_id})
    return _add(ldef)


def detection_output_layer(priorbox, conf, loc, *, num_classes: int,
                           nms_threshold: float = 0.45,
                           nms_top_k: int = 100, keep_top_k: int = 200,
                           confidence_threshold: float = 0.01,
                           background_id: int = 0, name=None):
    ldef = LayerDef(name=name or _auto_name("detection_output"),
                    type="detection_output",
                    inputs=[Input(_in(priorbox)[0].name),
                            Input(_in(loc)[0].name),
                            Input(_in(conf)[0].name)], bias=False,
                    attrs={"num_classes": num_classes,
                           "nms_threshold": nms_threshold,
                           "nms_top_k": nms_top_k, "keep_top_k": keep_top_k,
                           "confidence_threshold": confidence_threshold,
                           "background_id": background_id})
    return _add(ldef)
