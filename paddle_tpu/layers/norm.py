"""Normalization layers: batch_norm, rms_norm and cross-map response norm.

``BatchNormalizationLayer``/``CudnnBatchNormLayer`` (``paddle/gserver/layers/
BatchNorm*Layer.cpp``): scale+shift per channel, batch statistics in
training, moving statistics at test. The reference keeps moving mean/var as
two *static* parameters (inputs 1 and 2 of the layer); here they are static
entries in the parameter dict (``w1``, ``w2``) and the training
apply records their EMA update in ``ctx.state_updates`` — the train step
applies those updates functionally (no mutation inside jit).

``CMRProjectionNormLayer`` ("norm" with norm_type cmrnorm-projection):
AlexNet-style local response normalization across channel windows.

``rms_norm`` (capability-add; today's decoder blocks normalise so):
``x / sqrt(mean(x^2) + eps) * g`` over the feature dim, no shift, no
statistics kept; ``rms_normalize`` is the function, for layers that
normalise inside (latent attention's two low-rank paths).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)
from paddle_tpu.layers.conv import to_nhwc


@register_layer("batch_norm", "cudnn_batch_norm", "batch_normalization")
class BatchNormLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return in_infos[0]

    def params(self, cfg, in_infos):
        c = in_infos[0].channels or in_infos[0].size
        return {
            # scale: the reference creates it via create_input_parameter
            # without dims (goldens record none)
            "w0": ParamSpec(shape=(c,), init="const", initial_mean=1.0,
                            initial_std=0.0, wire_dims=()),
            "wbias": ParamSpec(shape=(c,), init="zeros", is_bias=True),
            "w1": ParamSpec(shape=(c,), init="zeros", is_static=True,
                            wire_shared=True),
            # moving variance starts at 0 like the reference (the
            # epsilon in the denominator keeps sqrt well-defined)
            "w2": ParamSpec(shape=(c,), init="zeros", is_static=True,
                            wire_shared=True),
        }

    def apply(self, cfg, params, ins, ctx):
        info = ctx.in_infos[0]
        eps = cfg.attrs.get("epsilon", 1e-5)
        momentum = cfg.attrs.get("moving_average_fraction", 0.9)
        use_global = cfg.attrs.get("use_global_stats", None)
        img = info.channels is not None
        x = (to_nhwc(ins[0].value, info.channels, info.height, info.width)
             if img else ins[0].value)
        axes = tuple(range(x.ndim - 1))
        if use_global is None:
            use_global = not ctx.train
        if use_global:
            mean, var = params["w1"], params["w2"]
        else:
            mean = jnp.mean(x, axis=axes)
            var = jnp.mean(jnp.square(x - mean), axis=axes)
        y = (x - mean) * lax.rsqrt(var + eps) * params["w0"] + params["wbias"]
        if ctx.train and not use_global:
            lname = cfg.name
            ctx.state_updates[f"_{lname}.w1"] = (
                momentum * params["w1"] + (1.0 - momentum) * mean)
            ctx.state_updates[f"_{lname}.w2"] = (
                momentum * params["w2"] + (1.0 - momentum) * var)
        return Argument(value=y, mask=ins[0].mask)


def rms_normalize(x, scale, eps: float):
    """RMSNorm over the last axis. The statistic and the division are
    float32 whatever ``x`` is stored in; the result takes ``x``'s type."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                       + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


@register_layer("rms_norm")
class RmsNormLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return in_infos[0]

    def params(self, cfg, in_infos):
        return {"w0": ParamSpec(shape=(in_infos[0].size,), init="const",
                                initial_mean=1.0, initial_std=0.0)}

    def apply(self, cfg, params, ins, ctx):
        y = rms_normalize(ins[0].value, params["w0"],
                          cfg.attrs.get("epsilon", 1e-6))
        return Argument(value=y, mask=ins[0].mask)


@register_layer("norm", "cmrnorm-projection")
class CrossMapNormLayer(LayerImpl):
    """Local response normalization across a window of ``size`` channels:
    out = x * (1 + alpha/size * sum_{window} x^2)^{-beta}  — matching the
    reference's scale formula (``paddle/function/CrossMapNormalOp.cpp``)."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        info = ctx.in_infos[0]
        extra = cfg.inputs[0].extra
        size = extra.get("size", 5)
        # the reference folds /size into the stored scale at config time
        # (parse_norm, config_parser.py:1239-1240) and the kernel applies
        # it verbatim — the effective coefficient is user_scale / size
        alpha = extra.get("scale", 1e-4)
        beta = extra.get("pow", 0.75)
        x = to_nhwc(ins[0].value, info.channels, info.height, info.width)
        sq = jnp.square(x)
        half = size // 2
        acc = lax.reduce_window(
            sq, 0.0, lax.add, (1, 1, 1, size), (1, 1, 1, 1),
            ((0, 0), (0, 0), (0, 0), (half, size - 1 - half)))
        scale = jnp.power(1.0 + (alpha / size) * acc, -beta)
        return Argument(value=x * scale, mask=ins[0].mask)
