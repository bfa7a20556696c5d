"""The gated short convolution as a layer type (capability-add): a token
mixer that is no attention, LiquidAI's LFM2 operator.

``dsl.short_conv(x, kernel=3)``: ``[B | C | X] = u W_in``, ``y = (C *
conv_k(B * X)) W_out`` with ``conv_k`` a depthwise causal convolution of
``kernel`` taps over time (``ops/short_conv.py``), no bias; output size
= input size. Parameters ``wi [d, 3d]``, ``wc [kernel, d]`` (tap
``kernel - 1`` weighs the current step), ``wo [d, d]``.

``apply`` lies under three inner scopes, every operation under exactly
one, so that a device trace divides the layer's time by part, forward,
recomputed and backward alike (``docs/observability.md``): ``sconv_in``
(the ``W_in`` product), ``sconv_core`` (the gates and the convolution:
bound by HBM, where the two products beside it are bound by the matrix
unit) and ``sconv_out`` (the ``W_out`` product, the mask).
"""

from __future__ import annotations

import jax

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)
from paddle_tpu.ops.short_conv import gated_short_conv


@register_layer("short_conv")
class ShortConvLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return ShapeInfo(size=in_infos[0].size, is_sequence=True)

    def params(self, cfg, in_infos):
        d, k = in_infos[0].size, int(cfg.attrs.get("kernel", 3))
        return {"wi": ParamSpec(shape=(d, 3 * d)),
                "wc": ParamSpec(shape=(k, d)),
                "wo": ParamSpec(shape=(d, d))}

    def apply(self, cfg, params, ins, ctx):
        u, mask = ins[0].value, ins[0].mask
        with jax.named_scope("sconv_in"):
            bcx = u @ params["wi"]
        y = gated_short_conv(bcx, params["wc"], mask)
        with jax.named_scope("sconv_out"):
            out = y @ params["wo"]
            if mask is not None:
                out = out * mask[..., None].astype(out.dtype)
        return Argument(value=out, mask=mask)
