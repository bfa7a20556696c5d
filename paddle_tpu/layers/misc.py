"""Long-tail layer types: elementwise, shape, and image utility layers.

Each class cites its reference implementation in
``paddle/gserver/layers/``. All are pure jnp functions — gradients come
from ``jax.grad``; anything image-shaped flows NHWC (see conv.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)
from paddle_tpu.layers.conv import to_nhwc
from paddle_tpu.ops.short_conv import depthwise_time_conv


@register_layer("agent")
class AgentLayer(LayerImpl):
    """``AgentLayer.cpp``: forwards another layer's output unchanged (the
    reference wires it by name across sub-model boundaries; here groups
    pass boundaries explicitly, so agent is identity). In the expanded
    wire format (recurrent sub-models) memory agents have *no* config
    inputs and are fed at runtime — the executor treats an input-less
    agent as a feed slot (``feed_slot``)."""

    feed_slot = True

    def infer(self, cfg, in_infos):
        if not in_infos:
            return ShapeInfo(size=cfg.size or 0,
                             is_sequence=cfg.attrs.get("is_sequence", False))
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        return ins[0]


@register_layer("scatter_agent")
class ScatterAgentLayer(LayerImpl):
    """``AgentLayer.cpp:209`` (``REGISTER_LAYER(scatter_agent, ...)``):
    inside an expanded recurrent sub-model, the in-link boundary that
    receives one timestep's frame of the outer sequence. The reference
    wires it at runtime via ``setRealLayer`` (``AgentLayer.h:133``); here
    the group executor feeds it by name each scan step, so it is a feed
    slot when input-less and an identity connector when wired
    explicitly."""

    feed_slot = True

    def infer(self, cfg, in_infos):
        if not in_infos:
            return ShapeInfo(size=cfg.size or 0,
                             is_sequence=cfg.attrs.get("is_sequence", False))
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        return ins[0]


@register_layer("gather_agent")
class GatherAgentLayer(LayerImpl):
    """``AgentLayer.cpp:209`` (``REGISTER_LAYER(gather_agent, ...)``):
    collects the per-frame outputs of a recurrent sub-model back into one
    sequence (``GatherAgentLayer::forward`` copies each real layer's rows
    via ``copyByRowIndex``). In the scan-based engine the stacking happens
    inside the group body, so a gather over one wired input is identity;
    several wired inputs concatenate along time in order — the flat-frame
    equivalent of gathering multiple real layers."""

    def infer(self, cfg, in_infos):
        if not in_infos:
            return ShapeInfo(size=cfg.size or 0, is_sequence=True)
        return dataclasses.replace(in_infos[0], is_sequence=True)

    def apply(self, cfg, params, ins, ctx):
        if len(ins) == 1:
            return ins[0]
        vals = [a.value for a in ins]
        masks = [a.mask if a.mask is not None
                 else jnp.ones(a.value.shape[:2], jnp.float32) for a in ins]
        return Argument(value=jnp.concatenate(vals, axis=1),
                        mask=jnp.concatenate(masks, axis=1))


@register_layer("out_prod")
class OuterProdLayer(LayerImpl):
    """``OuterProdLayer.cpp:48``: per-sample outer product of two vectors,
    out[b] = flatten(x0[b] ⊗ x1[b]) — (B, d0) × (B, d1) → (B, d0*d1).
    Used by neural-turing-machine-style addressing. One batched einsum on
    the MXU instead of the reference's per-row GEMM loop."""

    def infer(self, cfg, in_infos):
        if in_infos[0].is_sequence != in_infos[1].is_sequence:
            raise ValueError(
                "out_prod needs two inputs of the same kind (both "
                "sequence or both non-sequence); the reference pairs "
                "rows 1:1 (OuterProdLayer.cpp CHECK_EQ on heights)")
        return ShapeInfo(size=in_infos[0].size * in_infos[1].size,
                         is_sequence=in_infos[0].is_sequence)

    def apply(self, cfg, params, ins, ctx):
        x0, x1 = ins[0].value, ins[1].value
        out = jnp.einsum("...i,...j->...ij", x0, x1)
        out = out.reshape(out.shape[:-2] + (x0.shape[-1] * x1.shape[-1],))
        from paddle_tpu.layers.common import _first_mask
        return Argument(value=out, mask=_first_mask(ins))


@register_layer("data_norm")
class DataNormLayer(LayerImpl):
    """``DataNormLayer.cpp:21``: normalize dense input features with
    *precomputed* statistics held in one static 5×size parameter
    (rows: min, 1/(max-min), mean, 1/std, 1/10^j — layout from
    ``DataNormLayer::init``). Strategies: z-score (x-mean)*stdRecip,
    min-max (x-min)*rangeRecip, decimal-scaling x*decimalRecip. The
    parameter is static (never trained); gradients still flow to the
    input through the affine map, matching ``DataNormLayer::backward``."""

    def infer(self, cfg, in_infos):
        return dataclasses.replace(in_infos[0])

    def params(self, cfg, in_infos):
        return {"w0": ParamSpec(shape=(5, in_infos[0].size), init="zeros",
                                is_static=True)}

    def apply(self, cfg, params, ins, ctx):
        w = params["w0"]
        mode = cfg.attrs.get("data_norm_strategy", "z-score")
        x = ins[0].value
        if mode == "z-score":
            out = (x - w[2]) * w[3]
        elif mode == "min-max":
            out = (x - w[0]) * w[1]
        elif mode == "decimal-scaling":
            out = x * w[4]
        else:
            raise ValueError(
                f"unknown data normalization strategy {mode!r} "
                "(z-score | min-max | decimal-scaling)")
        return ins[0].with_value(out)


@register_layer("clip")
class ClipLayer(LayerImpl):
    """``ClipLayer.cpp``: elementwise clamp to [min, max]."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        lo = cfg.attrs.get("min", -1.0)
        hi = cfg.attrs.get("max", 1.0)
        return ins[0].with_value(jnp.clip(ins[0].value, lo, hi))


@register_layer("power")
class PowerLayer(LayerImpl):
    """``PowerLayer.cpp``: out = x ** p with a per-sample exponent; weight
    input first ([B,1]), data second — same convention as scaling."""

    def infer(self, cfg, in_infos):
        return in_infos[1]

    def apply(self, cfg, params, ins, ctx):
        p, x = ins[0].value, ins[1].value
        p = p.reshape((p.shape[0],) + (1,) * (x.ndim - 1))
        return ins[1].with_value(x ** p)


@register_layer("prelu")
class PReluLayer(LayerImpl):
    """``ParameterReluLayer.cpp``: out = max(0,x) + alpha*min(0,x); alpha
    learned. ``partial_sum`` groups features sharing one alpha (1 =
    per-feature, size = one shared alpha), as in the reference config."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def params(self, cfg, in_infos):
        partial = cfg.attrs.get("partial_sum", 1)
        n = in_infos[0].size // partial
        # the reference initializes the slopes smart-normal like any
        # input parameter (create_input_parameter with NO dims recorded,
        # so smart std = 1/sqrt(size)) — NOT the torch-style 0.25 constant
        return {"w0": ParamSpec(shape=(n,), wire_dims=())}

    def apply(self, cfg, params, ins, ctx):
        x = ins[0].value
        partial = cfg.attrs.get("partial_sum", 1)
        alpha = jnp.repeat(params["w0"], partial)
        return ins[0].with_value(
            jnp.maximum(x, 0.0) + alpha * jnp.minimum(x, 0.0))


@register_layer("maxout")
class MaxOutLayer(LayerImpl):
    """``MaxOutLayer.cpp``: channels split into groups, max over the group
    axis. Image layers: C -> C/groups."""

    def infer(self, cfg, in_infos):
        g = cfg.attrs["groups"]
        info = in_infos[0]
        if info.channels:
            return ShapeInfo(size=info.size // g, channels=info.channels // g,
                             height=info.height, width=info.width)
        return ShapeInfo(size=info.size // g)

    def apply(self, cfg, params, ins, ctx):
        g = cfg.attrs["groups"]
        info = ctx.in_infos[0]
        x = ins[0].value
        if info.channels:
            x = to_nhwc(x, info.channels, info.height, info.width)
            b, h, w, c = x.shape
            # reference groups ADJACENT channels: out i = max over input
            # channels [i*g, i*g + g)  (Matrix.cpp maxoutForward)
            x = x.reshape(b, h, w, c // g, g).max(axis=4)
            return Argument(value=x)
        b = x.shape[0]
        return ins[0].with_value(x.reshape(b, -1, g).max(axis=2))


@register_layer("multiplex")
class MultiplexLayer(LayerImpl):
    """``MultiplexLayer.cpp``: first input is an index column; output row b
    copies row b of data input index[b]."""

    def infer(self, cfg, in_infos):
        return in_infos[1]

    def apply(self, cfg, params, ins, ctx):
        idx = ins[0].value.reshape(-1).astype(jnp.int32)
        stack = jnp.stack([a.value for a in ins[1:]], axis=0)  # [N, B, D]
        out = jnp.take_along_axis(
            stack, idx[None, :, None], axis=0)[0]
        return ins[1].with_value(out)


@register_layer("eos_id")
class EosIdCheckLayer(LayerImpl):
    """``EosIdCheckLayer.cpp``: 1.0 where the input id equals eos_id."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=1, is_sequence=in_infos[0].is_sequence)

    def apply(self, cfg, params, ins, ctx):
        eos = cfg.attrs["eos_id"]
        ids = ins[0].value
        if ids.ndim > 2:
            ids = ids[..., 0]
        out = (ids == eos).astype(jnp.float32)[..., None]
        return Argument(value=out, mask=ins[0].mask)


@register_layer("sampling_id")
class SamplingIdLayer(LayerImpl):
    """``SamplingIdLayer.cpp``: sample one id per row from the input
    distribution (used by stochastic generation)."""

    needs_rng = True

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=in_infos[0].size,
                         is_sequence=in_infos[0].is_sequence)

    def apply(self, cfg, params, ins, ctx):
        logits = jnp.log(jnp.maximum(ins[0].value, 1e-20))
        ids = jax.random.categorical(ctx.layer_rng(cfg.name), logits, axis=-1)
        return Argument(value=ids.astype(jnp.int32), mask=ins[0].mask)


@register_layer("print")
class PrintLayer(LayerImpl):
    """``PrintLayer.cpp``: debug-print the input on every forward, pass it
    through unchanged (host callback via jax.debug.print)."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        jax.debug.print(cfg.name + ": {}", ins[0].value)
        return ins[0]


@register_layer("resize")
class ResizeLayer(LayerImpl):
    """``ResizeLayer.cpp``: reinterpret the batch as rows of ``size``
    (total element count preserved, batch dim changes)."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=cfg.size)

    def apply(self, cfg, params, ins, ctx):
        return Argument(value=ins[0].value.reshape(-1, cfg.size))


@register_layer("rotate")
class RotateLayer(LayerImpl):
    """``RotateLayer.cpp``: rotate each CHW image 90 degrees clockwise
    (the reference calls ``Matrix::rotate(..., true /*clock-wise*/)``:
    out[j, i] = in[H-1-i, j])."""

    def infer(self, cfg, in_infos):
        info = in_infos[0]
        return ShapeInfo(size=info.size, channels=info.channels,
                         height=info.width, width=info.height)

    def apply(self, cfg, params, ins, ctx):
        info = ctx.in_infos[0]
        x = to_nhwc(ins[0].value, info.channels, info.height, info.width)
        # clockwise: out[a, b] = in[H-1-b, a]
        x = jnp.swapaxes(jnp.flip(x, axis=1), 1, 2)
        return Argument(value=x)


@register_layer("bilinear_interp")
class BilinearInterpLayer(LayerImpl):
    """``BilinearInterpLayer.cpp``: bilinear resize to (out_size_y,
    out_size_x); XLA gather/weighted-sum via jax.image.resize."""

    def infer(self, cfg, in_infos):
        info = in_infos[0]
        oh = cfg.attrs["out_size_y"]
        ow = cfg.attrs["out_size_x"]
        return ShapeInfo(size=info.channels * oh * ow, channels=info.channels,
                         height=oh, width=ow)

    def apply(self, cfg, params, ins, ctx):
        info = ctx.in_infos[0]
        x = to_nhwc(ins[0].value, info.channels, info.height, info.width)
        oh, ow = cfg.attrs["out_size_y"], cfg.attrs["out_size_x"]
        out = jax.image.resize(x, (x.shape[0], oh, ow, x.shape[3]),
                               method="bilinear")
        return Argument(value=out)


@register_layer("pad")
class PadLayer(LayerImpl):
    """``PadLayer.cpp`` / ``function/PadOp``: zero-pad along C/H/W with
    [before, after] pairs (pad_c, pad_h, pad_w attrs)."""

    def infer(self, cfg, in_infos):
        info = in_infos[0]
        pc = cfg.attrs.get("pad_c", [0, 0])
        ph = cfg.attrs.get("pad_h", [0, 0])
        pw = cfg.attrs.get("pad_w", [0, 0])
        c = info.channels + sum(pc)
        h = info.height + sum(ph)
        w = info.width + sum(pw)
        return ShapeInfo(size=c * h * w, channels=c, height=h, width=w)

    def apply(self, cfg, params, ins, ctx):
        info = ctx.in_infos[0]
        x = to_nhwc(ins[0].value, info.channels, info.height, info.width)
        pc = cfg.attrs.get("pad_c", [0, 0])
        ph = cfg.attrs.get("pad_h", [0, 0])
        pw = cfg.attrs.get("pad_w", [0, 0])
        out = jnp.pad(x, ((0, 0), tuple(ph), tuple(pw), tuple(pc)))
        return Argument(value=out)


@register_layer("crop")
class CropLayer(LayerImpl):
    """``CropLayer.cpp``: crop from ``axis`` onward with per-axis offsets;
    target geometry from the second input (reference semantics) or the
    ``shape`` attr. Axes follow the reference's NCHW numbering
    (0=batch 1=C 2=H 3=W)."""

    def infer(self, cfg, in_infos):
        info = in_infos[0]
        axis = cfg.attrs.get("axis", 2)
        if len(in_infos) > 1:
            ref = in_infos[1]
            c, h, w = ref.channels, ref.height, ref.width
        else:
            # shape spellings: 4 values = full NCHW (batch extent ignored,
            # SPMD owns the batch), 3 = (c, h, w), fewer = extents for
            # NCHW axes [axis..3]
            shape = list(cfg.attrs["shape"])
            dims = [info.channels, info.height, info.width]
            if len(shape) == 4:
                shape = shape[1:]
            start = 1 if len(shape) == 3 else max(axis, 1)
            for ax, s in zip(range(start, 4), shape):
                dims[ax - 1] = s
            c, h, w = dims
        c = c if axis <= 1 else info.channels
        h = h if axis <= 2 else info.height
        w = w if axis <= 3 else info.width
        return ShapeInfo(size=c * h * w, channels=c, height=h, width=w)

    def apply(self, cfg, params, ins, ctx):
        info = ctx.in_infos[0]
        out = ctx.out_info
        x = to_nhwc(ins[0].value, info.channels, info.height, info.width)
        axis = cfg.attrs.get("axis", 2)
        offs = cfg.attrs.get("offset", [0] * (4 - axis))
        # offsets are listed for axes [axis..3] in NCHW order
        oc = oh = ow = 0
        for ax, off in zip(range(axis, 4), offs):
            if ax == 1:
                oc = off
            elif ax == 2:
                oh = off
            elif ax == 3:
                ow = off
        return Argument(value=lax.dynamic_slice(
            x, (0, oh, ow, oc),
            (x.shape[0], out.height, out.width, out.channels)))


@register_layer("conv_shift")
class ConvShiftLayer(LayerImpl):
    """``ConvShiftLayer.cpp``: circular correlation — out[i] = sum_j
    a[(i + j - (M-1)/2) mod N] * b[j], b per-sample of odd length M (NTM
    attention-shift style)."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        a, b = ins[0].value, ins[1].value
        N, M = a.shape[1], b.shape[1]
        half = (M - 1) // 2
        idx = (jnp.arange(N)[:, None] + jnp.arange(M)[None, :] - half) % N
        # gathered[b_, i, j] = a[b_, idx[i, j]]
        gathered = a[:, idx]
        return ins[0].with_value(jnp.einsum("bij,bj->bi", gathered, b))


@register_layer("row_conv")
class RowConvLayer(LayerImpl):
    """``RowConvLayer.cpp`` / ``function/RowConvOp``: lookahead row
    convolution over future timesteps (DeepSpeech2): out[t] = sum_{j<k}
    x[t+j] * w[j] elementwise per feature."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def params(self, cfg, in_infos):
        k = cfg.attrs["context_length"]
        return {"w0": ParamSpec(shape=(k, in_infos[0].size))}

    def apply(self, cfg, params, ins, ctx):
        x, mask = ins[0].value, ins[0].mask  # [B, T, D]
        xm = x if mask is None else x * mask[:, :, None]
        # the short convolution's taps, looking ahead instead of back
        out = depthwise_time_conv(xm, params["w0"],
                                  causal=False).astype(x.dtype)
        if mask is not None:
            out = out * mask[:, :, None]
        return Argument(value=out, mask=mask)


@register_layer("tensor")
class TensorLayer(LayerImpl):
    """``TensorLayer.cpp``: bilinear form out[k] = x W_k y^T, parameter
    stored [Dx, size*Dy] as in the reference."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=cfg.size)

    def params(self, cfg, in_infos):
        dx, dy = in_infos[0].size, in_infos[1].size
        # wire layout is the reference's 3-dim (Dx, Dy, K) block form
        # (config_parser TensorLayer dims); engine packs [Dx, K*Dy]
        specs = {"w0": ParamSpec(shape=(dx, cfg.size * dy),
                                 wire_dims=(dx, dy, cfg.size))}
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(cfg.size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        x, y = ins[0].value, ins[1].value
        dy = y.shape[-1]
        w = params["w0"].reshape(x.shape[-1], cfg.size, dy)
        out = jnp.einsum("bi,ikj,bj->bk", x, w, y)
        if "wbias" in params:
            out = out + params["wbias"]
        return Argument(value=out)


@register_layer("selective_fc")
class SelectiveFcLayer(LayerImpl):
    """``SelectiveFullyConnectedLayer.cpp``: fc where only selected output
    columns are meaningful; selection is the (optional) second input as a
    0/1 row mask. On TPU the dense matmul runs whole (MXU-friendly) and the
    mask zeroes non-selected columns AFTER the activation (the reference
    computes only selected columns, leaving the rest exactly zero), so the
    activation is consumed here, not by the executor."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=cfg.size)

    def params(self, cfg, in_infos):
        specs = {"w0": ParamSpec(shape=(in_infos[0].size, cfg.size),
                                 wire_sparse=False)}
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(cfg.size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        from paddle_tpu.layers.activations import apply_activation
        out = ins[0].value @ params["w0"]
        if "wbias" in params:
            out = out + params["wbias"]
        act = cfg.attrs.get("active_type", "linear")
        if act and act != "linear":
            out = apply_activation(act, out)
        if len(ins) > 1:
            out = out * ins[1].value
        return Argument(value=out)


@register_layer("blockexpand")
class BlockExpandLayer(LayerImpl):
    """``BlockExpandLayer.cpp``: slide a block window over the image and
    emit one sequence element per block position (im2col-as-sequence)."""

    def _geom(self, cfg, info):
        bx, by = cfg.attrs["block_x"], cfg.attrs["block_y"]
        sx = cfg.attrs.get("stride_x", 1)
        sy = cfg.attrs.get("stride_y", 1)
        px = cfg.attrs.get("padding_x", 0)
        py = cfg.attrs.get("padding_y", 0)
        ow = (info.width + 2 * px - bx) // sx + 1
        oh = (info.height + 2 * py - by) // sy + 1
        return bx, by, sx, sy, px, py, ow, oh

    def infer(self, cfg, in_infos):
        info = in_infos[0]
        bx, by, _, _, _, _, ow, oh = self._geom(cfg, info)
        return ShapeInfo(size=info.channels * bx * by, is_sequence=True)

    def apply(self, cfg, params, ins, ctx):
        info = ctx.in_infos[0]
        x = to_nhwc(ins[0].value, info.channels, info.height, info.width)
        bx, by, sx, sy, px, py, ow, oh = self._geom(cfg, info)
        patches = lax.conv_general_dilated_patches(
            x, (by, bx), (sy, sx), [(py, py), (px, px)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        B = x.shape[0]
        seq = patches.reshape(B, oh * ow, -1)
        return Argument(value=seq,
                        mask=jnp.ones((B, oh * ow), jnp.float32))


@register_layer("sub_nested_seq")
class SubNestedSequenceLayer(LayerImpl):
    """``SubNestedSequenceLayer.cpp``: from a 2-level nested sequence,
    select one sub-sequence per outer sequence (selection index = second
    input). Padded layout: positions of the chosen sub-sequence are
    compacted to the front via an argsort-gather."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=in_infos[0].size, is_sequence=True)

    def apply(self, cfg, params, ins, ctx):
        a, sel = ins[0], ins[1]
        x, mask, starts = a.value, a.mask, a.sub_starts_mask
        if starts is None:
            raise ValueError("sub_nested_seq input must be a nested sequence")
        idx = sel.value.reshape(-1).astype(jnp.int32)  # [B]
        sub_id = jnp.cumsum(starts, axis=1) - 1  # [B, T]
        keep = (sub_id == idx[:, None]) & (mask > 0)
        T = x.shape[1]
        # stable compaction: kept positions first, original order preserved
        order = jnp.argsort(jnp.where(keep, 0, 1) * T + jnp.arange(T)[None, :],
                            axis=1)
        out = jnp.take_along_axis(x, order[:, :, None], axis=1)
        new_mask = jnp.take_along_axis(keep.astype(jnp.float32), order, axis=1)
        return Argument(value=out * new_mask[:, :, None], mask=new_mask)


@register_layer("get_output")
class GetOutputLayer(LayerImpl):
    """Reads a named auxiliary output of the previous layer (the
    reference's ``get_output_layer`` for e.g. lstm_step's state)."""

    def infer(self, cfg, in_infos):
        return dataclasses.replace(in_infos[0], size=cfg.size
                                   or in_infos[0].size)

    def apply(self, cfg, params, ins, ctx):
        arg = cfg.attrs.get("arg_name", "state")
        return Argument(value=ins[0].state[arg], mask=ins[0].mask)


@register_layer("featmap_expand")
class FeatureMapExpandLayer(LayerImpl):
    """``FeatureMapExpandLayer.cpp``: repeat the input N times along the
    feature axis — whole-vector tiling by default (as_row_vector), or
    per-element repetition when user_arg is "as_col_vec". Used by
    ``repeat_layer`` and layer_math broadcasting."""

    def infer(self, cfg, in_infos):
        n = cfg.attrs.get("num_filters", 1)
        info = in_infos[0]
        return ShapeInfo(size=info.size * n, is_sequence=info.is_sequence)

    def apply(self, cfg, params, ins, ctx):
        n = cfg.attrs.get("num_filters", 1)
        x = ins[0].value
        if cfg.attrs.get("user_arg") == "as_col_vec":
            out = jnp.repeat(x, n, axis=-1)
        else:
            out = jnp.tile(x, (1,) * (x.ndim - 1) + (n,))
        return ins[0].with_value(out)


@register_layer("row_l2_norm")
class RowL2NormLayer(LayerImpl):
    """``RowL2NormLayer.cpp``: x / ||x||_2 per row."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        x = ins[0].value
        norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)) + 1e-12
        return ins[0].with_value(x / norm)


@register_layer("cos_vm")
class CosSimVecMatLayer(LayerImpl):
    """``CosSimVecMatLayer.cpp``: cosine similarity of input 0's vector
    [B, D] against each of the `size` rows of input 1 [B, size*D]."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=cfg.size)

    def apply(self, cfg, params, ins, ctx):
        vec, mat = ins[0].value, ins[1].value
        n = cfg.size
        d = vec.shape[-1]
        rows = mat.reshape(mat.shape[0], n, d)
        scale = cfg.attrs.get("cos_scale", 1.0)
        dot = jnp.einsum("bd,bnd->bn", vec, rows)
        denom = (jnp.linalg.norm(vec, axis=-1, keepdims=True)
                 * jnp.linalg.norm(rows, axis=-1) + 1e-12)
        return Argument(value=scale * dot / denom)


@register_layer("kmax_seq_score")
class KmaxSeqScoreLayer(LayerImpl):
    """``KmaxSeqScoreLayer.cpp``: top-beam_size timestep indices of a
    per-timestep score sequence, by descending score."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=cfg.attrs.get("beam_size", 1))

    def apply(self, cfg, params, ins, ctx):
        k = cfg.attrs.get("beam_size", 1)
        scores = ins[0].value
        if scores.ndim == 3:
            scores = scores[..., 0]
        if ins[0].mask is not None:
            scores = jnp.where(ins[0].mask > 0, scores, -jnp.inf)
        _, idx = jax.lax.top_k(scores, k)
        return Argument(value=idx.astype(jnp.int32))


@register_layer("sum_to_one_norm")
class SumToOneNormLayer(LayerImpl):
    """``SumToOneNormLayer.cpp``: x / sum(x) per row."""

    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        x = ins[0].value
        s = jnp.sum(x, axis=-1, keepdims=True) + 1e-12
        return ins[0].with_value(x / s)


@register_layer("convex_comb")
class LinearCombLayer(LayerImpl):
    """``LinearChainCombLayer`` ("convex_comb", the reference's
    linear_comb_layer): weights [B, m] linearly combine the m rows of
    input 1 [B, m*size] into [B, size]."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=cfg.size)

    def apply(self, cfg, params, ins, ctx):
        w, v = ins[0].value, ins[1].value
        d = cfg.size
        m = v.shape[-1] // d
        rows = v.reshape(v.shape[0], m, d)
        return Argument(value=jnp.einsum("bm,bmd->bd", w[:, :m], rows))
