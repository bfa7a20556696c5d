"""What a decoder-only language model needs beyond its blocks
(capability-add): ``seq_shift``, a sequence moved along time so that
position i holds what position i + offset held, and ``lm_cost``, the
output head fused with its shifted cross-entropy.

``lm_cost`` (inputs: hidden states [B,T,d], the ids [B,T]): position i's
logits ``h_i W`` are scored against the id at ``i + shift``; the layer
emits each row's mean over the positions that have a target, times
``coeff``, so the trainer's batch mean is the mean over rows. The logits
are never held whole: rows of hidden states go through the head
``chunk`` at a time under ``jax.checkpoint``, so a chunk's [chunk, V]
float32 logits live only while its loss, or its gradient, is worked out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)


def shift_left(x, offset: int):
    """``y[:, i] = x[:, i + offset]`` along axis 1, zeros at the end."""
    if offset == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, offset)
    return jnp.pad(x[:, offset:], pad)


@register_layer("seq_shift")
class SeqShiftLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        k = int(cfg.attrs["offset"])
        a = ins[0]
        return Argument(value=shift_left(a.value, k),
                        mask=None if a.mask is None
                        else shift_left(a.mask, k))


def chunked_cross_entropy(h, w, targets, chunk: int):
    """``-log softmax(h W)[target]`` for every row of ``h [R, d]``, float32
    [R]; the logits ``chunk`` rows at a time, recomputed in the backward
    pass (the product in ``h``'s type with a float32 sum, the softmax in
    float32)."""
    R, d = h.shape
    chunk = min(int(chunk), R)
    pad = (-R) % chunk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))

    @jax.checkpoint
    def one(hc, tc):
        logits = jnp.dot(hc, w.astype(hc.dtype),
                         preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]

    ce = lax.map(lambda c: one(*c), (h.reshape(-1, chunk, d),
                                     targets.reshape(-1, chunk)))
    return ce.reshape(-1)[:R]


@register_layer("lm_cost")
class LmCostLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return ShapeInfo(size=1)

    def params(self, cfg, in_infos):
        return {"w0": ParamSpec(shape=(in_infos[0].size,
                                       int(cfg.attrs["vocab_size"])))}

    def apply(self, cfg, params, ins, ctx):
        h, ids = ins[0].value, ins[1].value.astype(jnp.int32)
        B, T, d = h.shape
        k = int(cfg.attrs.get("shift", 1))
        live = (ins[1].mask if ins[1].mask is not None
                else jnp.ones((B, T), jnp.float32))
        has_target = shift_left(live, k)          # [B,T], 0 on the last k
        ce = chunked_cross_entropy(
            h.reshape(B * T, d), params["w0"],
            shift_left(ids, k).reshape(B * T),
            int(cfg.attrs.get("chunk", 2048))).reshape(B, T)
        row = jnp.sum(ce * has_target, axis=1) / jnp.maximum(
            jnp.sum(has_target, axis=1), 1.0)
        return Argument(value=(float(cfg.attrs.get("coeff", 1.0))
                               * row).reshape(B, 1))
