"""Attention layers (capability-add over the reference).

The reference's only attention is the composite ``simple_attention``
(`python/paddle/trainer_config_helpers/networks.py`) built from fc/expand/
softmax-scaling layers — which this framework also supports through the
DSL. This module adds a first-class fused multi-head attention layer on
top of ops/attention.py (Pallas flash kernel on TPU), because on TPU the
fused path is the difference between MXU-bound and HBM-bound attention.

``multi_head_attention``: inputs (query[, key_value]); self-attention when
only query is given. Heads live in one [S, S] projection per q/k/v plus an
output projection, scaled-dot-product core with the sequence mask taken
from the key/value Argument; optional causal masking for decoder use.
"""

from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)
from paddle_tpu.ops.attention import flash_attention


@register_layer("multi_head_attention")
class MultiHeadAttentionLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        size = cfg.size or in_infos[0].size
        assert size % int(cfg.attrs.get("num_heads", 1)) == 0, (
            "size must be divisible by num_heads")
        return ShapeInfo(size=size, is_sequence=True)

    def params(self, cfg, in_infos):
        size = cfg.size or in_infos[0].size
        q_in = in_infos[0].size
        kv_in = in_infos[-1].size  # == q_in for self-attention
        specs = {
            "wq": ParamSpec(shape=(q_in, size)),
            "wk": ParamSpec(shape=(kv_in, size)),
            "wv": ParamSpec(shape=(kv_in, size)),
            "wo": ParamSpec(shape=(size, size)),
        }
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        q_arg = ins[0]
        kv_arg = ins[-1]
        size = ctx.out_info.size
        heads = int(cfg.attrs.get("num_heads", 1))
        causal = bool(cfg.attrs.get("causal", False))
        hd = size // heads

        def split(x):  # [B,T,S] -> [B,N,T,hd]
            B, T, _ = x.shape
            return x.reshape(B, T, heads, hd).transpose(0, 2, 1, 3)

        q = split(q_arg.value @ params["wq"])
        k = split(kv_arg.value @ params["wk"])
        v = split(kv_arg.value @ params["wv"])
        kv_mask = kv_arg.mask
        sp = cfg.attrs.get("seq_parallel")
        axis = cfg.attrs.get("seq_axis", "seq")
        if sp and ctx.mesh is not None and axis in ctx.mesh.shape \
                and ctx.mesh.shape[axis] > 1:
            # sequence parallelism: the [B, N, T, D] tensors shard over
            # the mesh's sequence axis; ring rotates KV over ICI
            # (ppermute), ulysses all-to-alls heads<->sequence
            # (parallel/ring.py). Config-reachable via
            # multi_head_attention(seq_parallel="ring"|"ulysses") + a
            # trainer mesh carrying a "seq" axis (create_mesh(n_seq=...)).
            from paddle_tpu.parallel.ring import make_ring_attention
            fn = make_ring_attention(ctx.mesh, axis, kind=sp,
                                     causal=causal)
            out = fn(q, k, v, kv_mask)
        else:
            # no mesh / no seq axis: same math on one device (the knob
            # degrades gracefully so configs run everywhere)
            out = flash_attention(q, k, v, kv_mask, causal=causal)
        B, N, T, _ = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(B, T, size) @ params["wo"]
        if "wbias" in params:
            out = out + params["wbias"]
        if q_arg.mask is not None:
            out = out * q_arg.mask[..., None]
        return Argument(value=out, mask=q_arg.mask)
