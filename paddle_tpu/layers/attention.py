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

``mla_attention``: multi-head latent attention (DeepSeek-V2,
arXiv:2405.04434 §2.1, as DeepSeek-V3's ``config.json`` keys carry it):
queries and keys/values each pass through a low-rank path with an
RMSNorm inside, a ``rope``-wide part of every query and ONE key of that
width shared by all heads carry the position (rotary, pairs interleaved),
so a head's query and key are ``nope + rope`` wide and its value
``v_head_dim``; the core is the same ``flash_attention``.

``gqa_attention``: grouped-query causal self-attention with a head
count of its own (a decoder's layers may differ in it), ``num_kv_heads``
key-value heads shared by groups of query heads (K and V reach the
kernels at their own heads), an optional sliding ``window``, rotary by
halves (Hugging Face's ``rotate_half``: element ``j`` pairs with ``j +
r/2``) over the first ``rotary_dim`` elements of a head with plain or
YaRN frequencies (``yarn_inv_freq``; cos and sin times YaRN's
``attention_factor``), and a per-head sigmoid gate on the core's output
(``sigmoid(u W_g)``, one scalar a head and token; arXiv:2505.06708's
head-wise form), and with ``qk_norm`` an RMS
normalisation of every head of q and of k before the turn (``gq``,
``gk`` ``[head_dim]``: one scale for all the heads of a kind; float32
statistics). One layer type for windowed and full layers: the core
runs under the inner scope ``attn_core`` in both, and a windowed layer's
``state["counters"]`` names what its band cost the tiling:
``swa_pairs_visited`` (query-key pairs inside the tiles the forward
kernel's grid walks, a head) and ``swa_pairs_visible`` (pairs the mask
lets see).

Both layers' ``apply`` lies under four inner scopes, every operation
under exactly one, so that a device trace divides a layer's time by
part, forward, recomputed and backward alike (``docs/observability.md``):
``attn_qkv`` (the input to q, k, v at the core's layout, before any
rotary turn), ``attn_rope`` (the turns, the concatenations that assemble
q and k), ``mla_core`` / ``attn_core`` (the kernels) and ``attn_out``
(gate, head merge, ``wo``, mask); a grouped-query layer with a q/k
normalisation has a fifth, ``attn_qk_norm``, between the first two. They
are names in the compiled text and nothing else.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)
from paddle_tpu.layers.norm import rms_normalize
from paddle_tpu.ops.attention import flash_attention, walked_pairs


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


def rotary_interleaved(x, theta: float):
    """Rotary position embedding over the last axis of ``x [..., T, d]``
    (positions 0..T-1 along axis -2), the pairs interleaved: elements
    ``(2i, 2i+1)`` turn by ``pos * theta ** (-2i / d)``. Computed in
    float32; the result takes ``x``'s type."""
    T, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@register_layer("mla_attention")
class MlaAttentionLayer(LayerImpl):
    """Causal self-attention through latent (low-rank) paths; no bias.
    Output size = input size."""

    @staticmethod
    def _dims(cfg):
        a = cfg.attrs
        return (int(a["num_heads"]), int(a["q_lora_rank"]),
                int(a["kv_lora_rank"]), int(a["qk_nope_head_dim"]),
                int(a["qk_rope_head_dim"]), int(a["v_head_dim"]))

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=in_infos[0].size, is_sequence=True)

    def params(self, cfg, in_infos):
        d = in_infos[0].size
        heads, qr, kvr, nope, rope, dv = self._dims(cfg)
        ones = dict(init="const", initial_mean=1.0, initial_std=0.0)
        return {
            "wqa": ParamSpec(shape=(d, qr)),
            "qnorm": ParamSpec(shape=(qr,), **ones),
            "wqb": ParamSpec(shape=(qr, heads * (nope + rope))),
            "wkva": ParamSpec(shape=(d, kvr + rope)),
            "kvnorm": ParamSpec(shape=(kvr,), **ones),
            "wkvb": ParamSpec(shape=(kvr, heads * (nope + dv))),
            "wo": ParamSpec(shape=(heads * dv, d)),
        }

    def apply(self, cfg, params, ins, ctx):
        u = ins[0].value
        B, T, _ = u.shape
        heads, _qr, kvr, nope, rope, dv = self._dims(cfg)
        eps = cfg.attrs.get("epsilon", 1e-6)
        theta = float(cfg.attrs.get("rope_theta", 10000.0))

        def split(x, width):  # [B,T,H*w] -> [B,H,T,w]
            return x.reshape(B, T, heads, width).transpose(0, 2, 1, 3)

        with jax.named_scope("attn_qkv"):
            q = split(rms_normalize(u @ params["wqa"], params["qnorm"], eps)
                      @ params["wqb"], nope + rope)
            kva = u @ params["wkva"]
            kv = split(rms_normalize(kva[..., :kvr], params["kvnorm"], eps)
                       @ params["wkvb"], nope + dv)
        with jax.named_scope("attn_rope"):
            # one rotary key for all the heads
            k_rope = rotary_interleaved(kva[..., kvr:], theta)[:, None]
            q = jnp.concatenate(
                [q[..., :nope], rotary_interleaved(q[..., nope:], theta)],
                -1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, (B, heads, T, rope))], -1)
        with jax.named_scope("mla_core"):
            # 512 x 512 tiles: 168 MFLOP a grid step, a few times a
            # step's fixed cost (the kernels' default 256 is a quarter)
            out = flash_attention(q, k, kv[..., nope:], ins[0].mask,
                                  causal=True, block_q=512, block_k=512)
        with jax.named_scope("attn_out"):
            out = out.transpose(0, 2, 1, 3).reshape(B, T, heads * dv) \
                @ params["wo"]
            if ins[0].mask is not None:
                out = out * ins[0].mask[..., None].astype(out.dtype)
        return Argument(value=out, mask=ins[0].mask)


def yarn_inv_freq(rotary_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's rotary frequencies (arXiv:2309.00071 §3.2, as Hugging
    Face's ``_compute_yarn_parameters`` blends them), ``[rotary_dim/2]``:
    the pairs that turn more than ``beta_fast`` times over the original
    context keep ``theta^(-2i/r)``, those that turn less than
    ``beta_slow`` times are divided by ``factor``, a linear ramp
    between."""
    r = rotary_dim

    def dim_of(turns):
        return r * math.log(original_max_position / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), r - 1)
    i = np.arange(r // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-2.0 * i / r)
    return ((1.0 - ramp) * plain + ramp * plain / factor).astype(np.float32)


def rotary_halves(x, inv_freq, factor: float = 1.0):
    """Rotary position embedding over the first ``2 * len(inv_freq)``
    elements of the last axis of ``x [..., T, d]`` (positions 0..T-1
    along axis -2), by halves: element ``j`` of the turned part pairs
    with ``j + r/2`` and both turn by ``pos * inv_freq[j]``; cos and sin
    are multiplied by ``factor`` (YaRN's attention factor), the rest of
    the head is left as it is. Computed in float32; the result takes
    ``x``'s type."""
    T, half = x.shape[-2], len(inv_freq)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    xf = x.astype(jnp.float32)
    a, b, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)
    return out.astype(x.dtype)


@register_layer("gqa_attention")
class GqaAttentionLayer(LayerImpl):
    """Causal grouped-query self-attention, windowed or full, with
    partial rotary, an optional per-head q/k normalisation and a
    per-head output gate; no bias. Output size = input size."""

    @staticmethod
    def _dims(cfg):
        a = cfg.attrs
        return int(a["num_heads"]), int(a["num_kv_heads"]), int(a["head_dim"])

    def infer(self, cfg, in_infos):
        heads, kv, _ = self._dims(cfg)
        assert heads % kv == 0, "num_kv_heads must divide num_heads"
        return ShapeInfo(size=in_infos[0].size, is_sequence=True)

    def params(self, cfg, in_infos):
        d = in_infos[0].size
        heads, kv, hd = self._dims(cfg)
        specs = {"wq": ParamSpec(shape=(d, heads * hd)),
                 "wk": ParamSpec(shape=(d, kv * hd)),
                 "wv": ParamSpec(shape=(d, kv * hd)),
                 "wo": ParamSpec(shape=(heads * hd, d))}
        if cfg.attrs.get("gate", True):
            specs["wg"] = ParamSpec(shape=(d, heads))
        if cfg.attrs.get("qk_norm"):
            ones = dict(init="const", initial_mean=1.0, initial_std=0.0)
            specs.update(gq=ParamSpec(shape=(hd,), **ones),
                         gk=ParamSpec(shape=(hd,), **ones))
        return specs

    @staticmethod
    def _rotary(cfg, hd):
        """``(inv_freq, factor)`` of this layer's rotary scheme."""
        a = cfg.attrs
        r = int(a.get("rotary_dim") or hd)
        theta = float(a.get("rope_theta", 10000.0))
        yarn = a.get("yarn")
        if not yarn:
            return theta ** (-2.0 * np.arange(r // 2) / r), 1.0
        return (yarn_inv_freq(r, theta, float(yarn["factor"]),
                              int(yarn["original_max_position_embeddings"]),
                              float(yarn.get("beta_fast", 32.0)),
                              float(yarn.get("beta_slow", 1.0))),
                float(yarn.get("attention_factor")
                      or 0.1 * math.log(float(yarn["factor"])) + 1.0))

    def apply(self, cfg, params, ins, ctx):
        u = ins[0].value
        B, T, _ = u.shape
        heads, kv, hd = self._dims(cfg)
        window = cfg.attrs.get("window")
        window = int(window) if window else None
        block = int(cfg.attrs.get("block", 512))
        inv_freq, factor = self._rotary(cfg, hd)

        def split(x, n):  # [B,T,n*hd] -> [B,n,T,hd]
            return x.reshape(B, T, n, hd).transpose(0, 2, 1, 3)

        def project(w, n):
            with jax.named_scope("attn_qkv"):
                return split(u @ params[w], n)

        def normed(x, g):
            if not cfg.attrs.get("qk_norm"):
                return x
            with jax.named_scope("attn_qk_norm"):
                return rms_normalize(x, params[g],
                                     cfg.attrs.get("qk_norm_eps", 1e-6))

        def turn(x):
            with jax.named_scope("attn_rope"):
                return rotary_halves(x, inv_freq, factor)

        q = turn(normed(project("wq", heads), "gq"))
        k = turn(normed(project("wk", kv), "gk"))
        v = project("wv", kv)
        with jax.named_scope("attn_core"):
            out = flash_attention(q, k, v, ins[0].mask, causal=True,
                                  block_q=block, block_k=block,
                                  window=window)
        with jax.named_scope("attn_out"):
            if "wg" in params:
                gate = jax.nn.sigmoid(
                    (u @ params["wg"]).astype(jnp.float32))
                out = (out * gate.transpose(0, 2, 1)[..., None]) \
                    .astype(out.dtype)
            out = out.transpose(0, 2, 1, 3).reshape(B, T, heads * hd) \
                @ params["wo"]
            if ins[0].mask is not None:
                out = out * ins[0].mask[..., None].astype(out.dtype)
        state = None
        if window:
            visited, visible = walked_pairs(T, T, True, window, block, block)
            state = {"counters": {
                "swa_pairs_visited": jnp.asarray(visited, jnp.float32),
                "swa_pairs_visible": jnp.asarray(visible, jnp.float32)}}
        return Argument(value=out, mask=ins[0].mask, state=state)
