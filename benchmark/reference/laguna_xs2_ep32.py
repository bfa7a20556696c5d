"""Plain reference of Laguna-XS.2's decoder as the configuration cuts it
(poolside/Laguna-XS.2 ``config.json``; the equations are written down
from its keys): float32, ``highest``, ``jax.numpy`` only, nothing of the
program imported.

Block ``l``: ``h = x + Attn_l(RMSNorm(x))``, ``x' = h + FFN_l(RMSNorm(h))``,
RMSNorm(v) = v / sqrt(mean(v^2) + eps) * g; a final RMSNorm, an untied
head, the mean over positions of CE(logits_i, t_{i+1}).

``Attn_l(u)``: ``q = u W_q`` in ``H_l`` heads of 128 (``H_l`` is
``num_attention_heads_per_layer[l]``), ``k = u W_k``, ``v = u W_v`` in
``num_key_value_heads`` heads, no bias. Rotary by halves (Hugging Face's
``rotate_half``: element ``j`` pairs with ``j + r/2``) over the first
``r = partial_rotary_factor * 128`` elements of a head, the rest
unturned. A ``sliding_attention`` layer: ``inv_freq_i = theta^(-2i/r)``.
A ``full_attention`` layer, YaRN: with ``b = rope_theta``, ``f =
factor``, ``L = original_max_position_embeddings``, ``dim(n) = r ln(L /
(2 pi n)) / (2 ln b)``, ``low = floor(dim(beta_fast))``, ``high =
ceil(dim(beta_slow))`` clipped to ``[0, r - 1]``, ``ramp_i = clip((i -
low) / (high - low), 0, 1)``, ``inv_freq_i = (1 - ramp_i) b^(-2i/r) +
ramp_i b^(-2i/r) / f``, and cos and sin multiplied by
``attention_factor``. Query head ``n`` attends key-value head ``n //
(H_l / kv)``: ``a = softmax(q k^T / sqrt(128) + mask) v``; query ``i``
sees key ``j`` iff ``j <= i`` and, on a sliding layer, ``i - j <
sliding_window``. ``g = sigmoid(u W_g)`` in ``[T, H_l]``; ``Attn_l(u) =
concat_n(g_n a_n) W_o``.

``FFN_l``: by ``mlp_layer_types[l]`` a SwiGLU of ``intermediate_size``
or the expert layer: ``s = sigmoid(u W_r)`` over all ``num_experts``,
the top ``k`` of ``s + b`` chosen, weights ``s[chosen] / sum(s[chosen])
* moe_routed_scaling_factor``, ``y = sum_i w_i E_i(u) + Shared(u)``.

Departures from the published description, each the configuration's
(its ``assumed`` names the three points ``config.json`` does not fix:
the gate's form, the router's score function, no q/k normalisation):

- **the chip's share**: the router scores all ``num_experts``; of the
  chosen experts only those held here (``expert_offset .. expert_offset
  + experts_held``) add to the result, what the absent ones would have
  added is left out, and that partial sum plus the shared expert goes
  on. No sort and no dispatch: every held expert over every token,
  weighted by the token's weight for it (0 where it was not chosen);
- the vocabulary is the slice the configuration gives, the depth the
  length of its ``layer_types``;
- the selection bias is a static leaf (zeros here) and takes no gradient;
- the router's product is float32 at ``highest`` in every arithmetic
  (the configuration states that the router stays float32); every other
  product goes through ``arith``.

To fit beside the check's 24 bytes a parameter at the timed size (one
row of 8,192 tokens: a head's scores are 268 MB in float32, 64 heads'
17 GB), rows go one at a time (``lax.map``), every block and every
query head's attention is a ``jax.checkpoint`` (heads one at a time,
``lax.map``, each reading its group's K and V), and a row's logits
([S, V] float32) are the chunk in which the head is computed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
SLIDING = "sliding_attention"


def _args(cfg):
    return cfg["model"]["args"]


def _kind(m, i):
    """Layer ``i``'s attention leaf prefix: its name says its kind."""
    return f"_blk{i}_" + ("swa" if m["layer_types"][i] == SLIDING
                          else "attn")


def leaves(cfg):
    m = _args(cfg)
    d, v, hd = m["hidden_size"], m["vocab_size"], m["head_dim"]
    kv = m["num_key_value_heads"]
    out = {"_embed.w0": ((v, d), "normal"),
           "_out_norm.w0": ((d,), "ones"),
           "_out_head.w0": ((d, v), "normal")}
    for i, heads in enumerate(m["num_attention_heads_per_layer"]):
        a = _kind(m, i)
        out.update({f"_blk{i}_a_norm.w0": ((d,), "ones"),
                    f"_blk{i}_f_norm.w0": ((d,), "ones"),
                    f"{a}.wq": ((d, heads * hd), "normal"),
                    f"{a}.wk": ((d, kv * hd), "normal"),
                    f"{a}.wv": ((d, kv * hd), "normal"),
                    f"{a}.wo": ((heads * hd, d), "normal")})
        if m.get("gating", True):
            out[f"{a}.wg"] = ((d, heads), "normal")
        if m["mlp_layer_types"][i] == "dense":
            f = m["intermediate_size"]
            out.update({f"_blk{i}_mlp.wg": ((d, f), "normal"),
                        f"_blk{i}_mlp.wu": ((d, f), "normal"),
                        f"_blk{i}_mlp.wd": ((f, d), "normal")})
            continue
        e, h = m["num_experts"], m["moe_intermediate_size"]
        held = m.get("experts_held") or e
        hs = m.get("shared_expert_intermediate_size", 0)
        t = f"_blk{i}_moe"
        out.update({f"{t}.wr": ((d, e), "normal"),
                    f"{t}.br": ((e,), "static"),
                    f"{t}.wg": ((held, d, h), "normal"),
                    f"{t}.wu": ((held, d, h), "normal"),
                    f"{t}.wd": ((held, h, d), "normal")})
        if hs:
            out.update({f"{t}.sg": ((d, hs), "normal"),
                        f"{t}.su": ((d, hs), "normal"),
                        f"{t}.sd": ((hs, d), "normal")})
    return out


# ------------------------------------------------------------ one row
def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def inv_freq(rope, head_dim):
    """``(inv_freq [r/2], attention factor)`` of one kind of layer's
    ``rope_parameters`` entry."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    b = float(rope.get("rope_theta", 10000.0))
    plain = [b ** (-2.0 * i / r) for i in range(r // 2)]
    if rope.get("rope_type", "default") != "yarn":
        return jnp.asarray(plain, jnp.float32), 1.0
    f, L = float(rope["factor"]), rope["original_max_position_embeddings"]

    def dim_of(turns):
        return r * math.log(L / (2 * math.pi * turns)) / (2 * math.log(b))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), r - 1)
    out = []
    for i, p in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append((1 - ramp) * p + ramp * p / f)
    return jnp.asarray(out, jnp.float32), float(rope["attention_factor"])


def rotary(x, freqs, factor):
    """x [S, d] at positions 0..S-1: the first ``2 len(freqs)`` elements
    turned by halves, ``x1' = x1 cos - x2 sin``, ``x2' = x2 cos + x1
    sin`` with cos and sin times ``factor``; the rest as it is."""
    half = freqs.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[:, :half], x[:, half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[:, 2 * half:]], axis=-1)


def _attention(p, i, u, m, arith):
    S = u.shape[0]
    heads = m["num_attention_heads_per_layer"][i]
    kv, hd = m["num_key_value_heads"], m["head_dim"]
    group = heads // kv
    kind = m["layer_types"][i]
    freqs, factor = inv_freq((m.get("rope_parameters") or {}).get(kind, {}),
                             hd)
    a = _kind(m, i)
    q = arith.dot(u, p[f"{a}.wq"]).reshape(S, heads, hd).transpose(1, 0, 2)
    k = arith.dot(u, p[f"{a}.wk"]).reshape(S, kv, hd).transpose(1, 0, 2)
    v = arith.dot(u, p[f"{a}.wv"]).reshape(S, kv, hd).transpose(1, 0, 2)
    pos = jnp.arange(S)
    sees = pos[None, :] <= pos[:, None]
    if kind == SLIDING:
        sees &= pos[:, None] - pos[None, :] < m["sliding_window"]

    @jax.checkpoint
    def head(q_h, n):
        k_h = rotary(k[n // group], freqs, factor)
        s = arith.mm(rotary(q_h, freqs, factor), k_h.T) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
        return arith.mm(prob, v[n // group])

    out = lax.map(lambda a_: head(*a_), (q, jnp.arange(heads)))
    if f"{a}.wg" in p:
        gate = jax.nn.sigmoid(arith.mm(u, p[f"{a}.wg"]))        # [S, H]
        out = out * gate.T[:, :, None]
    joined = arith.out(out.transpose(1, 0, 2).reshape(S, heads * hd))
    return arith.dot(joined, p[f"{a}.wo"])


def _swiglu(u, wg, wu, wd, arith):
    return arith.dot(arith.out(jax.nn.silu(arith.mm(u, wg))
                               * arith.mm(u, wu)), wd)


def _experts(p, tag, u, m, arith):
    w = lambda s: p[f"_{tag}_moe.{s}"]
    e = m["num_experts"]
    held = m.get("experts_held") or e
    offset = m.get("expert_offset") or 0
    s = jax.nn.sigmoid(jnp.matmul(u, w("wr"), precision=HIGHEST))
    _, ids = lax.top_k(s + lax.stop_gradient(w("br")),
                       m["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) \
        * m["moe_routed_scaling_factor"]
    # [S, E]: a token's weight for every expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(ids, e, dtype=u.dtype)
                    * weights[..., None], axis=1)

    def add(y, expert):
        wg, wu, wd, weight = expert
        return y + weight[:, None] * _swiglu(u, wg, wu, wd, arith), None

    y, _ = lax.scan(add, jnp.zeros_like(u),
                    (w("wg"), w("wu"), w("wd"),
                     dense[:, offset:offset + held].T))
    if f"_{tag}_moe.sg" in p:
        y = y + _swiglu(u, w("sg"), w("su"), w("sd"), arith)
    return arith.out(y)


def _block(p, i, x, m, arith):
    eps, tag = m["rms_norm_eps"], f"blk{i}"
    h = arith.out(x + _attention(
        p, i, arith.out(_rms(x, p[f"_{tag}_a_norm.w0"], eps)), m, arith))
    u = arith.out(_rms(h, p[f"_{tag}_f_norm.w0"], eps))
    f = (_swiglu(u, p[f"_{tag}_mlp.wg"], p[f"_{tag}_mlp.wu"],
                 p[f"_{tag}_mlp.wd"], arith)
         if m["mlp_layer_types"][i] == "dense"
         else _experts(p, tag, u, m, arith))
    return arith.out(h + f)


def _cross_entropy(p, h, targets, m, arith):
    """Mean of -log softmax(RMSNorm(h) W_head)[target] over the
    ``len(targets)`` leading positions."""
    n = targets.shape[0]
    u = arith.out(_rms(h[:n], p["_out_norm.w0"], m["rms_norm_eps"]))
    logits = arith.mm(u, p["_out_head.w0"])
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def row_loss(p, ids, m, arith):
    """One sequence ``ids [S]``."""
    block = jax.checkpoint(_block, static_argnums=(1, 3, 4))
    x = arith.out(p["_embed.w0"][ids])
    for i in range(len(m["layer_types"])):
        x = block(p, i, x, m, arith)
    ce = jax.checkpoint(_cross_entropy, static_argnums=(3, 4))
    return ce(p, x, ids[1:], m, arith)


def loss(params, batch, cfg, arith):
    m = _HashableDict(_args(cfg))
    return jnp.mean(lax.map(lambda ids: row_loss(params, ids, m, arith),
                            batch["words"].astype(jnp.int32)))


class _HashableDict(dict):
    """The configuration's sizes as a static argument of
    ``jax.checkpoint``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
