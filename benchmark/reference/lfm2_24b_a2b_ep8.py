"""Plain reference of LFM2-24B-A2B's decoder as the configuration cuts it
(LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type`` ``lfm2_moe``; the
equations are written down from its keys and the family's published
form): float32, ``highest``, ``jax.numpy`` only, nothing of the program
imported.

    x = E[ids]                                                (E [V, d])
    for l:  h = x + Op_l(N(x; g_op_l));  x = h + FFN_l(N(h; g_ffn_l))
    z = N(x; g_out) E^T;  loss = mean_i CE(z_i, id_{i+1})   (the head is E)
    N(v; g) = v / sqrt(mean(v^2) + norm_eps) * g

``Op_l`` by ``layer_types[l]``. ``conv``: ``[B | C | X] = u W_in`` (thirds
in this order), ``s = B * X``, ``c_t = sum_{j<k} w[j] * s_{t-k+1+j}``
(depthwise, causal, ``k = conv_L_cache`` taps, ``s_{<0} = 0``, no bias:
written as ``k`` explicit shifted sums), ``y = (C * c) W_out``.
``full_attention``: ``q = u W_q`` in ``H`` heads of ``d / H``, ``k = u
W_k``, ``v = u W_v`` in ``KV`` heads, no bias; ``q = N(q; g_q)``, ``k =
N(k; g_k)`` over each head's elements (one scale for all the heads of a
kind, ``norm_eps``); rotary by halves over the whole head
(``rope_theta``); query head ``n`` attends key-value head ``n // (H /
KV)``, ``a = softmax(q k^T / sqrt(d / H) + causal) v``; ``W_o``. No gate,
no window.

``FFN_l``: for ``l < num_dense_layers`` a SwiGLU of ``intermediate_size``,
else the expert layer: ``s = sigmoid(u W_r)`` over all ``num_experts``,
the top ``k`` of ``s + b`` chosen, weights ``s[chosen] / (sum(s[chosen])
+ norm_topk_eps) * routed_scaling_factor``, ``y = sum_i w_i E_i(u)``; no
shared expert.

Departures from the published description, each the configuration's
(its ``assumed`` names every point ``config.json`` does not fix):

- **the chip's share**: the router scores all ``num_experts``; of the
  chosen experts only those held here (``expert_offset .. expert_offset
  + experts_held``) add to the result, and that partial sum goes on. No
  sort and no dispatch: every held expert over every token, weighted by
  the token's weight for it (0 where it was not chosen);
- the vocabulary is the slice the configuration gives, the depth the
  length of its ``layer_types``;
- the expert bias is a static leaf (zeros here) and takes no gradient;
- the convolution's weight is a leaf ``[k, d]`` (the published ``[d, 1,
  k]`` transposed), tap ``k - 1`` on the current step;
- the router's product is float32 at ``highest`` in every arithmetic
  (the configuration states that the router stays float32); every other
  product goes through ``arith``.

To fit beside the check's bytes at the timed size (one row of 8,192
tokens: a head's scores are 268 MB in float32), rows go one at a time
(``lax.map``), every block and every query head's attention is a
``jax.checkpoint`` (heads one at a time, ``lax.map``, each reading its
group's K and V), and a row's logits ([S, V] float32) are the chunk in
which the head is computed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
CONV = "conv"


def _args(cfg):
    return cfg["model"]["args"]


def _dense(m, i):
    return i < m["num_dense_layers"]


def leaves(cfg):
    m = _args(cfg)
    d, v = m["hidden_size"], m["vocab_size"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // heads
    out = {"_embed.w0": ((v, d), "normal"),     # and the head: tied
           "_out_norm.w0": ((d,), "ones")}
    for i, kind in enumerate(m["layer_types"]):
        out.update({f"_blk{i}_a_norm.w0": ((d,), "ones"),
                    f"_blk{i}_f_norm.w0": ((d,), "ones")})
        if kind == CONV:
            t = f"_blk{i}_sconv"
            out.update({f"{t}.wi": ((d, 3 * d), "normal"),
                        f"{t}.wc": ((m["conv_L_cache"], d), "normal"),
                        f"{t}.wo": ((d, d), "normal")})
        else:
            t = f"_blk{i}_attn"
            out.update({f"{t}.wq": ((d, heads * hd), "normal"),
                        f"{t}.wk": ((d, kv * hd), "normal"),
                        f"{t}.wv": ((d, kv * hd), "normal"),
                        f"{t}.wo": ((heads * hd, d), "normal"),
                        f"{t}.gq": ((hd,), "ones"),
                        f"{t}.gk": ((hd,), "ones")})
        if _dense(m, i):
            f = m["intermediate_size"]
            out.update({f"_blk{i}_mlp.wg": ((d, f), "normal"),
                        f"_blk{i}_mlp.wu": ((d, f), "normal"),
                        f"_blk{i}_mlp.wd": ((f, d), "normal")})
            continue
        e, h = m["num_experts"], m["moe_intermediate_size"]
        held = m.get("experts_held") or e
        t = f"_blk{i}_moe"
        out.update({f"{t}.wr": ((d, e), "normal"),
                    f"{t}.br": ((e,), "static"),
                    f"{t}.wg": ((held, d, h), "normal"),
                    f"{t}.wu": ((held, d, h), "normal"),
                    f"{t}.wd": ((held, h, d), "normal")})
    return out


# ------------------------------------------------------------ one row
def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def _short_conv(p, i, u, m, arith):
    """u [S, d] -> [S, d]: two gates around a depthwise causal
    convolution, between the two products."""
    t = f"_blk{i}_sconv"
    S, d = u.shape
    bcx = arith.dot(u, p[f"{t}.wi"])
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    s = b * x
    w = p[f"{t}.wc"]
    k = w.shape[0]
    conv = jnp.zeros_like(s)
    for j in range(k):
        back = k - 1 - j        # tap j weighs the step `back` steps ago
        conv = conv + w[j] * jnp.concatenate(
            [jnp.zeros((back, d), s.dtype), s[:S - back]])
    return arith.dot(arith.out(c * conv), p[f"{t}.wo"])


def rotary(x, theta):
    """x [S, d] at positions 0..S-1, turned by halves over the whole
    head: ``x1' = x1 cos - x2 sin``, ``x2' = x2 cos + x1 sin``, element
    ``j`` with ``j + d/2``, by ``pos * theta^(-2j/d)``."""
    half = x.shape[-1] // 2
    freqs = jnp.asarray([theta ** (-2.0 * j / x.shape[-1])
                         for j in range(half)], jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(p, i, u, m, arith):
    S, d = u.shape
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd, group = d // heads, heads // kv
    theta = float((m.get("rope_parameters") or {}).get("rope_theta", 1e4))
    t = f"_blk{i}_attn"
    q = arith.dot(u, p[f"{t}.wq"]).reshape(S, heads, hd).transpose(1, 0, 2)
    k = arith.dot(u, p[f"{t}.wk"]).reshape(S, kv, hd).transpose(1, 0, 2)
    v = arith.dot(u, p[f"{t}.wv"]).reshape(S, kv, hd).transpose(1, 0, 2)
    q = _rms(q, p[f"{t}.gq"], m["norm_eps"])
    k = _rms(k, p[f"{t}.gk"], m["norm_eps"])
    pos = jnp.arange(S)
    sees = pos[None, :] <= pos[:, None]

    @jax.checkpoint
    def head(q_h, n):
        k_h = rotary(k[n // group], theta)
        s = arith.mm(rotary(q_h, theta), k_h.T) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
        return arith.mm(prob, v[n // group])

    out = lax.map(lambda a: head(*a), (q, jnp.arange(heads)))
    joined = arith.out(out.transpose(1, 0, 2).reshape(S, heads * hd))
    return arith.dot(joined, p[f"{t}.wo"])


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
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                        + m.get("norm_topk_eps", 1e-6)) \
        * m["routed_scaling_factor"]
    # [S, E]: a token's weight for every expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(ids, e, dtype=u.dtype)
                    * weights[..., None], axis=1)

    def add(y, expert):
        wg, wu, wd, weight = expert
        return y + weight[:, None] * _swiglu(u, wg, wu, wd, arith), None

    y, _ = lax.scan(add, jnp.zeros_like(u),
                    (w("wg"), w("wu"), w("wd"),
                     dense[:, offset:offset + held].T))
    return arith.out(y)


def _block(p, i, x, m, arith):
    eps, tag = m["norm_eps"], f"blk{i}"
    op = _short_conv if m["layer_types"][i] == CONV else _attention
    h = arith.out(x + op(
        p, i, arith.out(_rms(x, p[f"_{tag}_a_norm.w0"], eps)), m, arith))
    u = arith.out(_rms(h, p[f"_{tag}_f_norm.w0"], eps))
    f = (_swiglu(u, p[f"_{tag}_mlp.wg"], p[f"_{tag}_mlp.wu"],
                 p[f"_{tag}_mlp.wd"], arith)
         if _dense(m, i) else _experts(p, tag, u, m, arith))
    return arith.out(h + f)


def _cross_entropy(p, h, targets, m, arith):
    """Mean of -log softmax(RMSNorm(h) E^T)[target] over the
    ``len(targets)`` leading positions: the head is the embedding."""
    n = targets.shape[0]
    u = arith.out(_rms(h[:n], p["_out_norm.w0"], m["norm_eps"]))
    logits = arith.mm(u, p["_embed.w0"].T)
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
