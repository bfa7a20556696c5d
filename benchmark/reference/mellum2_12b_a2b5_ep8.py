"""Plain reference of Mellum2-12B-A2.5B's decoder as the configuration cuts
it (JetBrains/Mellum2-12B-A2.5B-Instruct ``config.json``, ``model_type``
``mellum``; the equations are written down from its keys and the
Qwen3-MoE family's published form): float32, ``highest``, ``jax.numpy``
only, nothing of the program imported.

    x = E[ids]                                           (E [V, d], untied)
    for l:  h = x + Attn_l(N(x));  x = h + MoE_l(N(h))            (pre-norm)
    z = N(x) W_head;  CE = mean over rows of mean_i CE(z_i, id_{i+1})
    loss = CE + router_aux_loss_coef * L_bal
    N(v; g) = v / sqrt(mean(v^2) + eps) * g

``Attn_l(u)``: ``q = u W_q`` in ``H`` heads of ``head_dim``, ``k = u
W_k``, ``v = u W_v`` in ``KV`` heads, no bias; ``q = N(q; g_q)``, ``k =
N(k; g_k)`` over each head's elements (one scale for all the heads of a
kind, ``eps``). Rotary by halves over the whole head (Hugging Face's
``rotate_half``: element ``j`` pairs with ``j + hd/2``). A
``sliding_attention`` layer: ``inv_freq_i = theta^(-2i/hd)``. A
``full_attention`` layer, YaRN: with ``b = rope_theta``, ``f = factor``,
``L = original_max_position_embeddings``, ``dim(n) = hd ln(L / (2 pi
n)) / (2 ln b)``, ``low = floor(dim(beta_fast))``, ``high =
ceil(dim(beta_slow))`` clipped to ``[0, hd - 1]``, ``ramp_i = clip((i -
low) / (high - low), 0, 1)``, ``inv_freq_i = (1 - ramp_i) b^(-2i/hd) +
ramp_i b^(-2i/hd) / f``, cos and sin times ``attention_factor``. Query
head ``n`` attends key-value head ``n // (H / KV)``: ``softmax(q k^T /
sqrt(hd) + mask) v``; query ``i`` sees key ``j`` iff ``j <= i`` and, on a
sliding layer, ``i - j < sliding_window``. ``W_o``. No gate.

``MoE_l(u)``: ``p = softmax(u W_r)`` over all ``num_experts``, the top
``k`` of ``p`` chosen, weights ``p[chosen] / sum(p[chosen])``, ``y =
sum_i w_i E_i(u)``; no shared expert.

``L_bal`` (Hugging Face's ``load_balancing_loss_func``: every expert
layer's router outputs concatenated, summed over the ``k`` slots): over
the ``N`` (layer, token) rows of the step, ``c_e = #{(n, j): chosen_{n,j}
= e} / N``, ``P_e = sum_n p_{n,e} / N``, ``L_bal = num_experts * sum_e
c_e P_e``; the choice carries no gradient, ``P`` does.

Departures from the published description, each the configuration's
(its ``assumed`` names every point ``config.json`` does not fix):

- **the chip's share**: the router scores all ``num_experts`` (so
  ``L_bal`` is the whole router's); of the chosen experts only those held
  here (``expert_offset .. expert_offset + experts_held``) add to the
  result, and that partial sum goes on. No sort and no dispatch: every
  held expert over every token, weighted by the token's weight for it (0
  where it was not chosen);
- the vocabulary is the slice the configuration gives, the depth the
  length of its ``layer_types``;
- the static selection bias leaf ``br`` the expert layer keeps is read by
  nothing here: a softmax router chooses by ``p`` alone;
- the router's product is float32 at ``highest`` in every arithmetic
  (the configuration states that the router stays float32); every other
  product goes through ``arith``.

To fit beside the check's bytes at the timed size (two rows of 8,192
tokens: a head's scores are 268 MB in float32), rows go one at a time
(``lax.map``), every block and every query head's attention is a
``jax.checkpoint`` (heads one at a time, ``lax.map``, each reading its
group's K and V), and a row's logits ([S, V] float32) are the chunk in
which the head is computed. A row hands its router statistics out beside
its loss; ``L_bal`` is formed over all of them.
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
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    e, h = m["num_experts"], m["moe_intermediate_size"]
    held = m.get("experts_held") or e
    out = {"_embed.w0": ((v, d), "normal"),
           "_out_norm.w0": ((d,), "ones"),
           "_out_head.w0": ((d, v), "normal")}
    for i in range(len(m["layer_types"])):
        a, t = _kind(m, i), f"_blk{i}_moe"
        out.update({f"_blk{i}_a_norm.w0": ((d,), "ones"),
                    f"_blk{i}_f_norm.w0": ((d,), "ones"),
                    f"{a}.wq": ((d, heads * hd), "normal"),
                    f"{a}.wk": ((d, kv * hd), "normal"),
                    f"{a}.wv": ((d, kv * hd), "normal"),
                    f"{a}.wo": ((heads * hd, d), "normal"),
                    f"{t}.wr": ((d, e), "normal"),
                    f"{t}.br": ((e,), "static"),
                    f"{t}.wg": ((held, d, h), "normal"),
                    f"{t}.wu": ((held, d, h), "normal"),
                    f"{t}.wd": ((held, h, d), "normal")})
        if m.get("qk_norm", True):
            out.update({f"{a}.gq": ((hd,), "ones"),
                        f"{a}.gk": ((hd,), "ones")})
    return out


# ------------------------------------------------------------ one row
def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def inv_freq(rope, head_dim):
    """``(inv_freq [hd/2], attention factor)`` of one kind of layer's
    ``rope_parameters`` entry: the whole head turns."""
    b = float(rope.get("rope_theta", 10000.0))
    plain = [b ** (-2.0 * i / head_dim) for i in range(head_dim // 2)]
    if rope.get("rope_type", "default") != "yarn":
        return jnp.asarray(plain, jnp.float32), 1.0
    f, L = float(rope["factor"]), rope["original_max_position_embeddings"]

    def dim_of(turns):
        return (head_dim * math.log(L / (2 * math.pi * turns))
                / (2 * math.log(b)))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), head_dim - 1)
    out = []
    for i, p in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append((1 - ramp) * p + ramp * p / f)
    return jnp.asarray(out, jnp.float32), float(rope["attention_factor"])


def rotary(x, freqs, factor):
    """x [S, hd] at positions 0..S-1, turned by halves: ``x1' = x1 cos -
    x2 sin``, ``x2' = x2 cos + x1 sin`` with cos and sin times
    ``factor``."""
    half = freqs.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(p, i, u, m, arith):
    S = u.shape[0]
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    group = heads // kv
    kind = m["layer_types"][i]
    freqs, factor = inv_freq((m.get("rope_parameters") or {}).get(kind, {}),
                             hd)
    a = _kind(m, i)
    q = arith.dot(u, p[f"{a}.wq"]).reshape(S, heads, hd).transpose(1, 0, 2)
    k = arith.dot(u, p[f"{a}.wk"]).reshape(S, kv, hd).transpose(1, 0, 2)
    v = arith.dot(u, p[f"{a}.wv"]).reshape(S, kv, hd).transpose(1, 0, 2)
    if f"{a}.gq" in p:
        q = _rms(q, p[f"{a}.gq"], m["rms_norm_eps"])
        k = _rms(k, p[f"{a}.gk"], m["rms_norm_eps"])
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
    joined = arith.out(out.transpose(1, 0, 2).reshape(S, heads * hd))
    return arith.dot(joined, p[f"{a}.wo"])


def _swiglu(u, wg, wu, wd, arith):
    return arith.dot(arith.out(jax.nn.silu(arith.mm(u, wg))
                               * arith.mm(u, wu)), wd)


def _experts(p, tag, u, m, arith):
    """``(y, (sum_n p_n [E], slots [E]))``: the held experts' part of the
    layer and the router's statistics over the row's tokens."""
    w = lambda s: p[f"_{tag}_moe.{s}"]
    e = m["num_experts"]
    held = m.get("experts_held") or e
    offset = m.get("expert_offset") or 0
    probs = jax.nn.softmax(jnp.matmul(u, w("wr"), precision=HIGHEST),
                           axis=-1)
    _, ids = lax.top_k(probs, m["num_experts_per_tok"])
    chosen = jnp.take_along_axis(probs, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    # [S, E]: a token's weight for every expert, 0 where not chosen
    picked = jax.nn.one_hot(ids, e, dtype=u.dtype)                # [S, k, E]
    dense = jnp.sum(picked * weights[..., None], axis=1)

    def add(y, expert):
        wg, wu, wd, weight = expert
        return y + weight[:, None] * _swiglu(u, wg, wu, wd, arith), None

    y, _ = lax.scan(add, jnp.zeros_like(u),
                    (w("wg"), w("wu"), w("wd"),
                     dense[:, offset:offset + held].T))
    slots = lax.stop_gradient(jnp.sum(picked, axis=(0, 1)))
    return arith.out(y), (jnp.sum(probs, axis=0), slots)


def _block(p, i, x, m, arith):
    eps, tag = m["rms_norm_eps"], f"blk{i}"
    h = arith.out(x + _attention(
        p, i, arith.out(_rms(x, p[f"_{tag}_a_norm.w0"], eps)), m, arith))
    u = arith.out(_rms(h, p[f"_{tag}_f_norm.w0"], eps))
    f, stats = _experts(p, tag, u, m, arith)
    return arith.out(h + f), stats


def _cross_entropy(p, h, targets, m, arith):
    """Mean of -log softmax(RMSNorm(h) W_head)[target] over the
    ``len(targets)`` leading positions."""
    n = targets.shape[0]
    u = arith.out(_rms(h[:n], p["_out_norm.w0"], m["rms_norm_eps"]))
    logits = arith.mm(u, p["_out_head.w0"])
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def row_loss(p, ids, m, arith):
    """One sequence ``ids [S]``: ``(its CE, (sum of p [E], slots [E]))``,
    the statistics summed over the layers."""
    block = jax.checkpoint(_block, static_argnums=(1, 3, 4))
    x = arith.out(p["_embed.w0"][ids])
    probs = slots = 0.0
    for i in range(len(m["layer_types"])):
        x, (pr, sl) = block(p, i, x, m, arith)
        probs, slots = probs + pr, slots + sl
    ce = jax.checkpoint(_cross_entropy, static_argnums=(3, 4))
    return ce(p, x, ids[1:], m, arith), (probs, slots)


def balance(probs, slots, n):
    """``L_bal`` from the statistics summed over the ``n`` (layer, token)
    rows."""
    return probs.shape[-1] * jnp.sum((slots / n) * (probs / n))


def loss(params, batch, cfg, arith):
    m = _HashableDict(_args(cfg))
    ids = batch["words"].astype(jnp.int32)
    ce, (probs, slots) = lax.map(lambda r: row_loss(params, r, m, arith),
                                 ids)
    n = ids.shape[0] * ids.shape[1] * len(m["layer_types"])
    return jnp.mean(ce) + m["router_aux_loss_coef"] * balance(
        jnp.sum(probs, axis=0), jnp.sum(slots, axis=0), n)


class _HashableDict(dict):
    """The configuration's sizes as a static argument of
    ``jax.checkpoint``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
