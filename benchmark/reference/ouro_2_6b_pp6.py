"""Plain reference of Ouro-2.6B's looped decoder as the configuration
cuts it (ByteDance/Ouro-2.6B ``config.json`` fixes the sizes; the paper,
arXiv:2510.25741, and the model's published ``modeling_ouro.py``, quoted
from memory, fix the form; the configuration's ``assumed`` names every
point the keys do not carry): float32, ``highest``, ``jax.numpy`` only,
nothing of the program imported. It holds ONE copy of each weight and
loops::

    x_0 = E[ids]
    for t = 1..R:           (R = total_ut_steps; the same L layers, the
        y = x_{t-1}          same weights, every pass)
        for l = 1..L:   a = y + N2_l(Attn_l(N1_l(y)))
                        y = a + N4_l(FFN_l(N3_l(a)))
        x_t = N_out(y)      (the final norm closes every pass; the next
                             pass reads the normed state)
        z_t = x_t W_head;   g_t = x_t w_gate + b_gate
    lam_t = sigmoid(g_t);   p_t = lam_t prod_{j<t}(1 - lam_j) for t < R
    p_R = prod_{j<R}(1 - lam_j)                         (per position)
    loss = mean_i [ sum_t p_t[i] CE(z_t[i], id_{i+1}) - beta H(p[i]) ]
    H(p) = -sum_t p_t log p_t

``Attn(u)``: ``q, k, v = u W_q, u W_k, u W_v`` in ``num_attention_heads``
heads of ``head_dim`` (query head ``n`` attends key-value head ``n //
(heads / kv)``), no bias; rotary by halves over the whole head
(``rotate_half``: element ``j`` pairs with ``j + head_dim / 2``,
``inv_freq_j = rope_theta^(-2j / head_dim)``); ``softmax(q k^T /
sqrt(head_dim) + causal) v``; ``W_o``. ``FFN(u) = (silu(u W_g) * (u
W_u)) W_d``. ``N(v) = v / sqrt(mean(v^2) + rms_norm_eps) * g``. The mean
is over the positions that have a next id, a row at a time, then over
the rows.

Departures, each the configuration's: the depth is ``num_hidden_layers``
of the cut, the vocabulary its slice; the gate's product is float32 at
``highest`` in every arithmetic (the configuration states that the gate
stays float32), every other product goes through ``arith``.

To fit beside the check's bytes at the timed size (one row of 4,096
tokens; a head's scores are 67 MB in float32): rows go one at a time
(``lax.map``), every application of a block, every head's attention
(heads one at a time, ``lax.map``) and every pass's head with its
cross-entropy is a ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _args(cfg):
    return cfg["model"]["args"]


def leaves(cfg):
    m = _args(cfg)
    d, v, hd = m["hidden_size"], m["vocab_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    f = m["intermediate_size"]
    out = {"_embed.w0": ((v, d), "normal"),
           "_ut0_out_norm.w0": ((d,), "ones"),
           "_out_head.w0": ((d, v), "normal"),
           "_out_head.wgate": ((d, 1), "normal"),
           "_out_head.bgate": ((1,), "zeros")}
    for i in range(m["num_hidden_layers"]):
        t = f"_ut0_blk{i}"
        out.update({f"{t}_n{k}.w0": ((d,), "ones") for k in (1, 2, 3, 4)})
        out.update({f"{t}_attn.wq": ((d, heads * hd), "normal"),
                    f"{t}_attn.wk": ((d, kv * hd), "normal"),
                    f"{t}_attn.wv": ((d, kv * hd), "normal"),
                    f"{t}_attn.wo": ((heads * hd, d), "normal"),
                    f"{t}_mlp.wg": ((d, f), "normal"),
                    f"{t}_mlp.wu": ((d, f), "normal"),
                    f"{t}_mlp.wd": ((f, d), "normal")})
    return out


# ------------------------------------------------------------ one row
def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def rotary(x, theta):
    """x [S, d] at positions 0..S-1, turned by halves over the whole
    head: ``x1' = x1 cos - x2 sin``, ``x2' = x2 cos + x1 sin``."""
    S, d = x.shape
    freqs = jnp.asarray([float(theta) ** (-2.0 * j / d)
                         for j in range(d // 2)], jnp.float32)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(p, i, u, m, arith):
    S = u.shape[0]
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    group, theta = heads // kv, m["rope_theta"]
    a = f"_ut0_blk{i}_attn"
    q = arith.dot(u, p[f"{a}.wq"]).reshape(S, heads, hd).transpose(1, 0, 2)
    k = arith.dot(u, p[f"{a}.wk"]).reshape(S, kv, hd).transpose(1, 0, 2)
    v = arith.dot(u, p[f"{a}.wv"]).reshape(S, kv, hd).transpose(1, 0, 2)
    pos = jnp.arange(S)
    sees = pos[None, :] <= pos[:, None]

    @jax.checkpoint
    def head(q_h, n):
        s = arith.mm(rotary(q_h, theta),
                     rotary(k[n // group], theta).T) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
        return arith.mm(prob, v[n // group])

    out = lax.map(lambda a_: head(*a_), (q, jnp.arange(heads)))
    joined = arith.out(out.transpose(1, 0, 2).reshape(S, heads * hd))
    return arith.dot(joined, p[f"{a}.wo"])


def _swiglu(u, wg, wu, wd, arith):
    return arith.dot(arith.out(jax.nn.silu(arith.mm(u, wg))
                               * arith.mm(u, wu)), wd)


def _block(p, i, y, m, arith):
    eps, t = m["rms_norm_eps"], f"_ut0_blk{i}"

    def norm(x, k):
        return arith.out(_rms(x, p[f"{t}_n{k}.w0"], eps))

    a = arith.out(y + norm(_attention(p, i, norm(y, 1), m, arith), 2))
    f = _swiglu(norm(a, 3), p[f"{t}_mlp.wg"], p[f"{t}_mlp.wu"],
                p[f"{t}_mlp.wd"], arith)
    return arith.out(a + norm(f, 4))


def _cross_entropy(p, x, targets, arith):
    """-log softmax(x W_head)[target] at the ``len(targets)`` leading
    positions, [S - 1]."""
    logits = arith.mm(x[:targets.shape[0]], p["_out_head.w0"])
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


def exit_distribution(lams, n):
    """``lams``: the gates ``lam_1 .. lam_{R-1}`` (each [n]); ``p [R, n]``
    as the equations write it, a running product of what has not left."""
    left, p = jnp.ones((n,), jnp.float32), []
    for lam in lams:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def row_parts(p, ids, m, arith):
    """One sequence ``ids [S]``: ``(ce [R, S-1], p [R, S-1])``."""
    block = jax.checkpoint(_block, static_argnums=(1, 3, 4))
    ce_of = jax.checkpoint(_cross_entropy, static_argnums=(3,))
    R, n = m["total_ut_steps"], ids.shape[0] - 1
    x = arith.out(p["_embed.w0"][ids])
    ce, lams = [], []
    for t in range(R):
        for i in range(m["num_hidden_layers"]):
            x = block(p, i, x, m, arith)
        x = arith.out(_rms(x, p["_ut0_out_norm.w0"], m["rms_norm_eps"]))
        ce.append(ce_of(p, x, ids[1:], arith))
        if t < R - 1:
            g = jnp.matmul(x[:n], p["_out_head.wgate"][:, 0],
                           precision=HIGHEST) + p["_out_head.bgate"][0]
            lams.append(jax.nn.sigmoid(g))
    return jnp.stack(ce), exit_distribution(lams, n)


def row_loss(p, ids, m, arith):
    ce, prob = row_parts(p, ids, m, arith)
    entropy = -jnp.sum(prob * jnp.log(prob), axis=0)
    return jnp.mean(jnp.sum(prob * ce, axis=0)
                    - m.get("entropy_weight", 0.1) * entropy)


def loss(params, batch, cfg, arith):
    m = _HashableDict(_args(cfg))
    return jnp.mean(lax.map(lambda ids: row_loss(params, ids, m, arith),
                            batch["words"].astype(jnp.int32)))


class _HashableDict(dict):
    """The configuration's sizes as a static argument of
    ``jax.checkpoint``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
