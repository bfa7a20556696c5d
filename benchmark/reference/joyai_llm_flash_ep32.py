"""Plain reference of JoyAI-LLM-Flash's decoder as the configuration
cuts it (jdopensource/JoyAI-LLM-Flash ``config.json``, whose keys are
DeepSeek-V3's; the layer equations are those of arXiv:2412.19437 §2.1
and §2.2): float32, ``highest``, ``jax.numpy`` only, nothing of the
program imported.

Block: ``h = x + Attn(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``,
RMSNorm(v) = v / sqrt(mean(v^2) + eps) * g. Latent attention: ``c_q =
RMSNorm(u W_qa)``, ``q = c_q W_qb`` in heads of [nope | rope]; ``[c_kv |
k_rope] = u W_kva``, ``c_kv = RMSNorm(c_kv)``, ``c_kv W_kvb`` in heads of
[k_nope | v]; rotary (pairs ``(2i, 2i+1)`` interleaved, no scaling) on
``q_rope`` and on the one ``k_rope`` all heads share; ``softmax(q k^T /
sqrt(nope + rope))`` causal, times ``v``; the heads joined, times
``W_o``. The FFN is SwiGLU, dense in the first ``first_k_dense_replace``
blocks and the expert layer after: ``s = sigmoid(u W_r)`` over all the
routed experts, the top ``k`` of ``s + b`` chosen, weights ``s[chosen] /
sum(s[chosen]) * routed_scaling_factor``, ``y = sum_i w_i E_i(u) +
Shared(u)``. Multi-token prediction, depth 1: ``h'_i = [RMSNorm(h_i);
RMSNorm(Emb(t_{i+1}))] W_eh``, one expert block, RMSNorm, the model's own
head, scored against ``t_{i+2}``. Loss = CE(main, t_{i+1}) + weight *
CE(mtp, t_{i+2}), each a mean over the positions that have a target.

Departures from the published description, each the configuration's:

- **the chip's share**: the router scores all ``n_routed_experts``; of
  the chosen experts only those held here (``expert_offset ..
  expert_offset + experts_held``) add to the result, what the absent
  ones would have added is left out, and that partial sum plus the
  shared expert goes on. The plain form has no sort and no dispatch: a
  dense one-hot combine, every held expert over every token, weighted by
  the token's weight for it (0 where it was not chosen);
- the vocabulary is the slice the configuration gives, the depth its
  ``num_hidden_layers``;
- the selection bias is a static leaf (zeros here) and takes no gradient;
- the multi-token module's loss weight is the configuration's
  ``mtp_loss_weight`` (``config.json`` has none); position ``S-1`` of
  the module's input, which has no next token, embeds as zeros (causal:
  it touches no position that is scored);
- the router's product is float32 at ``highest`` in every arithmetic
  (the configuration states that the router stays float32); every other
  product goes through ``arith``.

To fit beside the check's 24 bytes a parameter at the timed size, rows go
one at a time (``lax.map``), every block and every head's attention is a
``jax.checkpoint``, and a row's logits ([S, V] float32) are the chunk in
which the head is computed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _args(cfg):
    return cfg["model"]["args"]


def _attn_leaves(m, tag):
    d, heads = m["hidden_size"], m["num_attention_heads"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return {
        f"_{tag}_attn.wqa": ((d, qr), "normal"),
        f"_{tag}_attn.qnorm": ((qr,), "ones"),
        f"_{tag}_attn.wqb": ((qr, heads * (nope + rope)), "normal"),
        f"_{tag}_attn.wkva": ((d, kvr + rope), "normal"),
        f"_{tag}_attn.kvnorm": ((kvr,), "ones"),
        f"_{tag}_attn.wkvb": ((kvr, heads * (nope + dv)), "normal"),
        f"_{tag}_attn.wo": ((heads * dv, d), "normal"),
    }


def _moe_leaves(m, tag):
    d, h = m["hidden_size"], m["moe_intermediate_size"]
    e = m["n_routed_experts"]
    held = m.get("experts_held") or e
    hs = m.get("n_shared_experts", 1) * h
    out = {
        f"_{tag}_moe.wr": ((d, e), "normal"),
        f"_{tag}_moe.br": ((e,), "static"),
        f"_{tag}_moe.wg": ((held, d, h), "normal"),
        f"_{tag}_moe.wu": ((held, d, h), "normal"),
        f"_{tag}_moe.wd": ((held, h, d), "normal"),
    }
    if hs:
        out.update({f"_{tag}_moe.sg": ((d, hs), "normal"),
                    f"_{tag}_moe.su": ((d, hs), "normal"),
                    f"_{tag}_moe.sd": ((hs, d), "normal")})
    return out


def _block_leaves(m, tag, dense):
    d = m["hidden_size"]
    out = {f"_{tag}_a_norm.w0": ((d,), "ones"),
           f"_{tag}_f_norm.w0": ((d,), "ones"),
           **_attn_leaves(m, tag)}
    if dense:
        f = m["intermediate_size"]
        out.update({f"_{tag}_mlp.wg": ((d, f), "normal"),
                    f"_{tag}_mlp.wu": ((d, f), "normal"),
                    f"_{tag}_mlp.wd": ((f, d), "normal")})
    else:
        out.update(_moe_leaves(m, tag))
    return out


def leaves(cfg):
    m = _args(cfg)
    d, v = m["hidden_size"], m["vocab_size"]
    out = {"_embed.w0": ((v, d), "normal"),
           "_out_norm.w0": ((d,), "ones"),
           "_out_head.w0": ((d, v), "normal")}
    for i in range(m["num_hidden_layers"]):
        out.update(_block_leaves(
            m, f"blk{i}", i < m.get("first_k_dense_replace", 1)))
    if m.get("num_nextn_predict_layers", 1):
        out.update({"_mtp_h_norm.w0": ((d,), "ones"),
                    "_mtp_e_norm.w0": ((d,), "ones"),
                    "_mtp_proj.w0": ((2 * d, d), "normal"),
                    "_mtp_out_norm.w0": ((d,), "ones"),
                    **_block_leaves(m, "mtp", False)})
    return out


# ------------------------------------------------------------ one row
def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def _rotary(x, theta):
    """x [S, d] at positions 0..S-1; the pair (x[2i], x[2i+1]) turned by
    pos * theta^(-2i/d)."""
    S, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    even, odd = x[:, 0::2], x[:, 1::2]
    turned = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                        even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return turned.reshape(S, d)


def _attention(p, tag, u, m, arith):
    S = u.shape[0]
    heads = m["num_attention_heads"]
    kvr = m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    w = lambda s: p[f"_{tag}_attn.{s}"]
    c_q = arith.out(_rms(arith.mm(u, w("wqa")), w("qnorm"), eps))
    q = arith.dot(c_q, w("wqb")).reshape(S, heads, nope + rope)
    kva = arith.dot(u, w("wkva"))
    c_kv = arith.out(_rms(kva[:, :kvr], w("kvnorm"), eps))
    kv = arith.dot(c_kv, w("wkvb")).reshape(S, heads, nope + dv)
    k_rope = _rotary(kva[:, kvr:], theta)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def head(q_h, kv_h):
        q_h = jnp.concatenate([q_h[:, :nope], _rotary(q_h[:, nope:], theta)],
                              axis=-1)
        k_h = jnp.concatenate([kv_h[:, :nope], k_rope], axis=-1)
        s = arith.mm(q_h, k_h.T) * scale
        prob = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return arith.mm(prob, kv_h[:, nope:])

    out = lax.map(lambda a: head(*a), (q.transpose(1, 0, 2),
                                       kv.transpose(1, 0, 2)))
    joined = arith.out(out.transpose(1, 0, 2).reshape(S, heads * dv))
    return arith.dot(joined, w("wo"))


def _swiglu(u, wg, wu, wd, arith):
    return arith.dot(arith.out(jax.nn.silu(arith.mm(u, wg))
                               * arith.mm(u, wu)), wd)


def _experts(p, tag, u, m, arith):
    w = lambda s: p[f"_{tag}_moe.{s}"]
    e = m["n_routed_experts"]
    held = m.get("experts_held") or e
    offset = m.get("expert_offset") or 0
    s = jax.nn.sigmoid(jnp.matmul(u, w("wr"), precision=HIGHEST))
    _, ids = lax.top_k(s + lax.stop_gradient(w("br")),
                       m["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) \
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
    if f"_{tag}_moe.sg" in p:
        y = y + _swiglu(u, w("sg"), w("su"), w("sd"), arith)
    return arith.out(y)


def _block(p, tag, x, m, dense, arith):
    eps = m["rms_norm_eps"]
    h = arith.out(x + _attention(
        p, tag, arith.out(_rms(x, p[f"_{tag}_a_norm.w0"], eps)), m, arith))
    u = arith.out(_rms(h, p[f"_{tag}_f_norm.w0"], eps))
    f = (_swiglu(u, p[f"_{tag}_mlp.wg"], p[f"_{tag}_mlp.wu"],
                 p[f"_{tag}_mlp.wd"], arith) if dense
         else _experts(p, tag, u, m, arith))
    return arith.out(h + f)


def _cross_entropy(p, h, norm, targets, m, arith):
    """Mean of -log softmax(RMSNorm(h) W_head)[target] over the
    ``len(targets)`` leading positions."""
    n = targets.shape[0]
    u = arith.out(_rms(h[:n], p[norm], m["rms_norm_eps"]))
    logits = arith.mm(u, p["_out_head.w0"])
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def row_loss(p, ids, m, arith):
    """One sequence ``ids [S]``."""
    block = jax.checkpoint(_block, static_argnums=(1, 3, 4, 5))
    emb = arith.out(p["_embed.w0"][ids])
    x = emb
    for i in range(m["num_hidden_layers"]):
        x = block(p, f"blk{i}", x, m, i < m.get("first_k_dense_replace", 1),
                  arith)
    ce = jax.checkpoint(_cross_entropy, static_argnums=(2, 4, 5))
    loss = ce(p, x, "_out_norm.w0", ids[1:], m, arith)
    if m.get("num_nextn_predict_layers", 1):
        eps = m["rms_norm_eps"]
        nxt = jnp.concatenate([emb[1:], jnp.zeros_like(emb[:1])])
        both = jnp.concatenate(
            [arith.out(_rms(x, p["_mtp_h_norm.w0"], eps)),
             arith.out(_rms(nxt, p["_mtp_e_norm.w0"], eps))], axis=-1)
        h = block(p, "mtp", arith.dot(both, p["_mtp_proj.w0"]), m, False,
                  arith)
        loss = loss + m.get("mtp_loss_weight", 0.3) * ce(
            p, h, "_mtp_out_norm.w0", ids[2:], m, arith)
    return loss


def loss(params, batch, cfg, arith):
    m = _HashableDict(_args(cfg))
    return jnp.mean(lax.map(lambda ids: row_loss(params, ids, m, arith),
                            batch["words"].astype(jnp.int32)))


class _HashableDict(dict):
    """The configuration's sizes as a static argument of
    ``jax.checkpoint``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
