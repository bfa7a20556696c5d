"""Plain reference of the reference's RNN benchmark model
(``benchmark/paddle/rnn/rnn.py``): embedding -> N x [fc 4h + lstmemory]
-> max-pool over time -> fc softmax -> cross-entropy.

float32 with every matrix product as ``arith`` says (``plain.Arith``), a
``lax.scan`` over time, no kernel, no mask (the traffic pads every row
to the same length, so every position is live).
The cell is the reference's ``hl_lstm_ops.cuh``: gate blocks
[input, input-gate, forget-gate, output-gate], a 7h bias = 4 gate biases
and the three peephole diagonals.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def leaves(cfg):
    m = cfg["model"]["args"]
    v, e, h = m["vocab_size"], m["embed_dim"], m["hidden"]
    out = {"_embed.w0": ((v, e), "normal")}
    width = e
    for i in range(m["num_layers"]):
        out[f"_lstm{i}_proj.w0"] = ((width, 4 * h), "normal")
        out[f"_lstm{i}_proj.wbias"] = ((4 * h,), "zeros")
        out[f"_lstm{i}.w0"] = ((h, 4 * h), "normal")
        out[f"_lstm{i}.wbias"] = ((7 * h,), "zeros")
        width = h
    out["_output.w0"] = ((h, m["classes"]), "normal")
    out["_output.wbias"] = ((m["classes"],), "zeros")
    return out


def _lstm(xs, w, bias, arith):
    """xs [T,B,4H] -> hs [T,B,H]."""
    h_size = w.shape[0]
    gate_bias = bias[:4 * h_size]
    p_i, p_f, p_o = (bias[(4 + k) * h_size:(5 + k) * h_size]
                     for k in range(3))

    def step(carry, x_t):
        h, c = carry
        gates = x_t + arith.mm(h, w) + gate_bias
        a_i, a_ig, a_fg, a_og = jnp.split(gates, 4, axis=-1)
        i = jnp.tanh(a_i)
        ig = jax.nn.sigmoid(a_ig + c * p_i)
        fg = jax.nn.sigmoid(a_fg + c * p_f)
        c = arith.keep(i * ig + c * fg)
        og = jax.nn.sigmoid(a_og + c * p_o)
        h = arith.keep(og * jnp.tanh(c))
        return (h, c), h

    z = jnp.zeros((xs.shape[1], h_size), jnp.float32)
    _, hs = jax.lax.scan(step, (z, z), xs)
    return hs


def logits(params, batch, cfg, arith):
    m = cfg["model"]["args"]
    x = arith.out(params["_embed.w0"][batch["words"]])       # [B,T,E]
    x = jnp.swapaxes(x, 0, 1)                                 # [T,B,E]
    for i in range(m["num_layers"]):
        proj = arith.dot(x, params[f"_lstm{i}_proj.w0"]) \
            + params[f"_lstm{i}_proj.wbias"]
        x = _lstm(arith.out(proj), params[f"_lstm{i}.w0"],
                  params[f"_lstm{i}.wbias"], arith)
    pooled = jnp.max(x, axis=0)                               # [B,H]
    return arith.dot(pooled, params["_output.w0"]) + params["_output.wbias"]


def loss(params, batch, cfg, arith):
    p = jax.nn.softmax(logits(params, batch, cfg, arith), axis=-1)
    ll = jnp.take_along_axis(p, batch["label"][:, None], axis=-1)[:, 0]
    return jnp.mean(-jnp.log(jnp.clip(ll, 1e-10, 1.0)))
