"""Operations and bytes the Ouro-2.6B cut's algorithm needs, from its
shapes alone. The stack runs ``R = total_ut_steps`` times a step and
every pass counts: the four passes are the model, not recomputation. A
backward pass costs two products for every forward one, so forward +
backward is three times the forward; recomputed work (the layers run
under ``recompute``, the head's chunks under ``jax.checkpoint``) does
not count. A token sees ``S / 2`` keys (causal: half of S^2). All per
sample (one sequence of ``seq_len`` tokens) unless said.
"""

from __future__ import annotations

FULL = "full_attention"


def _m(cfg: dict) -> dict:
    return cfg["model"]["args"]


def _attn_proj_macs(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d


def _core_macs_per_token(m: dict, seq_len: int) -> float:
    """QK^T and PV of one layer for one token."""
    return m["num_attention_heads"] * (seq_len / 2) * 2 * m["head_dim"]


def _layer_macs_per_token(m: dict, seq_len: int) -> float:
    return (_attn_proj_macs(m) + _core_macs_per_token(m, seq_len)
            + 3 * m["hidden_size"] * m["intermediate_size"])


def _head_macs_per_token(m: dict) -> int:
    """One pass's head and exit gate."""
    return m["hidden_size"] * m["vocab_size"] + m["hidden_size"]


def forward_macs_per_token(cfg: dict, seq_len: int) -> float:
    """``R x [L x (projections + core + SwiGLU) + head + gate]``."""
    m = _m(cfg)
    return m["total_ut_steps"] * (
        m["num_hidden_layers"] * _layer_macs_per_token(m, seq_len)
        + _head_macs_per_token(m))


def step_flops_per_sample(cfg: dict, mix: dict) -> float:
    """Forward + backward FLOPs of one training sample."""
    s = int(mix["seq_len"])
    return 3 * 2 * forward_macs_per_token(cfg, s) * s


def param_count(cfg: dict) -> int:
    """One copy of every weight, however often a pass uses it."""
    m = _m(cfg)
    d = m["hidden_size"]
    layer = _attn_proj_macs(m) + 3 * d * m["intermediate_size"] + 4 * d
    return (m["num_hidden_layers"] * layer      # with its four norms
            + 2 * m["vocab_size"] * d + d       # embedding, head, norm
            + d + 1)                            # the gate and its bias


def attn_core(cfg: dict, mix: dict, batch: int, kind: str,
              itemsize: int = 2) -> dict:
    """The attention cores alone of every application of a layer (``R x
    L`` of them, all ``"full_attention"``; another ``kind`` has none),
    forward and backward, for one step of ``batch`` rows: softmax(q k^T)
    v over the causal triangle. Forward reads q, k, v and writes o;
    backward reads those four and dO and writes dq, dk, dv; K, V and
    their gradients at the key-value heads. The Laguna counts'
    signature, since ``attn_full_core_roofline`` calls it."""
    m = _m(cfg)
    if kind != FULL:
        return {"flops": 0.0, "bytes": 0.0}
    s, hd = int(mix["seq_len"]), m["head_dim"]
    cores = m["total_ut_steps"] * m["num_hidden_layers"]
    flops = cores * 3 * 2 * batch * s * _core_macs_per_token(m, s)
    q = batch * m["num_attention_heads"] * s * hd * itemsize
    k = batch * m["num_key_value_heads"] * s * hd * itemsize
    nbytes = cores * ((2 * q + 2 * k) + (3 * q + 2 * k) + (q + 2 * k))
    return {"flops": float(flops), "bytes": float(nbytes)}


def loop_head(cfg: dict, mix: dict, batch: int, itemsize: int = 2) -> dict:
    """The ``R`` heads with the exit gate, forward and backward, for one
    step of ``batch`` rows: ``x_t W_head`` and ``x_t w_gate`` for every
    pass. Bytes: every pass's state read and its gradient written, and
    the head's weight charged once a chunk of ``loss_chunk`` rows a pass
    in each of the three products (the logits live on the chip a chunk
    at a time and are charged nothing); the weight's gradient written
    once."""
    m = _m(cfg)
    s, d, v = int(mix["seq_len"]), m["hidden_size"], m["vocab_size"]
    R = m["total_ut_steps"]
    rows = batch * s
    flops = 3 * 2 * R * rows * _head_macs_per_token(m)
    chunks = -(-rows // int(m.get("loss_chunk", 2048)))
    weight = d * v * itemsize
    nbytes = R * (3 * chunks * weight + 2 * rows * d * itemsize) + weight
    return {"flops": float(flops), "bytes": float(nbytes)}
