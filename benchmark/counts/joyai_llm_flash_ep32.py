"""Operations and bytes the JoyAI-LLM-Flash cut's algorithm needs, from
its shapes alone. A backward pass costs two products for every forward
one, so forward + backward is three times the forward; recomputed work
(the blocks run under ``recompute``) does not count. Causal attention
counts half of S^2. The routed experts count the rows routed to the
experts held here under a uniform router: ``k * held / E`` experts a
token. All per sample (one sequence of ``seq_len`` tokens) unless said.
"""

from __future__ import annotations


def _m(cfg: dict) -> dict:
    return cfg["model"]["args"]


def _blocks(m: dict):
    """(attention layers, expert layers, dense layers), the multi-token
    module's block among them."""
    mtp = 1 if m.get("num_nextn_predict_layers", 1) else 0
    dense = min(m.get("first_k_dense_replace", 1), m["num_hidden_layers"])
    return (m["num_hidden_layers"] + mtp,
            m["num_hidden_layers"] - dense + mtp, dense)


def _held(m: dict) -> int:
    return m.get("experts_held") or m["n_routed_experts"]


def _attn_proj_macs(m: dict) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return (d * qr + qr * h * (nope + rope) + d * (kvr + rope)
            + kvr * h * (nope + dv) + h * dv * d)


def _core_macs_per_token(m: dict, seq_len: int) -> float:
    """QK^T and PV of one layer for one token, causal: a token sees
    (seq_len + 1) / 2 keys on average; counted as seq_len / 2."""
    return (m["num_attention_heads"] * seq_len / 2
            * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
               + m["v_head_dim"]))


def _expert_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def forward_macs_per_token(cfg: dict, seq_len: int) -> float:
    m = _m(cfg)
    attn, moe, dense = _blocks(m)
    d = m["hidden_size"]
    routed = (m["num_experts_per_tok"] * _held(m) / m["n_routed_experts"]
              * _expert_macs(m))
    shared = m.get("n_shared_experts", 1) * _expert_macs(m)
    total = attn * (_attn_proj_macs(m) + _core_macs_per_token(m, seq_len))
    total += dense * 3 * d * m["intermediate_size"]
    total += moe * (d * m["n_routed_experts"] + shared + routed)
    heads = 1
    if m.get("num_nextn_predict_layers", 1):
        total += 2 * d * d          # the module's projection
        heads = 2
    return total + heads * d * m["vocab_size"]


def step_flops_per_sample(cfg: dict, mix: dict) -> float:
    """Forward + backward FLOPs of one training sample."""
    s = int(mix["seq_len"])
    return 3 * 2 * forward_macs_per_token(cfg, s) * s


def param_count(cfg: dict) -> int:
    m = _m(cfg)
    attn, moe, dense = _blocks(m)
    d, h = m["hidden_size"], m["moe_intermediate_size"]
    n = 2 * m["vocab_size"] * d + d                 # embedding, head, norm
    n += attn * (_attn_proj_macs(m) + m["q_lora_rank"] + m["kv_lora_rank"]
                 + 2 * d)                           # + the block's norms
    n += dense * 3 * d * m["intermediate_size"]
    n += moe * (d * m["n_routed_experts"] + m["n_routed_experts"]
                + (_held(m) + m.get("n_shared_experts", 1)) * 3 * d * h)
    if m.get("num_nextn_predict_layers", 1):
        n += 2 * d * d + 3 * d                      # projection, 3 norms
    return n


def mla_core(cfg: dict, mix: dict, batch: int, itemsize: int = 2) -> dict:
    """The attention cores alone (every ``*_attn`` layer, forward and
    backward) for one step of ``batch`` rows: softmax(q k^T) v, causal,
    with q, k of nope + rope and v of v_head_dim. Forward reads q, k, v
    and writes o; backward reads those four and dO and writes dq, dk,
    dv; the rows' statistics are negligible."""
    m = _m(cfg)
    attn, _, _ = _blocks(m)
    s, heads = int(mix["seq_len"]), m["num_attention_heads"]
    dqk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    dv = m["v_head_dim"]
    flops = 3 * 2 * attn * batch * s * _core_macs_per_token(m, s)
    qk = batch * heads * s * dqk * itemsize
    v = batch * heads * s * dv * itemsize
    forward = 2 * qk + 2 * v
    backward = (2 * qk + 3 * v) + (2 * qk + v)
    return {"flops": float(flops),
            "bytes": float(attn * (forward + backward))}


def moe_experts(cfg: dict, mix: dict, batch: int, itemsize: int = 2,
                rows: float = None, active: float = None) -> dict:
    """The grouped products of the routed experts alone (every ``*_moe``
    layer, forward and backward) for one step of ``batch`` rows, at
    ``rows`` rows a layer over the experts held, ``active`` of which got
    any row (the program's own counts where it gives them: a router is
    not uniform; else what a uniform router sends here, to every held
    expert). Forward reads the three stacked weights of the experts that
    got rows and the gathered rows [R, d] and writes gate, up [R, h] and
    the result [R, d]; backward reads those weights again and writes
    their gradients, and moves about twice the forward's activations. An
    expert without a row costs nothing here: its weights need not be
    read, and its gradient is zero."""
    m = _m(cfg)
    _, moe, _ = _blocks(m)
    d, h = m["hidden_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = (batch * int(mix["seq_len"]) * m["num_experts_per_tok"]
                * _held(m) / m["n_routed_experts"])
    if active is None:
        active = _held(m)
    active = min(active, _held(m), rows)    # an expert needs a row
    flops = 3 * 2 * moe * rows * _expert_macs(m)
    weights = active * _expert_macs(m) * itemsize
    acts = rows * (2 * d + 3 * h) * itemsize
    return {"flops": float(flops),
            "bytes": float(moe * (3 * weights + 3 * acts))}
