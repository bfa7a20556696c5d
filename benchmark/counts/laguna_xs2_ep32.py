"""Operations and bytes the Laguna-XS.2 cut's algorithm needs, from its
shapes alone. A backward pass costs two products for every forward one,
so forward + backward is three times the forward; recomputed work (the
layers run under ``recompute``) does not count. A token of a full layer
sees ``S / 2`` keys (causal: half of S^2), a token of a sliding layer
``W - W^2 / (2 S)`` (the band of ``W`` keys, less the triangle the first
``W`` tokens lack). The routed experts count the rows routed to the
experts held here under a uniform router: ``k * held / E`` experts a
token. All per sample (one sequence of ``seq_len`` tokens) unless said.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def _m(cfg: dict) -> dict:
    return cfg["model"]["args"]


def _held(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def _attn_proj_macs(m: dict, heads: int) -> int:
    d, hd, kv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    gate = heads if m.get("gating", True) else 0
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d + d * gate


def _keys_seen(m: dict, kind: str, seq_len: int) -> float:
    """Keys a token sees on average on a layer of ``kind``."""
    w = m["sliding_window"]
    if kind == SLIDING and w < seq_len:
        return w - w * w / (2 * seq_len)
    return seq_len / 2


def _core_macs_per_token(m: dict, i: int, seq_len: int) -> float:
    """QK^T and PV of layer ``i`` for one token."""
    return (m["num_attention_heads_per_layer"][i]
            * _keys_seen(m, m["layer_types"][i], seq_len)
            * 2 * m["head_dim"])


def _expert_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _shared_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m.get("shared_expert_intermediate_size", 0)


def _moe_layers(m: dict) -> int:
    return sum(1 for kind in m["mlp_layer_types"] if kind != "dense")


def forward_macs_per_token(cfg: dict, seq_len: int) -> float:
    m = _m(cfg)
    d = m["hidden_size"]
    routed = (m["num_experts_per_tok"] * _held(m) / m["num_experts"]
              * _expert_macs(m))
    total = 0.0
    for i, heads in enumerate(m["num_attention_heads_per_layer"]):
        total += _attn_proj_macs(m, heads) + _core_macs_per_token(
            m, i, seq_len)
        if m["mlp_layer_types"][i] == "dense":
            total += 3 * d * m["intermediate_size"]
        else:
            total += d * m["num_experts"] + _shared_macs(m) + routed
    return total + d * m["vocab_size"]


def step_flops_per_sample(cfg: dict, mix: dict) -> float:
    """Forward + backward FLOPs of one training sample."""
    s = int(mix["seq_len"])
    return 3 * 2 * forward_macs_per_token(cfg, s) * s


def param_count(cfg: dict) -> int:
    m = _m(cfg)
    d, h = m["hidden_size"], m["moe_intermediate_size"]
    n = 2 * m["vocab_size"] * d + d                 # embedding, head, norm
    for i, heads in enumerate(m["num_attention_heads_per_layer"]):
        n += _attn_proj_macs(m, heads) + 2 * d      # + the block's norms
        if m["mlp_layer_types"][i] == "dense":
            n += 3 * d * m["intermediate_size"]
        else:
            n += (d * m["num_experts"] + m["num_experts"]
                  + _held(m) * 3 * d * h + _shared_macs(m))
    return n


def attn_core(cfg: dict, mix: dict, batch: int, kind: str,
              itemsize: int = 2) -> dict:
    """The attention cores alone of every layer of ``kind``
    (``"full_attention"`` or ``"sliding_attention"``), forward and
    backward, for one step of ``batch`` rows: softmax(q k^T) v over the
    keys the mask lets see. Forward reads q, k, v and writes o; backward
    reads those four and dO and writes dq, dk, dv; K, V and their
    gradients counted at the key-value heads (a group's query heads
    share them), the rows' statistics negligible."""
    m = _m(cfg)
    s, hd, kv = int(mix["seq_len"]), m["head_dim"], m["num_key_value_heads"]
    flops = nbytes = 0.0
    for i, heads in enumerate(m["num_attention_heads_per_layer"]):
        if m["layer_types"][i] != kind:
            continue
        flops += 3 * 2 * batch * s * _core_macs_per_token(m, i, s)
        q = batch * heads * s * hd * itemsize       # q, o, dO, dq alike
        k = batch * kv * s * hd * itemsize          # k, v, dk, dv alike
        nbytes += (2 * q + 2 * k) + (3 * q + 2 * k) + (q + 2 * k)
    return {"flops": float(flops), "bytes": float(nbytes)}


def moe_experts(cfg: dict, mix: dict, batch: int, itemsize: int = 2,
                rows: float = None, active: float = None) -> dict:
    """The grouped products of the routed experts alone (every ``*_moe``
    layer, forward and backward) for one step of ``batch`` rows, at
    ``rows`` rows a layer over the experts held, ``active`` of which got
    any row (the program's own counts where it gives them; else what a
    uniform router sends here, to every held expert): the JoyAI counts'
    signature and bytes, since ``moe_experts_roofline`` calls it."""
    m = _m(cfg)
    moe = _moe_layers(m)
    d, h = m["hidden_size"], m["moe_intermediate_size"]
    if rows is None:
        rows = (batch * int(mix["seq_len"]) * m["num_experts_per_tok"]
                * _held(m) / m["num_experts"])
    if active is None:
        active = _held(m)
    active = min(active, _held(m), rows)    # an expert needs a row
    flops = 3 * 2 * moe * rows * _expert_macs(m)
    weights = active * _expert_macs(m) * itemsize
    acts = rows * (2 * d + 3 * h) * itemsize
    return {"flops": float(flops),
            "bytes": float(moe * (3 * weights + 3 * acts))}
