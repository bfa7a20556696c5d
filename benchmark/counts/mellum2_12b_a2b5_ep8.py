"""Operations and bytes the Mellum2-12B-A2.5B cut's algorithm needs, from
its shapes alone. A backward pass costs two products for every forward
one, so forward + backward is three times the forward; recomputed work
(the attention layers run under ``recompute``) does not count, nor do
the norms, the q/k norms and the balancing term (vector work of a few
floats a token). A token of the full layer sees ``S / 2`` keys (causal:
half of S^2), a token of a sliding layer ``W - W^2 / (2 S)`` (the band of
``W`` keys, less the triangle the first ``W`` tokens lack). The routed
experts count the rows routed to the experts held here under a uniform
router: ``k * held / E`` experts a token. All per sample (one sequence of
``seq_len`` tokens) unless said. The Laguna counts' signatures.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def _m(cfg: dict) -> dict:
    return cfg["model"]["args"]


def _held(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def _attn_proj_macs(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return 2 * d * heads * hd + 2 * d * kv * hd


def _keys_seen(m: dict, kind: str, seq_len: int) -> float:
    """Keys a token sees on average on a layer of ``kind``."""
    w = m["sliding_window"]
    if kind == SLIDING and w < seq_len:
        return w - w * w / (2 * seq_len)
    return seq_len / 2


def _core_macs_per_token(m: dict, kind: str, seq_len: int) -> float:
    """QK^T and PV of a layer of ``kind`` for one token."""
    return (m["num_attention_heads"] * _keys_seen(m, kind, seq_len)
            * 2 * m["head_dim"])


def _expert_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def forward_macs_per_token(cfg: dict, seq_len: int) -> float:
    m = _m(cfg)
    d = m["hidden_size"]
    routed = (m["num_experts_per_tok"] * _held(m) / m["num_experts"]
              * _expert_macs(m))
    total = 0.0
    for kind in m["layer_types"]:
        total += (_attn_proj_macs(m) + _core_macs_per_token(m, kind, seq_len)
                  + d * m["num_experts"] + routed)
    return total + d * m["vocab_size"]


def step_flops_per_sample(cfg: dict, mix: dict) -> float:
    """Forward + backward FLOPs of one training sample."""
    s = int(mix["seq_len"])
    return 3 * 2 * forward_macs_per_token(cfg, s) * s


def param_count(cfg: dict) -> int:
    """The trained parameters (the static selection bias not among them)."""
    m = _m(cfg)
    d, hd = m["hidden_size"], m["head_dim"]
    n = 2 * m["vocab_size"] * d + d                 # embedding, head, norm
    per_layer = (_attn_proj_macs(m) + 2 * d          # + the block's norms
                 + (2 * hd if m.get("qk_norm", True) else 0)
                 + d * m["num_experts"] + _held(m) * _expert_macs(m))
    return n + len(m["layer_types"]) * per_layer


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
    s, hd = int(mix["seq_len"]), m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    layers = sum(1 for k in m["layer_types"] if k == kind)
    q = batch * heads * s * hd * itemsize           # q, o, dO, dq alike
    k = batch * kv * s * hd * itemsize              # k, v, dk, dv alike
    return {"flops": float(layers * 3 * 2 * batch * s
                           * _core_macs_per_token(m, kind, s)),
            "bytes": float(layers * ((2 * q + 2 * k) + (3 * q + 2 * k)
                                     + (q + 2 * k)))}


def moe_experts(cfg: dict, mix: dict, batch: int, itemsize: int = 2,
                rows: float = None, active: float = None) -> dict:
    """The grouped products of the routed experts alone (every ``*_moe``
    layer, forward and backward) for one step of ``batch`` rows, at
    ``rows`` rows a layer over the experts held, ``active`` of which got
    any row (the program's own counts where it gives them; else what a
    uniform router sends here, to every held expert)."""
    m = _m(cfg)
    moe = len(m["layer_types"])
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
