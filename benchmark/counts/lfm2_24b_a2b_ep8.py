"""Operations and bytes the LFM2-24B-A2B cut's algorithm needs, from its
shapes alone. A backward pass costs two products for every forward one,
so forward + backward is three times the forward; recomputed work (the
layers run under ``recompute``) does not count. A token of the attention
layer sees ``S / 2`` keys (causal: half of S^2). The routed experts count
the rows routed to the experts held here under a uniform router: ``k *
held / E`` experts a token. The head is the embedding's leaf: its
product counts once, the lookup is no product. All per sample (one
sequence of ``seq_len`` tokens) unless said.
"""

from __future__ import annotations

CONV, FULL = "conv", "full_attention"


def _m(cfg: dict) -> dict:
    return cfg["model"]["args"]


def _held(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def _kinds(m: dict, kind: str) -> int:
    return sum(1 for k in m["layer_types"] if k == kind)


def _conv_product_macs(m: dict) -> int:
    """``W_in`` [d, 3d] and ``W_out`` [d, d] for one token."""
    return 4 * m["hidden_size"] ** 2


def _attn_proj_macs(m: dict) -> int:
    d = m["hidden_size"]
    hd = d // m["num_attention_heads"]
    return 2 * d * d + 2 * d * m["num_key_value_heads"] * hd


def _core_macs_per_token(m: dict, seq_len: int) -> float:
    """QK^T and PV of one attention layer for one token."""
    return m["hidden_size"] * (seq_len / 2) * 2     # heads * head = d


def _expert_macs(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _moe_layers(m: dict) -> int:
    return len(m["layer_types"]) - m["num_dense_layers"]


def forward_macs_per_token(cfg: dict, seq_len: int) -> float:
    m = _m(cfg)
    d = m["hidden_size"]
    routed = (m["num_experts_per_tok"] * _held(m) / m["num_experts"]
              * _expert_macs(m))
    return (_kinds(m, CONV) * (_conv_product_macs(m)
                               + m["conv_L_cache"] * d)
            + _kinds(m, FULL) * (_attn_proj_macs(m)
                                 + _core_macs_per_token(m, seq_len))
            + m["num_dense_layers"] * 3 * d * m["intermediate_size"]
            + _moe_layers(m) * (d * m["num_experts"] + routed)
            + d * m["vocab_size"])


def step_flops_per_sample(cfg: dict, mix: dict) -> float:
    """Forward + backward FLOPs of one training sample."""
    s = int(mix["seq_len"])
    return 3 * 2 * forward_macs_per_token(cfg, s) * s


def param_count(cfg: dict) -> int:
    """The trained parameters (the static expert biases are none)."""
    m = _m(cfg)
    d, hd = m["hidden_size"], m["hidden_size"] // m["num_attention_heads"]
    n = m["vocab_size"] * d + d                 # the tied table, the norm
    n += len(m["layer_types"]) * 2 * d          # the blocks' norms
    n += _kinds(m, CONV) * (_conv_product_macs(m) + m["conv_L_cache"] * d)
    n += _kinds(m, FULL) * (_attn_proj_macs(m) + 2 * hd)
    n += m["num_dense_layers"] * 3 * d * m["intermediate_size"]
    n += _moe_layers(m) * (d * m["num_experts"]
                           + _held(m) * _expert_macs(m))
    return n


def short_conv(cfg: dict, mix: dict, batch: int, itemsize: int = 2) -> dict:
    """Every ``conv`` operator, forward and backward, for one step of
    ``batch`` rows, as a lower bound whatever is fused into what. FLOPs:
    the ``W_in`` and ``W_out`` products, times three. Bytes, in elements
    of ``T x d`` (T the step's tokens) and ``d x d``: the layer's input
    and output and their gradients (4 T d), the weights read forward and
    backward and their gradients written (12 d^2), and the core's
    traffic, ``[B | C | X]`` read and its result written forward (4 T
    d), those three and the result's gradient read and three gradients
    written backward (7 T d)."""
    m = _m(cfg)
    d, n = m["hidden_size"], _kinds(m, CONV)
    tokens = batch * int(mix["seq_len"])
    return {"flops": float(n * 3 * 2 * tokens * _conv_product_macs(m)),
            "bytes": float(n * itemsize * (15 * tokens * d + 12 * d * d))}


def attn_core(cfg: dict, mix: dict, batch: int, kind: str,
              itemsize: int = 2) -> dict:
    """The attention cores alone of every layer of ``kind``, forward and
    backward, for one step of ``batch`` rows: softmax(q k^T) v over the
    causal triangle (the Laguna counts' signature and bytes, since
    ``attn_full_core_roofline`` calls it). Forward reads q, k, v and
    writes o; backward reads those four and dO and writes dq, dk, dv; K,
    V and their gradients at the key-value heads."""
    m = _m(cfg)
    s, d = int(mix["seq_len"]), m["hidden_size"]
    layers = _kinds(m, kind)
    hd = d // m["num_attention_heads"]
    q = batch * s * d * itemsize                       # q, o, dO, dq alike
    k = batch * s * m["num_key_value_heads"] * hd * itemsize
    return {"flops": float(layers * 3 * 2 * batch * s
                           * _core_macs_per_token(m, s)),
            "bytes": float(layers * (6 * q + 6 * k))}


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
