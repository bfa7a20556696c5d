"""LFM2-MoE's decoder (LiquidAI, ``model_type`` ``lfm2_moe``; the keys
are its ``config.json``'s): the first decoder here whose layers differ
in *operator*, most of them no attention at all.

    x = E[ids]                                                (E [V, d])
    for l:  h = x + Op_l(N(x; g_op_l));  x = h + FFN_l(N(h; g_ffn_l))
    z = N(x; g_out) E^T;  loss = mean_i CE(z_i, id_{i+1})   (the head is E)
    N(v; g) = v / sqrt(mean(v^2) + norm_eps) * g

    Op = conv:  [B | C | X] = u W_in;  s = B * X
                c_t = sum_{j<k} w[j] * s_{t-k+1+j}   (depthwise, causal,
                k = conv_L_cache taps, s_{<0} = 0, no bias)
                y = (C * c) W_out
    Op = full_attention:  q, k, v = u W_q, u W_k, u W_v in H / KV / KV
                heads of d / H, no bias;  q = N(q; g_q), k = N(k; g_k)
                over each head (one scale for all heads);  rotary by
                halves over the whole head;  softmax(q k^T / sqrt(d / H)
                + causal) v;  W_o.  No gate, no window.
    FFN dense (l < num_dense_layers):  (silu(u W_1) * (u W_3)) W_2
    FFN sparse: s = sigmoid(u W_r) in float32 over num_experts;  chosen =
                top-k of (s + b), b the static expert bias;  g =
                s[chosen] / (sum(s[chosen]) + 1e-6) *
                routed_scaling_factor;  y = sum_e g_e SwiGLU_e(u) over
                the chosen experts held here;  no shared expert

The one input is ``words``. Layer names end in the kind of layer, which
is how the benchmark's trace reduction sorts device time: ``blk<i>_sconv``
(not ``conv``: a ResNet's layers own that word), ``blk<i>_attn``,
``blk<i>_moe``, ``blk<i>_mlp``, ``blk<i>_a_norm``, ``blk<i>_f_norm``,
``out_norm``, ``out_head``.
"""

from __future__ import annotations

from typing import Optional, Sequence

from paddle_tpu.config import dsl
from paddle_tpu.config.model_config import ParamAttr

CONV, FULL = "conv", "full_attention"


def lfm2_moe(*, vocab_size: int = 65536, hidden_size: int = 2048,
             intermediate_size: int = 11776,
             layer_types: Sequence[str] = (CONV, CONV, FULL, CONV),
             num_dense_layers: int = 2, num_attention_heads: int = 32,
             num_key_value_heads: int = 8, conv_L_cache: int = 3,
             rope_parameters: dict = None, norm_eps: float = 1e-5,
             num_experts: int = 64, experts_held: Optional[int] = None,
             expert_offset: int = 0, num_experts_per_tok: int = 4,
             moe_intermediate_size: int = 1536,
             routed_scaling_factor: float = 1.0, norm_topk_prob: bool = True,
             norm_topk_eps: float = 1e-6, use_expert_bias: bool = True,
             conv_bias: bool = False, recompute: bool = True,
             loss_chunk: int = 2048, attention_block: int = 512):
    """Returns (cost, softmax_output, data_names); one block for every
    entry of ``layer_types``, the first ``num_dense_layers`` of them with
    the dense SwiGLU and the rest with the expert layer, of whose
    ``num_experts`` this chip holds ``experts_held`` from
    ``expert_offset`` on. ``rope_parameters`` is the config's group
    (``{"rope_theta", "rope_type": "default"}``; None: theta 10000).
    ``recompute`` marks the operators and the dense feed-forward layers
    for rematerialisation as in ``models.laguna``: a ``conv`` operator
    keeps its input and runs its ``W_in`` product again, an attention
    layer keeps its input and its core's output. The head is the
    embedding's leaf (``tie_embedding``); the softmax output is for
    inference and no part of the cost's graph."""
    if conv_bias or not (norm_topk_prob and use_expert_bias):
        raise ValueError("the family's published form only: no convolution "
                         "bias, normalised top-k weights, an expert bias")
    remat = {"recompute": True} if recompute else None
    rope = rope_parameters or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"no rotary scheme {rope['rope_type']!r} here")

    def operator(x, i):
        kind = layer_types[i]
        if kind == CONV:
            return dsl.short_conv(x, kernel=conv_L_cache, layer_attr=remat,
                                  name=f"blk{i}_sconv")
        if kind != FULL:
            raise ValueError(f"layer {i}: no operator of kind {kind!r}")
        return dsl.gqa_attention(
            x, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads,
            head_dim=hidden_size // num_attention_heads,
            rope_theta=float(rope.get("rope_theta", 10000.0)), gate=False,
            qk_norm=True, qk_norm_eps=norm_eps, block=attention_block,
            layer_attr=remat, name=f"blk{i}_attn")

    def feed_forward(x, i):
        if i < num_dense_layers:
            return dsl.swiglu(x, hidden=intermediate_size,
                              name=f"blk{i}_mlp", layer_attr=remat)
        return dsl.moe(
            x, expert_hidden=moe_intermediate_size, num_experts=num_experts,
            top_k=num_experts_per_tok, experts_held=experts_held,
            expert_offset=expert_offset, shared_hidden=0,
            routed_scaling_factor=routed_scaling_factor,
            norm_eps=norm_topk_eps, name=f"blk{i}_moe")

    words = dsl.data(name="words", size=vocab_size, is_sequence=True)
    embed = dsl.embedding(input=words, size=hidden_size,
                          vocab_size=vocab_size, name="embed")
    x = embed
    for i in range(len(layer_types)):
        tag = f"blk{i}"
        a = operator(dsl.rms_norm(x, epsilon=norm_eps,
                                  name=f"{tag}_a_norm"), i)
        h = dsl.addto([x, a], name=f"{tag}_op_add")
        f = feed_forward(dsl.rms_norm(h, epsilon=norm_eps,
                                      name=f"{tag}_f_norm"), i)
        x = dsl.addto([h, f], name=f"{tag}_ffn_add")
    final = dsl.rms_norm(x, epsilon=norm_eps, name="out_norm")
    cost = dsl.lm_cost(final, words, vocab_size=vocab_size, shift=1,
                       chunk=loss_chunk, name="out_head", tied_to=embed)
    out = dsl.mixed([final], size=vocab_size, act="softmax", name="output",
                    projections=[{"type": "trans_full_matrix",
                                  "param_attr": ParamAttr(
                                      name=f"_{embed.name}.w0")}])
    return cost, out, ["words"]
