"""Laguna-XS.2's decoder (poolside, 33.4B-A3B; the keys are its
``config.json``'s): the first decoder here whose layers differ in kind.
Pre-norm residual blocks ``h = x + Attn_l(RMSNorm(x))``, ``x' = h +
FFN_l(RMSNorm(h))``; layer ``l``'s attention is grouped-query
(``num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads``) with a per-head sigmoid output gate, and by
``layer_types[l]`` either ``sliding_attention`` (a window of
``sliding_window`` keys, rotary over the whole head at
``rope_parameters.sliding_attention``'s theta) or ``full_attention``
(the whole sequence, rotary over ``partial_rotary_factor`` of the head
with ``rope_parameters.full_attention``'s YaRN frequencies); its
feed-forward half by ``mlp_layer_types[l]`` a ``dense`` SwiGLU of
``intermediate_size`` or the ``sparse`` expert layer (sigmoid top-k
routing over ``num_experts``, of which this chip holds ``experts_held``
from ``expert_offset`` on, weights normalised and scaled, one shared
expert). A final RMSNorm, an untied head, the mean cross-entropy of
position ``i`` against ``t_{i+1}``.

The one input is ``words``. Layer names end in the kind of layer, which
is how the benchmark's trace reduction sorts device time: a full layer's
attention is ``blk<i>_attn``, a sliding layer's ``blk<i>_swa``, then
``blk<i>_moe``, ``blk<i>_mlp``, ``blk<i>_a_norm``, ``out_head``.
"""

from __future__ import annotations

from typing import Optional, Sequence

from paddle_tpu.config import dsl
from paddle_tpu.config.model_config import ParamAttr

FULL, SLIDING = "full_attention", "sliding_attention"


def laguna(*, vocab_size: int = 100352, hidden_size: int = 2048,
           intermediate_size: int = 8192,
           layer_types: Sequence[str] = (FULL, SLIDING, SLIDING, SLIDING),
           num_attention_heads_per_layer: Sequence[int] = (48, 64, 64, 64),
           mlp_layer_types: Sequence[str] = ("dense", "sparse", "sparse",
                                             "sparse"),
           num_key_value_heads: int = 8, head_dim: int = 128,
           sliding_window: int = 512, rope_parameters: dict = None,
           gating: bool = True, num_experts: int = 256,
           experts_held: Optional[int] = None, expert_offset: int = 0,
           num_experts_per_tok: int = 8, moe_intermediate_size: int = 512,
           shared_expert_intermediate_size: int = 512,
           moe_routed_scaling_factor: float = 2.5,
           rms_norm_eps: float = 1e-6, recompute: bool = True,
           loss_chunk: int = 2048, attention_block: int = 512):
    """Returns (cost, softmax_output, data_names); one block for every
    entry of ``layer_types``. ``rope_parameters`` is the config's group:
    ``{"full_attention": {rope_theta, rope_type, factor,
    original_max_position_embeddings, beta_slow, beta_fast,
    attention_factor, partial_rotary_factor}, "sliding_attention":
    {rope_theta, partial_rotary_factor}}`` (None: theta 10000 over the
    whole head in both). ``recompute`` marks the attention and dense
    feed-forward layers for rematerialisation as in
    ``models.joyai_llm_flash``: an attention layer of either kind keeps
    its input and its core's output and log-sum-exp (134 MB a sliding
    layer at 8,192 tokens, 64 heads of 128) and recomputes the
    projections, the rotary turn and the gate. ``attention_block`` is the
    flash kernels' tile (512 x 512: 134 MFLOP a grid step at a head of
    128). The softmax output is for inference and no part of the cost's
    graph."""
    depth = len(layer_types)
    if not (len(num_attention_heads_per_layer) == len(mlp_layer_types)
            == depth):
        raise ValueError("layer_types, num_attention_heads_per_layer and "
                         "mlp_layer_types name the same layers")
    remat = {"recompute": True} if recompute else None

    def attention(x, i):
        return attention_layer(
            x, i, layer_types[i], num_heads=num_attention_heads_per_layer[i],
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            sliding_window=sliding_window, rope_parameters=rope_parameters,
            gate=gating, block=attention_block, layer_attr=remat)

    def feed_forward(x, i):
        if mlp_layer_types[i] == "dense":
            return dsl.swiglu(x, hidden=intermediate_size,
                              name=f"blk{i}_mlp", layer_attr=remat)
        return dsl.moe(
            x, expert_hidden=moe_intermediate_size, num_experts=num_experts,
            top_k=num_experts_per_tok, experts_held=experts_held,
            expert_offset=expert_offset,
            shared_hidden=shared_expert_intermediate_size,
            routed_scaling_factor=moe_routed_scaling_factor,
            name=f"blk{i}_moe")

    words, final, _ = decoder(vocab_size, hidden_size, depth, attention,
                              feed_forward, rms_norm_eps)
    cost, out = untied_head(final, words, vocab_size, loss_chunk)
    return cost, out, ["words"]


def attention_layer(x, i, kind, *, num_heads, num_key_value_heads,
                    head_dim, sliding_window, rope_parameters, gate,
                    qk_norm=False, qk_norm_eps=1e-6, block, layer_attr):
    """Layer ``i``'s grouped-query attention of ``kind``: a window of
    ``sliding_window`` keys and ``rope_parameters.sliding_attention``'s
    rotary turn (``blk<i>_swa``), or the whole sequence and
    ``rope_parameters.full_attention``'s, YaRN where its ``rope_type``
    says so (``blk<i>_attn``); rotary over ``partial_rotary_factor`` of
    the head (all of it by default)."""
    if kind not in (FULL, SLIDING):
        raise ValueError(f"layer {i}: no attention of kind {kind!r}")
    r = (rope_parameters or {}).get(kind, {})
    rotary_dim = int(head_dim * r.get("partial_rotary_factor", 1))
    yarn = None
    if r.get("rope_type", "default") == "yarn":
        yarn = {k: r[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor") if k in r}
    return dsl.gqa_attention(
        x, num_heads=num_heads, num_kv_heads=num_key_value_heads,
        head_dim=head_dim, window=sliding_window if kind == SLIDING else None,
        rotary_dim=rotary_dim,
        rope_theta=float(r.get("rope_theta", 10000.0)), yarn=yarn, gate=gate,
        qk_norm=qk_norm, qk_norm_eps=qk_norm_eps, block=block,
        layer_attr=layer_attr,
        name=f"blk{i}_{'swa' if kind == SLIDING else 'attn'}")


def decoder(vocab_size, hidden_size, depth, attention, feed_forward, eps):
    """``(words, final, feed_forwards)``: the ids' data layer, the final
    RMSNorm of ``depth`` pre-norm residual blocks over their embedding,
    ``h = x + attention(N(x), i)``, ``x' = h + feed_forward(N(h), i)``,
    and every block's feed-forward layer."""
    words = dsl.data(name="words", size=vocab_size, is_sequence=True)
    x = dsl.embedding(input=words, size=hidden_size, vocab_size=vocab_size,
                      name="embed")
    feed_forwards = []
    for i in range(depth):
        tag = f"blk{i}"
        a = attention(dsl.rms_norm(x, epsilon=eps, name=f"{tag}_a_norm"), i)
        h = dsl.addto([x, a], name=f"{tag}_attn_add")
        f = feed_forward(dsl.rms_norm(h, epsilon=eps, name=f"{tag}_f_norm"),
                         i)
        feed_forwards.append(f)
        x = dsl.addto([h, f], name=f"{tag}_ffn_add")
    final = dsl.rms_norm(x, epsilon=eps, name="out_norm")
    return words, final, feed_forwards


def untied_head(final, words, vocab_size, loss_chunk):
    """``(cost, softmax_output)``: the head, a leaf of its own, fused with
    the mean cross-entropy of position ``i`` against ``t_{i+1}``, and the
    same head's softmax for inference (no part of the cost's graph)."""
    head = ParamAttr(name="_out_head.w0")
    cost = dsl.lm_cost(final, words, vocab_size=vocab_size, shift=1,
                       chunk=loss_chunk, name="out_head", param_attr=head)
    out = dsl.fc(input=final, size=vocab_size, act="softmax",
                 bias_attr=False, param_attr=head, name="output")
    return cost, out
