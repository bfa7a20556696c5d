"""Mellum2-12B-A2.5B's decoder (JetBrains, ``model_type`` ``mellum``; the
keys are its ``config.json``'s, of the Qwen3-MoE lineage): Laguna's
blocks (``models/laguna.py``) with a softmax router trained against a
load-balancing term, every layer sparse, no gate and no shared expert.

    x = E[ids]                                           (E [V, d], untied)
    for l:  h = x + Attn_l(N(x));  x = h + MoE_l(N(h))            (pre-norm)
    z = N(x) W_head;  CE = mean_i CE(z_i, id_{i+1});  loss = CE + c * L_bal
    N(v) = v / sqrt(mean(v^2) + eps) * g

    Attn_l:  q, k, v = u W_q, u W_k, u W_v in H / KV / KV heads of
             head_dim, no bias;  q = N_h(q; g_q), k = N_h(k; g_k) over
             each head (one scale of head_dim for all heads of a kind);
             rotary by halves over the whole head: a sliding layer by
             rope_parameters.sliding_attention's theta, a full layer by
             rope_parameters.full_attention's YaRN frequencies, cos and
             sin times its attention_factor;  softmax(q k^T / sqrt(hd) +
             mask) v, query head n reading key-value head n // (H / KV);
             mask causal, on a sliding layer also a window of
             sliding_window keys;  W_o.  No gate.
    MoE_l:   p = softmax(u W_r) in float32 over num_experts;  chosen =
             top-k of p;  w = p[chosen] / sum(p[chosen]);  y = sum of
             w_e SwiGLU_e(u) over the chosen experts held here;  no
             shared expert
    L_bal:   over the N (layer, token) rows of the step's expert layers,
             c_e = #{(n, j): chosen_{n,j} = e} / N,  P_e = sum_n p_{n,e} / N,
             L_bal = num_experts * sum_e c_e P_e   (k balanced; the choice
             carries no gradient, P does)

The one input is ``words``. Layer names end in the kind of layer, which
is how the benchmark's trace reduction sorts device time: ``blk<i>_swa``,
``blk<i>_attn``, ``blk<i>_moe``, ``blk<i>_a_norm``, ``blk<i>_f_norm``,
``out_norm``, ``out_head``, and ``moe_balance``, the balancing term.
"""

from __future__ import annotations

from typing import Optional, Sequence

from paddle_tpu.config import dsl
from paddle_tpu.models.laguna import (FULL, SLIDING, attention_layer,
                                      decoder, untied_head)


def mellum2(*, vocab_size: int = 98304, hidden_size: int = 2304,
            layer_types: Sequence[str] = (SLIDING, SLIDING, SLIDING, FULL),
            mlp_layer_types: Sequence[str] = ("sparse",) * 4,
            num_attention_heads: int = 32, num_key_value_heads: int = 4,
            head_dim: int = 128, sliding_window: int = 1024,
            rope_parameters: dict = None, num_experts: int = 64,
            experts_held: Optional[int] = None, expert_offset: int = 0,
            num_experts_per_tok: int = 8, moe_intermediate_size: int = 896,
            norm_topk_prob: bool = True, router_aux_loss_coef: float = 0.001,
            qk_norm: bool = True, rms_norm_eps: float = 1e-6,
            recompute: bool = True, loss_chunk: int = 2048,
            attention_block: int = 512):
    """Returns (cost, softmax_output, data_names); one block for every
    entry of ``layer_types``, each with the expert layer, of whose
    ``num_experts`` this chip holds ``experts_held`` from
    ``expert_offset`` on. ``rope_parameters`` is the config's group
    (``{"full_attention": {...}, "sliding_attention": {...}}``, as
    ``models.laguna`` reads it). ``recompute`` marks the attention layers
    for rematerialisation as in ``models.laguna``: a layer keeps its input
    and its core's output and log-sum-exp. ``qk_norm`` False leaves the
    per-head normalisation out. The cost is the head's loss plus
    ``router_aux_loss_coef`` times the balancing term over every expert
    layer (``dsl.moe_balance_cost``)."""
    if not norm_topk_prob or any(k != "sparse" for k in mlp_layer_types) \
            or len(mlp_layer_types) != len(layer_types):
        raise ValueError("the published form only: every layer sparse, the "
                         "chosen experts' weights normalised")
    remat = {"recompute": True} if recompute else None

    def attention(x, i):
        return attention_layer(
            x, i, layer_types[i], num_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            sliding_window=sliding_window, rope_parameters=rope_parameters,
            gate=False, qk_norm=qk_norm, qk_norm_eps=rms_norm_eps,
            block=attention_block, layer_attr=remat)

    def experts(x, i):
        return dsl.moe(
            x, expert_hidden=moe_intermediate_size, num_experts=num_experts,
            top_k=num_experts_per_tok, experts_held=experts_held,
            expert_offset=expert_offset, shared_hidden=0, score="softmax",
            name=f"blk{i}_moe")

    words, final, layers = decoder(vocab_size, hidden_size,
                                   len(layer_types), attention, experts,
                                   rms_norm_eps)
    cost, out = untied_head(final, words, vocab_size, loss_chunk)
    balance = dsl.moe_balance_cost(layers, coeff=router_aux_loss_coef,
                                   name="moe_balance")
    return dsl.addto([cost, balance], name="cost"), out, ["words"]
