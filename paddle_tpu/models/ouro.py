"""Ouro's looped decoder (ByteDance, Ouro-2.6B; "Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741; the keys are its
``config.json``'s): one stack of ``num_hidden_layers`` layers run
``total_ut_steps`` times a step over ONE copy of its weights, four
sandwich norms a layer, an exit gate, and a loss weighted over the
passes (the paper's stage-I objective)::

    x_0 = E[ids]
    for t = 1..R            (R = total_ut_steps; the same L layers, the
        y = x_{t-1}          same weights, every pass)
        for l = 1..L:   a = y + N2_l(Attn_l(N1_l(y)))
                        y = a + N4_l(FFN_l(N3_l(a)))
        x_t = N_out(y)      (the final norm closes every pass; the next
                             pass reads the normed state)
        z_t = x_t W_head;   g_t = x_t w_gate + b_gate
    lam_t = sigmoid(g_t);   p_t = lam_t prod_{j<t}(1 - lam_j) for t < R
    p_R = prod_{j<R}(1 - lam_j)                         (per position)
    loss = mean_i [ sum_t p_t[i] CE(z_t[i], id_{i+1}) - beta H(p[i]) ]
    H(p) = -sum_t p_t log p_t

``Attn(u)``: q, k, v = u W_q, u W_k, u W_v in ``num_attention_heads``
heads of ``head_dim`` over ``num_key_value_heads``, no bias; rotary by
halves over the whole head at ``rope_theta``; softmax(q k^T /
sqrt(head_dim) + causal) v; W_o. No gate, no q/k normalisation, no
window. ``FFN(u) = (silu(u W_g) * (u W_u)) W_d``, ``intermediate_size``
wide. ``N(v) = v / sqrt(mean(v^2) + rms_norm_eps) * g``.

The graph is unrolled: it holds ``R x L`` blocks, pass ``t``'s block
``i`` named ``ut<t>_blk<i>_attn`` / ``_mlp`` / ``_n1`` .. ``_n4``, each
using the parameters of pass 0's (``params_of``), so the parameter table
holds one leaf a weight and its gradient is the sum over the passes. The
names end in the kind of layer, which is how the benchmark's trace
reduction sorts device time, and say which pass an operation ran in. The
one input is ``words``.
"""

from __future__ import annotations

from paddle_tpu.config import dsl
from paddle_tpu.config.model_config import ParamAttr


def ouro(*, vocab_size: int = 49152, hidden_size: int = 2048,
         intermediate_size: int = 5632, num_hidden_layers: int = 48,
         num_attention_heads: int = 16, num_key_value_heads: int = 16,
         head_dim: int = 128, total_ut_steps: int = 4,
         rope_theta: float = 1e6, rms_norm_eps: float = 1e-6,
         entropy_weight: float = 0.1, recompute: bool = True,
         loss_chunk: int = 2048, attention_block: int = 512):
    """Returns (cost, softmax_output, data_names). ``entropy_weight`` is
    the loss's ``beta``. ``recompute`` marks the attention and
    feed-forward layers for rematerialisation as in ``models.laguna``:
    every APPLICATION of an attention layer keeps its input and its
    core's output and log-sum-exp (``total_ut_steps x num_hidden_layers``
    of them: 34 MB each at 4,096 tokens, 16 heads of 128) and recomputes
    the projections and the rotary turn. The softmax output reads the
    last pass's state; it is for inference and no part of the cost's
    graph."""
    remat = {"recompute": True} if recompute else None
    eps = rms_norm_eps

    def block(y, t, i):
        tag = f"ut{t}_blk{i}"

        def own(kind):      # pass 0 owns the weights, the others use them
            return None if t == 0 else f"ut0_blk{i}_{kind}"

        def norm(x, k):
            return dsl.rms_norm(x, epsilon=eps, name=f"{tag}_n{k}",
                                params_of=own(f"n{k}"))

        attn = dsl.gqa_attention(
            norm(y, 1), num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            window=None, rope_theta=float(rope_theta), gate=False,
            block=attention_block, layer_attr=remat, name=f"{tag}_attn",
            params_of=own("attn"))
        a = dsl.addto([y, norm(attn, 2)], name=f"{tag}_attn_add")
        ffn = dsl.swiglu(norm(a, 3), hidden=intermediate_size,
                         layer_attr=remat, name=f"{tag}_mlp",
                         params_of=own("mlp"))
        return dsl.addto([a, norm(ffn, 4)], name=f"{tag}_ffn_add")

    words = dsl.data(name="words", size=vocab_size, is_sequence=True)
    x = dsl.embedding(input=words, size=hidden_size, vocab_size=vocab_size,
                      name="embed")
    states = []
    for t in range(total_ut_steps):
        for i in range(num_hidden_layers):
            x = block(x, t, i)
        x = dsl.rms_norm(x, epsilon=eps, name=f"ut{t}_out_norm",
                         params_of=None if t == 0 else "ut0_out_norm")
        states.append(x)
    head = ParamAttr(name="_out_head.w0")
    cost = dsl.looped_lm_cost(states, words, vocab_size=vocab_size, shift=1,
                              beta=entropy_weight, chunk=loss_chunk,
                              name="out_head", param_attr=head)
    out = dsl.fc(input=states[-1], size=vocab_size, act="softmax",
                 bias_attr=False, param_attr=head, name="output")
    return cost, out, ["words"]
