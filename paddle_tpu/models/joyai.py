"""JoyAI-LLM-Flash's decoder (jdopensource, 48B-A2.7B; the keys are its
``config.json``'s, DeepSeek-V3's): the first decoder-only language model
builder here. Pre-norm residual blocks ``h = x + Attn(RMSNorm(x))``,
``x' = h + FFN(RMSNorm(h))`` with multi-head latent attention; the first
``first_k_dense_replace`` blocks have a dense SwiGLU FFN, the rest the
expert layer (sigmoid top-k routing over ``n_routed_experts``, of which
this chip holds ``experts_held`` from ``expert_offset`` on, plus shared
experts); one multi-token-prediction module of depth 1 (DeepSeek-V3,
arXiv:2412.19437 §2.2): ``h'_i = [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))]
W_eh``, one expert block, RMSNorm, the model's own head, scored against
``t_{i+2}``. Loss = CE(main, t_{i+1}) + ``mtp_loss_weight`` * CE(mtp,
t_{i+2}), each a mean over the positions that have a target.

The one input is ``words``; the targets are shifts of it. Layer names end
in the kind of layer (``blk3_attn``, ``blk3_moe``, ``blk0_mlp``,
``blk3_a_norm``, ``mtp_proj``, ``out_head``), which is how the
benchmark's trace reduction sorts device time.
"""

from __future__ import annotations

from paddle_tpu.config import dsl
from paddle_tpu.config.model_config import ParamAttr


def joyai_llm_flash(*, vocab_size: int = 129280, hidden_size: int = 2048,
                    num_hidden_layers: int = 40,
                    first_k_dense_replace: int = 1,
                    intermediate_size: int = 7168,
                    moe_intermediate_size: int = 768,
                    n_routed_experts: int = 256, experts_held: int = None,
                    expert_offset: int = 0, n_shared_experts: int = 1,
                    num_experts_per_tok: int = 8,
                    routed_scaling_factor: float = 2.5,
                    num_attention_heads: int = 32, q_lora_rank: int = 1536,
                    kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
                    qk_rope_head_dim: int = 64, v_head_dim: int = 128,
                    rope_theta: float = 32e6, rms_norm_eps: float = 1e-6,
                    num_nextn_predict_layers: int = 1,
                    mtp_loss_weight: float = 0.3, recompute: bool = True,
                    loss_chunk: int = 2048):
    """Returns (cost, softmax_output, data_names). ``recompute`` marks
    the attention and dense feed-forward layers for rematerialisation
    (an expert layer keeps little: its routed part recomputes from the
    layer's input by itself). A feed-forward layer then keeps its input
    alone; an attention layer keeps its core's output and log-sum-exp
    besides (the kernel names them, ``ops/attention.py``: 68 MB a layer
    at 2 x 4,096 tokens, 32 heads of 128), so the backward pass
    recomputes the projections and not the core. The softmax output is
    for inference and no part of the cost's graph."""
    if num_nextn_predict_layers not in (0, 1):
        raise ValueError("one multi-token-prediction module at most")
    remat = {"recompute": True} if recompute else None
    eps = rms_norm_eps
    head = ParamAttr(name="_out_head.w0")

    def attention(x, name):
        return dsl.mla_attention(
            x, num_heads=num_attention_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, epsilon=eps, name=name, layer_attr=remat)

    def experts(x, name):
        return dsl.moe(
            x, expert_hidden=moe_intermediate_size,
            num_experts=n_routed_experts, top_k=num_experts_per_tok,
            experts_held=experts_held, expert_offset=expert_offset,
            shared_hidden=n_shared_experts * moe_intermediate_size,
            routed_scaling_factor=routed_scaling_factor, name=name)

    def block(x, tag, dense):
        a = attention(dsl.rms_norm(x, epsilon=eps, name=f"{tag}_a_norm"),
                      f"{tag}_attn")
        h = dsl.addto([x, a], name=f"{tag}_attn_add")
        n = dsl.rms_norm(h, epsilon=eps, name=f"{tag}_f_norm")
        f = (dsl.swiglu(n, hidden=intermediate_size, name=f"{tag}_mlp",
                        layer_attr=remat) if dense
             else experts(n, f"{tag}_moe"))
        return dsl.addto([h, f], name=f"{tag}_ffn_add")

    words = dsl.data(name="words", size=vocab_size, is_sequence=True)
    embed = dsl.embedding(input=words, size=hidden_size,
                          vocab_size=vocab_size, name="embed")
    x = embed
    for i in range(num_hidden_layers):
        x = block(x, f"blk{i}", dense=i < first_k_dense_replace)
    final = dsl.rms_norm(x, epsilon=eps, name="out_norm")
    cost = dsl.lm_cost(final, words, vocab_size=vocab_size, shift=1,
                       chunk=loss_chunk, name="out_head", param_attr=head)
    if num_nextn_predict_layers:
        both = dsl.concat(
            [dsl.rms_norm(x, epsilon=eps, name="mtp_h_norm"),
             dsl.rms_norm(dsl.seq_shift(embed, offset=1, name="mtp_shift"),
                          epsilon=eps, name="mtp_e_norm")], name="mtp_cat")
        h = dsl.fc(input=both, size=hidden_size, act="linear",
                   bias_attr=False, name="mtp_proj")
        h = block(h, "mtp", dense=False)
        mtp = dsl.lm_cost(
            dsl.rms_norm(h, epsilon=eps, name="mtp_out_norm"), words,
            vocab_size=vocab_size, shift=2, coeff=mtp_loss_weight,
            chunk=loss_chunk, name="mtp_head", param_attr=head)
        cost = dsl.addto([cost, mtp], name="cost")
    out = dsl.fc(input=final, size=vocab_size, act="softmax",
                 bias_attr=False, param_attr=head, name="output")
    return cost, out, ["words"]
