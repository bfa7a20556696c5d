"""The full-attention cores' share of their roofline: the least time the
chip could take for every ``*_attn`` layer's softmax(q k^T) v over the
whole causal triangle, grouped queries, forward and backward, of one
step (``counts.attn_core`` of the kind ``full_attention``) over the
device time of every operation under those layers' inner ``attn_core``
scope, whatever implements it. A program without that scope, or counts
without ``attn_core``, give nothing to read."""

from benchmark.metrics import mla_core_roofline

SCOPE = r"_attn\).*attn_core"


def read(ctx):
    return mla_core_roofline.read(ctx, SCOPE, "attn_core",
                                  "attn_full_core_roofline",
                                  kind="full_attention")
