"""The sliding-window cores' share of their roofline: the least time the
chip could take for every ``*_swa`` layer's softmax(q k^T) v over the
band of ``sliding_window`` keys, grouped queries, forward and backward,
of one step (``counts.attn_core`` of the kind ``sliding_attention``)
over the device time of every operation under those layers' inner
``attn_core`` scope. The count is of the pairs the mask lets see: what
the tiling computes beside them (``swa_tile_waste``) lowers the share."""

from benchmark.metrics import mla_core_roofline

SCOPE = r"_swa\).*attn_core"


def read(ctx):
    return mla_core_roofline.read(ctx, SCOPE, "attn_core",
                                  "attn_window_core_roofline",
                                  kind="sliding_attention")
