"""The gated short-convolution layers' share of their roofline: the least
time the chip could take for every ``*_sconv`` layer, forward and
backward, of one step (``counts.short_conv``: the greater of the ``W_in``
and ``W_out`` products' FLOPs over the bf16 peak and the layer's bytes,
the core's traffic among them, over the HBM peak) over the device time
of every operation under those layers' scopes, forward, recomputed and
backward. Over the whole layer and not the core: a fusion is filed whole
under its root's part, so where the compiler folds a gate into a
product's operand the core's time moves under ``sconv_in`` or
``sconv_out``, and a share of the core alone would read over 100. A
program without such a layer, or counts without ``short_conv``, give
nothing to read."""

from benchmark.metrics import mla_core_roofline

SCOPE = r"jvp\(\w+_sconv\)"


def read(ctx):
    return mla_core_roofline.read(ctx, SCOPE, "short_conv",
                                  "short_conv_roofline")
