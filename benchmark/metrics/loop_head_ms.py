"""Milliseconds a step of device time under the looped cost's layer
``out_head``, forward and backward: the head on each of the ``R``
passes' states, the exit gate, the exit distribution and the weighted
loss. Its share of the least time the chip could take for the heads'
products (``counts.loop_head``) is printed on standard error, as the
roofline readers print theirs (it is theirs: the same reader, asked for
another scope and count). A program without the layer, or counts
without ``loop_head`` (another configuration's), give nothing to read."""

import sys

from benchmark.metrics import mla_core_roofline

SCOPE = r"jvp\(out_head\)"


def read(ctx):
    share = mla_core_roofline.read(ctx, SCOPE, "loop_head", "loop_head_ms")
    if share is None:
        return None
    print(f"[bench] loop_head_ms: the heads' least time is {share:.1f}% of it",
          file=sys.stderr)
    return 1e3 * ctx["trace"].scope_seconds(SCOPE) / ctx["window"].steps
