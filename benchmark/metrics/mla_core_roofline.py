"""The latent-attention cores' share of their roofline: the least time
the chip could take for every ``*_attn`` layer's softmax(q k^T) v,
forward and backward, of one step (``counts.mla_core``: max of FLOPs
over the bf16 peak and bytes over the HBM peak) over the device time of
every operation under those layers' inner ``mla_core`` scope, whatever
implements it and the forward's recomputation included. The bound that
sets the least time is printed on standard error."""

import sys

from benchmark import peaks

SCOPE = r"_attn\).*mla_core"


def read(ctx, scope=SCOPE, need="mla_core", name="mla_core_roofline", **at):
    trace, counts = ctx.get("trace"), ctx.get("counts")
    if trace is None or ctx.get("peak") is None \
            or not hasattr(counts, need):
        return None
    steps = ctx["window"].steps
    seconds = trace.scope_seconds(scope)
    if steps <= 0 or seconds <= 0:
        return None
    work = getattr(counts, need)(
        ctx["cfg"], ctx["mix"], int(ctx["mix"]["batch"]) // ctx["chips"],
        **at)
    least, bound = peaks.least_seconds(work["flops"], work["bytes"],
                                       ctx["peak"])
    print(f"[bench] {name}: {1e3 * seconds / steps:.3f} ms a step under "
          f"the scope, least {1e3 * least:.3f} ms, bound by {bound}",
          file=sys.stderr)
    return 100.0 * least * steps / seconds
