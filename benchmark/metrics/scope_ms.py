"""Milliseconds a step of device time under a scope pattern: the union
of the intervals of every operation whose ``op_name`` path matches, on
the worst device, over the steps of the traced window. The one body of
the readers that time a part of a layer (``attn_proj_ms``,
``attn_rope_ms``, ``attn_out_ms``, ``recompute_ms``,
``moe_dispatch_ms``, ``moe_combine_ms``); no metric of its own. A
``while`` encloses its body's operations, so the union, never a sum.
Nothing to read (no trace, no chip, a program that opens no such scope:
another model's, or a commit from before the scope) gives None."""

# a layer's scope as autodiff writes it, forward (``jvp(blk0_attn)``),
# backward (``transpose(jvp(blk0_attn))``) and, after it, recomputed
# (``.../checkpoint/rematted_computation/...``)
ATTENTION = r"jvp\(\w+_(?:attn|swa)\)"
EXPERTS = r"jvp\(\w+_moe\)"


def read(ctx, scope):
    trace = ctx.get("trace")
    if trace is None or ctx.get("peak") is None:
        return None
    steps = ctx["window"].steps
    if steps <= 0:
        return None
    seconds = trace.scope_seconds(scope)
    return 1e3 * seconds / steps if seconds > 0 else None
