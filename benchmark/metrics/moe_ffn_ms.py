"""Milliseconds a step of device time under the ``*_moe`` layers'
scopes, forward and backward: routing, sort, gather, the grouped
products, combine and the shared expert."""

SCOPE = r"jvp\(\w+_moe\)"


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or ctx.get("peak") is None:
        return None
    steps = ctx["window"].steps
    if steps <= 0:
        return None
    seconds = trace.scope_seconds(SCOPE)
    return 1e3 * seconds / steps if seconds > 0 else None
