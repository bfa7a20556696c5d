"""Milliseconds a step of device time under ``moe_route`` or
``moe_dispatch`` inside every ``*_moe`` layer, forward and backward:
the router's product, sigmoid and top-k; the plan (which pairs name a
held expert, the rows each gets), the sort by expert, a chunk's gather
of rows into the dispatch buffer and, in the backward rule, the
scatter back, the sums over the chunks and the casts."""

from benchmark.metrics import scope_ms

SCOPE = scope_ms.EXPERTS + r".*\bmoe_(?:route|dispatch)\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
