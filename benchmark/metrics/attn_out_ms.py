"""Milliseconds a step of device time under ``attn_out`` inside every
``*_attn`` / ``*_swa`` layer, forward, recomputed and backward: the
per-head gate's product, sigmoid and multiply (where the layer has
one), the head merge, the ``wo`` product, the mask."""

from benchmark.metrics import scope_ms

SCOPE = scope_ms.ATTENTION + r".*\battn_out\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
