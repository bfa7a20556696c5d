"""Milliseconds a step of device time under ``moe_combine`` inside
every ``*_moe`` layer, forward and backward: the gates gathered by
pair, the weighting of the experts' rows and the float32 scatter-add
back to tokens, the sum's buffer and its cast."""

from benchmark.metrics import scope_ms

SCOPE = scope_ms.EXPERTS + r".*\bmoe_combine\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
