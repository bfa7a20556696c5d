"""Milliseconds a step of device time under ``moe_balance``, forward and
backward: the router's statistics that every expert layer built with a
balancing term gathers (the softmax's probabilities and the slots summed
over the tokens), and the term itself, formed once from all the layers'
statistics, with its gradient back to every router."""

from benchmark.metrics import scope_ms

SCOPE = r"\bmoe_balance\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
