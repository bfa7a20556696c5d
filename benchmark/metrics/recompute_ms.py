"""Milliseconds a step of device time the backward pass spends running
forward operations a second time: everything under
``rematted_computation`` inside any layer's scope (``jax.checkpoint``
writes that name where a forward is run again: a ``recompute`` layer's
projections, rotary turn and gate or a SwiGLU layer's products, and the
chunked head's logits, which it recomputes by chunk). What the step
pays in time for the memory the checkpoints save; ``step_mfu_pct``
counts none of these FLOPs. (The expert loop's own recomputation of a
chunk in its hand-written backward rule carries no such name.)"""

from benchmark.metrics import scope_ms

SCOPE = r"jvp\(\w+\).*\brematted_computation\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
