"""Milliseconds a step of device time under ``attn_qk_norm`` inside every
``*_attn`` / ``*_swa`` layer, forward, recomputed and backward: the RMS
normalisation of every head of q and of k between the projections and
the rotary turn, its float32 statistics and its two scales' gradients."""

from benchmark.metrics import scope_ms

SCOPE = scope_ms.ATTENTION + r".*\battn_qk_norm\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
