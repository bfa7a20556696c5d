"""Milliseconds a step of device time under ``attn_rope`` inside every
``*_attn`` / ``*_swa`` layer, forward, recomputed and backward: the
rotary turns in float32 (interleaved pairs, or halves with YaRN's
factor), the concatenations that assemble q and k, the latent layers'
one rotary key broadcast over the heads."""

from benchmark.metrics import scope_ms

SCOPE = scope_ms.ATTENTION + r".*\battn_rope\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
