"""Milliseconds a step of device time under ``attn_qkv`` inside every
``*_attn`` / ``*_swa`` layer, forward, recomputed and backward: from the
layer's input to q, k and v at the core's layout (the latent layers'
``wqa``, ``wqb``, ``wkva``, ``wkvb`` products with their two norms, the
grouped-query layers' ``wq``, ``wk``, ``wv``; the head splits), before
any rotary turn."""

from benchmark.metrics import scope_ms

SCOPE = scope_ms.ATTENTION + r".*\battn_qkv\b"


def read(ctx):
    return scope_ms.read(ctx, SCOPE)
