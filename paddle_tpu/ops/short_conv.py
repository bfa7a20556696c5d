"""A depthwise convolution over time of a few taps, and the gated short
convolution built on it (capability-add: the token mixer of LiquidAI's
LFM2 family, ``y = C * conv_k(B * X)`` with ``[B | C | X]`` one
projection's three thirds).

Both are plain ``jnp``: the taps are ``k`` shifted multiply-adds over a
padded copy. What the TPU's compiler makes of the gated form at the
benchmark cell's shape (8,192 tokens of 2,048; ``tests/test_tpu_compile
.py`` pins the counts, PERF.md has the bytes): forward ONE loop fusion
that reads ``B`` and ``X`` and writes ``s = B * X`` in the input's type,
the taps and the ``C`` gate folded into the ``W_out`` product's operand,
so ``[B | C | X]`` is read once and one ``[T, d]`` array written; backward
two loop fusions, the weight's gradient a reduction riding in the ``dy
W_out^T`` product's fusion. A Pallas kernel would read and write no less
forward and could fuse into neither product, so there is none. (With
``s`` kept in float32 the compiler wrote it out at twice the bytes, and
four float32 ``[T, d]`` buffers in the backward pass.) A tap's weight is
``w[j]``, a ``[d]`` vector: ``w`` is ``[k, d]``, channels on the lanes
(the published ``[d, 1, k]`` transposed; ``RowConvLayer``'s ``[k, D]``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def depthwise_time_conv(s, w, *, causal: bool):
    """``out[:, t] = sum_j w[j] * s[:, t + j - lead]`` for ``s [B, T,
    d]`` and ``w [k, d]``, zeros outside the sequence, the sum in
    float32 (the result is float32): ``causal`` looks back (``lead = k -
    1``: tap ``k - 1`` is the current step), else ahead (``lead = 0``:
    the reference framework's lookahead row convolution)."""
    k, T = w.shape[0], s.shape[1]
    lead = k - 1 if causal else 0
    sp = jnp.pad(s, ((0, 0), (lead, k - 1 - lead), (0, 0)))
    out = jnp.zeros(s.shape, jnp.float32)
    for j in range(k):      # k is small and static: the adds fuse
        out = out + sp[:, j:j + T].astype(jnp.float32) \
            * w[j].astype(jnp.float32)
    return out


def gated_short_conv(bcx, w, mask=None):
    """``C * conv_k(B * X)`` for ``bcx [B, T, 3d]`` (thirds ``B | C |
    X``) and ``w [k, d]``: a gate, a causal depthwise convolution of
    ``k`` taps (``c_t = sum_j w[j] s_{t-k+1+j}``, ``s_{<0} = 0``), a
    second gate. ``s`` and the result in ``bcx``'s type, the taps'
    products, their sum and the second gate in float32. ``mask [B, T]``
    (0 on padding) keeps a padded step from feeding any later one."""
    with jax.named_scope("sconv_core"):
        d = w.shape[1]
        s = bcx[..., :d] * bcx[..., 2 * d:]
        if mask is not None:
            s = s * mask[..., None].astype(s.dtype)
        conv = depthwise_time_conv(s, w, causal=True)
        return (bcx[..., d:2 * d].astype(jnp.float32) * conv) \
            .astype(bcx.dtype)
