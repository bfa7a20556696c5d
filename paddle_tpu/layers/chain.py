"""Linear-chain CRF and CTC: sequential dynamic programs on TPU.

TPU-native equivalents of ``paddle/gserver/layers/LinearChainCRF.cpp`` /
``CRFLayer.cpp`` / ``CRFDecodingLayer.cpp`` and ``LinearChainCTC.cpp`` /
``CTCLayer.cpp`` (+ ``WarpCTCLayer.cpp``). The reference hand-writes
forward-backward recursions and their gradients per sequence on the host;
here each DP runs whole-batch on device — the likelihood recursions
dispatch to fused Pallas kernels with analytic beta-recursion VJPs on TPU
(``ops/crf.py``, ``ops/ctc.py``) and to ``lax.scan`` + autodiff elsewhere;
Viterbi decoding stays a scan (argmax has no gradient to fuse).

Parameter layout matches the reference CRF exactly
(``LinearChainCRF.cpp:28-45``): one (C+2, C) matrix whose row 0 is the
start potential a, row 1 the end potential b, rows 2.. the transition
matrix w[prev, next].

CTC follows ``LinearChainCTC.cpp``: blank id = C-1 (the layer's last
class), extended label sequence of length 2L+1 with interleaved blanks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)

# --------------------------------------------------------------------- CRF
def crf_log_likelihood(x, labels, mask, w):
    """Per-sequence log P(labels | x) for a linear-chain CRF.

    x: [B, T, C] emission scores; labels: [B, T] int; mask: [B, T];
    w: [(C+2), C] packed (start, end, transitions).
    Returns [B] log-likelihoods.
    """
    B, T, C = x.shape
    a, b, trans = w[0], w[1], w[2:]
    labels = labels.astype(jnp.int32)

    # ---- numerator: score of the gold path
    emit = jnp.take_along_axis(x, labels[:, :, None], axis=2)[:, :, 0]
    emit = jnp.sum(emit * mask, axis=1)
    prev_l, next_l = labels[:, :-1], labels[:, 1:]
    pair_m = mask[:, 1:] * mask[:, :-1]
    tr = trans[prev_l, next_l]  # [B, T-1]
    tr = jnp.sum(tr * pair_m, axis=1)
    start = a[labels[:, 0]]
    lengths = jnp.sum(mask, axis=1).astype(jnp.int32)
    last = jnp.take_along_axis(
        labels, jnp.maximum(lengths - 1, 0)[:, None], axis=1)[:, 0]
    end = b[last]
    gold = emit + tr + start + end

    # ---- denominator: forward algorithm (alpha frozen on padded steps).
    # Dispatches to the Pallas exp-space-matmul kernel on TPU
    # (ops/crf.py), lax.scan elsewhere.
    from paddle_tpu.ops.crf import crf_log_z
    log_z = crf_log_z(x, mask.astype(x.dtype), trans, a, b)
    return gold - log_z


def crf_decode(x, mask, w):
    """Viterbi decoding. Returns ([B, T] best path ids, [B] path scores)."""
    B, T, C = x.shape
    a, b, trans = w[0], w[1], w[2:]
    alpha0 = a[None, :] + x[:, 0]

    def fwd(alpha, inp):
        x_t, m_t = inp
        scores = alpha[:, :, None] + trans[None]  # [B, prev, next]
        best_prev = jnp.argmax(scores, axis=1)    # [B, C]
        nxt = jnp.max(scores, axis=1) + x_t
        nxt = jnp.where(m_t[:, None] > 0, nxt, alpha)
        # on padded steps the pointer is identity (state j came from j)
        ident = jnp.broadcast_to(jnp.arange(C)[None, :], (B, C))
        ptr = jnp.where(m_t[:, None] > 0, best_prev, ident)
        return nxt, ptr

    xs = jnp.swapaxes(x, 0, 1)[1:]
    ms = jnp.swapaxes(mask, 0, 1)[1:]
    alpha, ptrs = lax.scan(fwd, alpha0, (xs, ms))  # ptrs: [T-1, B, C]
    final = alpha + b[None, :]
    last_state = jnp.argmax(final, axis=1)  # [B]
    score = jnp.max(final, axis=1)

    def back(state, ptr_t):
        prev = jnp.take_along_axis(ptr_t, state[:, None], axis=1)[:, 0]
        return prev, state

    first_state, rev_path = lax.scan(back, last_state, ptrs, reverse=True)
    path = jnp.concatenate([first_state[None], rev_path], axis=0)  # [T, B]
    return jnp.swapaxes(path, 0, 1), score


@register_layer("crf")
class CRFLayer(LayerImpl):
    """``CRFLayer.cpp``: cost layer; inputs = (emission, label[, weight]).
    Output: per-sequence negative log-likelihood [B, 1]."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=1)

    def params(self, cfg, in_infos):
        C = in_infos[0].size
        # reference init: plain create_input_parameter -> smart normal
        return {"w0": ParamSpec(shape=(C + 2, C))}

    def apply(self, cfg, params, ins, ctx):
        x, label = ins[0], ins[1]
        mask = x.mask if x.mask is not None else \
            jnp.ones(x.value.shape[:2], x.value.dtype)
        ll = crf_log_likelihood(x.value, label.value, mask, params["w0"])
        cost = -ll
        if len(ins) > 2:
            cost = cost * ins[2].value.reshape(cost.shape)
        return Argument(value=cost[:, None])


@register_layer("crf_decoding")
class CRFDecodingLayer(LayerImpl):
    """``CRFDecodingLayer.cpp``: Viterbi decode. Without a label input the
    output is the decoded tag sequence; with one, a per-sequence 0/1 error
    indicator (1 = decoded != gold anywhere), as in the reference."""

    def infer(self, cfg, in_infos):
        if len(in_infos) > 1:
            return ShapeInfo(size=1)
        return ShapeInfo(size=1, is_sequence=True)

    def params(self, cfg, in_infos):
        C = in_infos[0].size
        # reference init: plain create_input_parameter -> smart normal
        return {"w0": ParamSpec(shape=(C + 2, C))}

    def apply(self, cfg, params, ins, ctx):
        x = ins[0]
        mask = x.mask if x.mask is not None else \
            jnp.ones(x.value.shape[:2], x.value.dtype)
        path, _ = crf_decode(x.value, mask, params["w0"])
        if len(ins) > 1:
            gold = ins[1].value.astype(path.dtype)
            wrong = jnp.any((path != gold) & (mask > 0), axis=1)
            # the reference layer carries BOTH: output_.ids = the decoded
            # path (what ChunkEvaluator reads) and value = the error
            # indicator (what sum_evaluator reads). The ids view rides in
            # state for evaluators that want ids.
            return Argument(value=wrong.astype(jnp.float32)[:, None],
                            state={"ids": path.astype(jnp.int32),
                                   "ids_mask": mask})
        return Argument(value=path.astype(jnp.int32)[:, :, None], mask=mask)


# --------------------------------------------------------------------- CTC
def ctc_loss(log_probs, labels, in_mask, label_mask, blank):
    """Per-sequence CTC negative log-likelihood.

    log_probs: [B, T, C] log softmax outputs; labels: [B, L] ints (no
    blanks); in_mask: [B, T]; label_mask: [B, L]; blank: scalar id.
    Standard extended-sequence alpha recursion (LinearChainCTC.cpp), log
    space, scanned over T.
    """
    B, T, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    labels = labels.astype(jnp.int32)
    # extended sequence: [blank, l1, blank, l2, ..., blank]
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels)
    lab_lens = jnp.sum(label_mask, axis=1).astype(jnp.int32)
    ext_lens = 2 * lab_lens + 1
    s_idx = jnp.arange(S)[None, :]
    valid_s = s_idx < ext_lens[:, None]

    # can we skip from s-2 to s? only if ext[s] != blank and ext[s]!=ext[s-2]
    ext_m2 = jnp.concatenate(
        [jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
    can_skip = (ext != blank) & (ext != ext_m2)

    # gather emissions once for every (t, ext-state); the gather's
    # transpose (scatter-add back into [B,T,C]) stays in XLA autodiff.
    # The DP itself dispatches to the Pallas kernel on TPU (ops/ctc.py),
    # lax.scan elsewhere. Empty transcripts (ext_lens == 1) count only
    # the blank-path entry (the ext_lens >= 2 guard lives in _final_ll).
    from paddle_tpu.ops.ctc import ctc_ll
    emit = jnp.take_along_axis(
        log_probs, jnp.broadcast_to(ext[:, None, :], (B, T, S)), axis=2)
    ll = ctc_ll(emit, in_mask.astype(log_probs.dtype),
                valid_s.astype(log_probs.dtype),
                can_skip.astype(log_probs.dtype), ext_lens)
    return -ll


@register_layer("ctc", "warp_ctc")
class CTCLayer(LayerImpl):
    """``CTCLayer.cpp``: inputs = (pre-softmax scores [B,T,C], label seq).
    size = num_classes + 1, blank = size - 1 (LinearChainCTC.cpp). With
    ``norm_by_times`` the cost divides by sequence length. ``warp_ctc``
    (WarpCTCLayer.cpp — the same math behind a GPU library) is an alias."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=1)

    def apply(self, cfg, params, ins, ctx):
        x, label = ins[0], ins[1]
        in_mask = x.mask if x.mask is not None else \
            jnp.ones(x.value.shape[:2], x.value.dtype)
        label_mask = label.mask if label.mask is not None else \
            jnp.ones(label.value.shape[:2], x.value.dtype)
        lab = label.value
        if lab.ndim == 3:
            lab = lab[:, :, 0]
        log_probs = jax.nn.log_softmax(x.value, axis=-1)
        blank = cfg.attrs.get("blank", x.value.shape[-1] - 1)
        cost = ctc_loss(log_probs, lab, in_mask, label_mask, blank)
        if cfg.attrs.get("norm_by_times", False):
            cost = cost / jnp.maximum(jnp.sum(in_mask, axis=1), 1.0)
        return Argument(value=cost[:, None])
