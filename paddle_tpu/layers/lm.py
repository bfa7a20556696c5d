"""What a decoder-only language model needs beyond its blocks
(capability-add): ``seq_shift``, a sequence moved along time so that
position i holds what position i + offset held, and ``lm_cost``, the
output head fused with its shifted cross-entropy.

``lm_cost`` (inputs: hidden states [B,T,d], the ids [B,T]): position i's
logits ``h_i W`` are scored against the id at ``i + shift``; the layer
emits each row's mean over the positions that have a target, times
``coeff``, so the trainer's batch mean is the mean over rows. A tied head
(``dsl.lm_cost(tied_to=<embedding>)``) is the embedding's own leaf ``E
[V, d]``, its logits ``h_i E^T``. The logits
are never held whole: rows of hidden states go through the head
``chunk`` at a time under ``jax.checkpoint``, so a chunk's [chunk, V]
float32 logits live only while its loss, or its gradient, is worked out.

``looped_lm_cost`` (inputs: the ``R`` normed states of a stack run ``R``
times over one copy of its weights, then the ids) is the cost of a looped
decoder (Ouro, arXiv:2510.25741, stage I): the one head on every pass's
state, an exit gate ``lam_t = sigmoid(x_t w + b)`` on each, the exit
distribution ``p_t = lam_t prod_{j<t}(1 - lam_j)`` (``p_R`` the rest),
and per position ``sum_t p_t CE_t - beta H(p)``. The ``R`` passes' rows
go through the same chunked head as ``lm_cost``'s, so no ``[R, T, V]``
logits exist. Its ``state["counters"]``: ``loop_exit_step_mean`` (``sum_t
t p_t``, 1..R: pinned at either end, the gate has collapsed and three of
the four heads are dead weight) and ``loop_exit_entropy`` (mean ``H(p)``,
0..ln R). The gate, ``p`` and the entropy run under the inner scope
``loop_gate``, in float32 whatever the states are stored in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)


def shift_left(x, offset: int):
    """``y[:, i] = x[:, i + offset]`` along axis 1, zeros at the end."""
    if offset == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, offset)
    return jnp.pad(x[:, offset:], pad)


@register_layer("seq_shift")
class SeqShiftLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return in_infos[0]

    def apply(self, cfg, params, ins, ctx):
        k = int(cfg.attrs["offset"])
        a = ins[0]
        return Argument(value=shift_left(a.value, k),
                        mask=None if a.mask is None
                        else shift_left(a.mask, k))


def chunked_cross_entropy(h, w, targets, chunk: int, tied: bool = False):
    """``-log softmax(h W)[target]`` for every row of ``h [R, d]``, float32
    [R]; the logits ``chunk`` rows at a time, recomputed in the backward
    pass (the product in ``h``'s type with a float32 sum, the softmax in
    float32). ``w`` is ``[d, V]``, or with ``tied`` an embedding's table
    ``[V, d]``, contracted on ``d`` as it lies."""
    R, d = h.shape
    chunk = min(int(chunk), R)
    pad = (-R) % chunk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))

    @jax.checkpoint
    def one(hc, tc):
        wc = w.astype(hc.dtype)
        logits = (jnp.einsum("rd,vd->rv", hc, wc,
                             preferred_element_type=jnp.float32) if tied
                  else jnp.dot(hc, wc, preferred_element_type=jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]

    ce = lax.map(lambda c: one(*c), (h.reshape(-1, chunk, d),
                                     targets.reshape(-1, chunk)))
    return ce.reshape(-1)[:R]


def shifted_cross_entropy(cfg, w, states, ids_arg):
    """What both costs need: ``(ce [R,B,T], has_target [B,T])``, position
    i of each of the ``R`` states ``[B,T,d]`` scored through the head
    ``w`` against the id at ``i + shift``; the states' rows go through
    the chunked head as one run of ``R*B*T`` rows."""
    ids = ids_arg.value.astype(jnp.int32)
    B, T = ids.shape
    k = int(cfg.attrs.get("shift", 1))
    live = (ids_arg.mask if ids_arg.mask is not None
            else jnp.ones((B, T), jnp.float32))
    rows = [s.reshape(B * T, s.shape[-1]) for s in states]
    ce = chunked_cross_entropy(
        jnp.concatenate(rows), w,
        jnp.tile(shift_left(ids, k).reshape(B * T), len(rows)),
        int(cfg.attrs.get("chunk", 2048)), bool(cfg.attrs.get("tied")))
    # has_target: 0 on a row's last k positions and on padding
    return ce.reshape(len(rows), B, T), shift_left(live, k)


def row_mean(per_position, has_target):
    """Each row's mean over the positions that have a target, [B]."""
    return jnp.sum(per_position * has_target, axis=1) / jnp.maximum(
        jnp.sum(has_target, axis=1), 1.0)


@register_layer("lm_cost")
class LmCostLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return ShapeInfo(size=1)

    def params(self, cfg, in_infos):
        shape = (in_infos[0].size, int(cfg.attrs["vocab_size"]))
        # a tied head is the embedding's table as it lies, [V, d]
        return {"w0": ParamSpec(shape=shape[::-1] if cfg.attrs.get("tied")
                                else shape)}

    def apply(self, cfg, params, ins, ctx):
        ce, has_target = shifted_cross_entropy(
            cfg, params["w0"], [ins[0].value], ins[1])
        row = row_mean(ce[0], has_target)
        return Argument(value=(float(cfg.attrs.get("coeff", 1.0))
                               * row).reshape(-1, 1))


def exit_distribution(gates):
    """``gates [R, ...]``, every pass's exit gate's logit (the last
    pass's plays no part: whatever has not left by then exits there):
    ``(p, log p)``, ``p_t = sigmoid(g_t) prod_{j<t} sigmoid(-g_j)`` for
    ``t < R`` and ``p_R`` what is left, through the logarithms so that
    neither a product nor the entropy's ``log p`` underflows. Float32."""
    g = gates[:-1].astype(jnp.float32)
    stay = jnp.concatenate([                 # log prod_{j<t} (1 - lam_j)
        jnp.zeros((1,) + g.shape[1:], jnp.float32),
        jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)])
    log_p = jnp.concatenate([jax.nn.log_sigmoid(g) + stay[:-1], stay[-1:]])
    return jnp.exp(log_p), log_p


@register_layer("looped_lm_cost")
class LoopedLmCostLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return ShapeInfo(size=1)

    def params(self, cfg, in_infos):
        d = in_infos[0].size
        return {"w0": ParamSpec(shape=(d, int(cfg.attrs["vocab_size"]))),
                # a Linear(d -> 1) on the normed state, float32 like a
                # router: its sigmoid weighs the passes' losses
                "wgate": ParamSpec(shape=(d, 1), compute_f32=True),
                "bgate": ParamSpec(shape=(1,), init="zeros",
                                   compute_f32=True)}

    def apply(self, cfg, params, ins, ctx):
        states = [a.value for a in ins[:-1]]
        R = len(states)
        ce, has_target = shifted_cross_entropy(cfg, params["w0"], states,
                                               ins[-1])
        with jax.named_scope("loop_gate"):
            w = params["wgate"].astype(jnp.float32)[:, 0]
            gates = jnp.stack([
                jnp.einsum("btd,d->bt", x.astype(jnp.float32), w,
                           precision=lax.Precision.HIGHEST)
                + params["bgate"].astype(jnp.float32) for x in states])
            p, log_p = exit_distribution(gates)            # [R,B,T]
            entropy = -jnp.sum(p * log_p, axis=0)          # [B,T]
            steps = jnp.arange(1, R + 1, dtype=jnp.float32)
            exit_step = jnp.einsum("r,rbt->bt", steps, p)
            n = jnp.maximum(jnp.sum(has_target), 1.0)
            counters = {
                "loop_exit_step_mean": jnp.sum(exit_step * has_target) / n,
                "loop_exit_entropy": jnp.sum(entropy * has_target) / n}
        per_position = jnp.sum(p * ce, axis=0) \
            - float(cfg.attrs.get("beta", 0.1)) * entropy
        return Argument(value=row_mean(per_position,
                                       has_target).reshape(-1, 1),
                        state={"counters": counters})
