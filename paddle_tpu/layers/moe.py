"""The feed-forward halves of a decoder block as layer types
(TPU-native capability-add): ``swiglu``, the dense FFN, and ``moe``, the
expert layer (``parallel/moe.py`` has the mathematics).

``dsl.moe(input, expert_hidden=..., num_experts=..., top_k=...,
experts_held=..., expert_offset=...)`` registers a layer whose parameters
live in the ordinary parameter table: the router ``wr`` over all
``num_experts`` (kept float32 under a lower ``compute_dtype``) with its
selection bias ``br`` (static: no gradient trains it), the held experts
stacked expert-major (``wg wu`` [held, d, h], ``wd`` [held, h, d]) and the
shared expert (``sg su sd``). The layer is told which experts it holds:
it routes over all of them, computes the chosen held experts' part of the
sum and leaves the rest out; no token routed to a held expert is ever
dropped. Its output's ``state["counters"]`` names what the step counted
here (the trainer hands every layer's counters back with the cost, into
``StepBreakdown.totals``): ``moe_rows_max`` and ``moe_rows_mean``, the
rows the fullest held expert got and the mean held expert's,
``moe_experts_active``, how many held experts got any row, and
``moe_turns``, the chunks of sorted rows the expert loop took (1 for the
usual batch: more says the overflow path ran).

``dsl.moe(..., score="softmax")`` routes by a softmax over all the
experts and hands the router's statistics on in its output's
``state["balance"]`` (``parallel/moe.py:balance_sums``), which one
``dsl.moe_balance_cost(layers, coeff=...)`` reads from every expert
layer of the model together: ``coeff * E * sum_e c_e P_e`` over the
(layer, token) rows of the step, ``c_e`` the share of the choices that
went to expert ``e`` and ``P_e`` its mean probability (Qwen3-MoE's
``load_balancing_loss_func``). Its counter ``moe_balance`` is that term
over ``k``: 1 at a balanced router, ``E / k`` at one that sends every
token to one expert. Its operations, and the statistics' in the expert
layers, lie under the inner scope ``moe_balance``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)
from paddle_tpu.parallel.moe import moe_ffn, swiglu


def _same_shape(in_infos):
    return ShapeInfo(size=in_infos[0].size,
                     is_sequence=in_infos[0].is_sequence)


@register_layer("swiglu")
class SwigluLayer(LayerImpl):
    """``(silu(x W_g) * (x W_u)) W_d``, no bias; output size = input's."""

    def infer(self, cfg, in_infos):
        return _same_shape(in_infos)

    def params(self, cfg, in_infos) -> Dict[str, ParamSpec]:
        d, h = in_infos[0].size, int(cfg.attrs["hidden"])
        return {"wg": ParamSpec(shape=(d, h)), "wu": ParamSpec(shape=(d, h)),
                "wd": ParamSpec(shape=(h, d))}

    def apply(self, cfg, params, ins, ctx):
        y = swiglu(ins[0].value, params["wg"], params["wu"], params["wd"])
        return Argument(value=y, mask=ins[0].mask)


@register_layer("moe")
class MoELayer(LayerImpl):
    def infer(self, cfg, in_infos):
        return _same_shape(in_infos)

    def params(self, cfg, in_infos) -> Dict[str, ParamSpec]:
        d = in_infos[0].size
        e = int(cfg.attrs["num_experts"])
        held = int(cfg.attrs.get("experts_held") or e)
        h = int(cfg.attrs["expert_hidden"])
        hs = int(cfg.attrs.get("shared_hidden") or 0)
        specs = {
            "wr": ParamSpec(shape=(d, e), compute_f32=True),
            "br": ParamSpec(shape=(e,), init="zeros", is_static=True,
                            compute_f32=True),
            "wg": ParamSpec(shape=(held, d, h)),
            "wu": ParamSpec(shape=(held, d, h)),
            "wd": ParamSpec(shape=(held, h, d)),
        }
        if hs:
            specs.update(sg=ParamSpec(shape=(d, hs)),
                         su=ParamSpec(shape=(d, hs)),
                         sd=ParamSpec(shape=(hs, d)))
        return specs

    def apply(self, cfg, params, ins, ctx):
        a = ins[0]
        shape = a.value.shape
        # the layer's own operations (the flattening, the counters) lie
        # under the inner scopes too: parallel/moe.py opens the rest
        with jax.named_scope("moe_dispatch"):
            x = a.value.reshape(-1, shape[-1])
            # padding is routed nowhere: it takes no expert's rows
            live = a.mask.reshape(-1) if a.mask is not None else None
        y, rows, turns, balance = moe_ffn(
            params, x, top_k=int(cfg.attrs["top_k"]),
            scale=float(cfg.attrs.get("routed_scaling_factor", 1.0)),
            offset=int(cfg.attrs.get("expert_offset") or 0), live=live,
            norm_eps=float(cfg.attrs.get("norm_eps") or 0.0),
            score=cfg.attrs.get("score", "sigmoid"))
        with jax.named_scope("moe_combine"):
            y = y.reshape(shape)
        with jax.named_scope("moe_dispatch"):
            rows = rows.astype(jnp.float32)     # [held]
            counters = {"moe_rows_max": rows.max(),
                        "moe_rows_mean": rows.mean(),
                        "moe_experts_active": (rows > 0).sum(),
                        "moe_turns": turns.astype(jnp.float32)}
        state = {"counters": counters}
        if balance is not None:     # dead code unless a cost layer reads it
            state["balance"] = balance
        return Argument(value=y, mask=a.mask, state=state)


@register_layer("moe_balance_cost")
class MoeBalanceCostLayer(LayerImpl):
    """The load-balancing term over every expert layer given as input
    (each routed by a softmax): one value for the step, on every row
    of the batch so that the trainer's mean over rows is that value."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=1)

    def apply(self, cfg, params, ins, ctx):
        with jax.named_scope("moe_balance"):
            sums = [a.state["balance"] for a in ins]
            n = sum(s["tokens"] for s in sums)
            share = sum(s["slots"] for s in sums) / n               # c_e
            prob = sum(s["probs"] for s in sums) / n                # P_e
            term = prob.shape[0] * jnp.sum(share * prob)
            value = jnp.full((ins[0].value.shape[0], 1),
                             float(cfg.attrs["coeff"]) * term)
            counters = {"moe_balance": term / jnp.sum(share)}
        return Argument(value=value, state={"counters": counters})
