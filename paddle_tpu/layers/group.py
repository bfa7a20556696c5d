"""Recurrent layer group: user-defined step networks unrolled over time.

TPU-native ``RecurrentGradientMachine`` (``paddle/gserver/gradientmachines/
RecurrentGradientMachine.cpp``): the reference clones a per-timestep
sub-network ("frame", ``resizeOrCreateFrames`` at ``:294-346``) with shared
parameters and walks frames sequentially; here the step sub-network is
traced ONCE and driven by ``lax.scan``, so XLA sees a single fused loop
body and the per-step matmuls stay on the MXU. Memories (``memory()`` in
the config DSL) become scan carries; padded timesteps are mask-guarded so
ragged batches keep reference semantics without dynamic shapes.

Sub-network parameters are hoisted into the global parameter table under
their sub-layer names (``ParamSpec.absolute_name``) — one set of weights
shared by every timestep, exactly like the reference's frame sharing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.argument import Argument, check_dead
from paddle_tpu.core.network import Network
from paddle_tpu.core.registry import (LayerImpl, ShapeInfo, register_layer)


def _group_subnet(cfg) -> Network:
    """Build (once) the step sub-network covering the group outputs and
    every memory link layer."""
    if "_subnet" not in cfg.attrs:
        targets = list(cfg.attrs["outputs"])
        for mem in cfg.attrs["memories"]:
            if mem["link"] not in targets:
                targets.append(mem["link"])
        cfg.attrs["_subnet"] = Network(cfg.attrs["sub_model"],
                                       outputs=targets)
    return cfg.attrs["_subnet"]


@register_layer("recurrent_layer_group")
class RecurrentLayerGroup(LayerImpl):
    """Training/eval path of the recurrent group (the generating path lives
    in ``paddle_tpu/core/generation.py``)."""

    def infer(self, cfg, in_infos):
        net = _group_subnet(cfg)
        main = cfg.attrs["outputs"][0]
        info = net.shape_infos[main]
        return dataclasses.replace(info, is_sequence=True)

    def params(self, cfg, in_infos):
        net = _group_subnet(cfg)
        return {f"sub:{p}": dataclasses.replace(spec, absolute_name=p)
                for p, spec in net.param_specs.items()}

    def apply(self, cfg, params, ins, ctx):
        net = _group_subnet(cfg)
        sub_params = {k[len("sub:"):]: v for k, v in params.items()}
        ins_meta: List[Dict[str, Any]] = cfg.attrs["ins"]
        memories: List[Dict[str, Any]] = cfg.attrs["memories"]
        reverse = bool(cfg.attrs.get("reverse", False))

        xs: Dict[str, jnp.ndarray] = {}
        flat_masks: Dict[str, jnp.ndarray] = {}  # [B, T] per flat in-link
        sub_xs: Dict[str, jnp.ndarray] = {}   # nested: [S, B, T_sub, D]
        sub_masks: Dict[str, jnp.ndarray] = {}  # [S, B, T_sub]
        static_feed: Dict[str, Argument] = {}
        boot: Dict[str, jnp.ndarray] = {}
        mask = None
        for a, m in zip(ins, ins_meta):
            kind = m["kind"]
            if kind == "auto":
                # wire-imported groups (compat/proto_import.py) cannot
                # recover the link kind from the proto; resolve it from
                # the Argument the way the reference engine inspects
                # hasSubseq at runtime
                if a.mask is not None and a.mask.ndim == 3:
                    kind = "subseq"
                elif a.mask is None:
                    # maskless [B, T, D] still walks as a full-length
                    # sequence; flat maskless values broadcast (the
                    # reference's non-sequence in-link semantics). KNOWN
                    # AMBIGUITY: a maskless [B, T] could also be an
                    # equal-length id sequence — the reference has offsets
                    # to disambiguate, the padded layout doesn't. Feed id
                    # sequences WITH masks (every feeder does) to step
                    # them.
                    kind = "seq" if a.value.ndim >= 3 else "static"
                else:
                    kind = "seq"
                m = dict(m, kind=kind)
            if m["kind"] == "seq":
                xs[m["boundary"]] = jnp.swapaxes(a.value, 0, 1)
                if a.mask is not None:
                    flat_masks[m["boundary"]] = a.mask
                if mask is None and a.mask is not None:
                    mask = a.mask
            elif m["kind"] == "subseq":
                # nested input [B, S, T_sub, D] with mask [B, S, T_sub]:
                # the outer scan walks S; each step feeds one sub-sequence
                if a.value.ndim < 3 or a.mask is None or a.mask.ndim != 3:
                    raise ValueError(
                        f"nested group {cfg.name!r} needs a [B, S, T, D] "
                        "value with a [B, S, T] mask (2-level padded "
                        "layout)")
                sub_xs[m["boundary"]] = jnp.swapaxes(a.value, 0, 1)
                sub_masks[m["boundary"]] = jnp.swapaxes(a.mask, 0, 1)
                is_target = m["boundary"] == cfg.attrs.get(
                    "target_boundary", ins_meta[0]["boundary"])
                if mask is None or is_target:
                    # an outer step is live if its sub-sequence has
                    # tokens; the target in-link wins the outer mask
                    mask = (jnp.sum(a.mask, axis=-1) > 0).astype(
                        jnp.float32)
            elif m["kind"] == "static":
                static_feed[m["boundary"]] = a
            elif m["kind"] == "boot":
                boot[m["boundary"]] = a.value
        if not xs and not sub_xs:
            raise ValueError(
                f"recurrent group {cfg.name!r} has no sequence input; "
                "use beam_search/generation for input-free unrolling")
        if sub_xs and xs:
            # mixed levels: the outer steps over SUB-SEQUENCES, so every
            # flat sequence input must align to the sub count; the
            # feeder may have padded it longer (pad_multiple bucketing)
            S = next(iter(sub_xs.values())).shape[0]
            # outer-step liveness (set by the target sub in-link above)
            # tells whether padded flat steps would feed live outer steps
            outer_live = mask if (mask is not None
                                  and mask.shape[1] == S) else None

            def _fit(k, v):
                if v.shape[0] > S:
                    fm = flat_masks.get(k)
                    if fm is None:
                        # maskless = every position live by definition, so
                        # any trim drops real data: fail closed, statically
                        raise ValueError(
                            f"recurrent group {cfg.name!r}: maskless flat "
                            f"in-link {k!r} (len {v.shape[0]}) cannot "
                            f"align to {S} sub-sequences")
                    check_dead(
                        jnp.sum(fm[:, S:]),
                        f"recurrent group {cfg.name!r}: flat in-link "
                        f"{k!r} (len {v.shape[0]}) vs {S} "
                        "sub-sequences")
                    return v[:S]
                if v.shape[0] < S:
                    if outer_live is None:
                        # no outer mask → the group later defaults it to
                        # all-ones, so padded steps WOULD be live
                        raise ValueError(
                            f"recurrent group {cfg.name!r}: flat in-link "
                            f"{k!r} (len {v.shape[0]}) shorter than the "
                            f"{S} sub-sequences with no outer mask to "
                            "prove the tail dead")
                    check_dead(
                        jnp.sum(outer_live[:, v.shape[0]:]),
                        f"recurrent group {cfg.name!r}: flat in-link "
                        f"{k!r} (len {v.shape[0]}) shorter than the "
                        f"{S} live sub-sequences")
                    pad = [(0, S - v.shape[0])] + [(0, 0)] * (v.ndim - 1)
                    return jnp.pad(v, pad)
                return v

            xs = {k: _fit(k, v) for k, v in xs.items()}
            if mask is not None and mask.shape[1] != S:
                mask = (mask[:, :S] if mask.shape[1] > S
                        else jnp.pad(mask,
                                     ((0, 0), (0, S - mask.shape[1]))))
        lead = next(iter(sub_xs.values())) if sub_xs \
            else next(iter(xs.values()))
        T = lead.shape[0]
        B = lead.shape[1]
        if mask is None:
            mask = jnp.ones((B, T), jnp.float32)
        mask_tb = jnp.swapaxes(mask, 0, 1)

        # cross-batch carry (--prev_batch_state): resume every memory from
        # the previous batch's final carry instead of boot/zeros
        carried = None if reverse else ctx.carried.get(cfg.name)
        carry0: Dict[str, jnp.ndarray] = {}
        for mem in memories:
            bname = mem["boundary"]
            if carried is not None and bname in carried:
                carry0[bname] = carried[bname]
            elif bname in boot:
                carry0[bname] = boot[bname]
            else:
                size = net.shape_infos[bname].size
                carry0[bname] = jnp.full((B, size), mem.get("init", 0.0),
                                         jnp.float32)

        out_names = cfg.attrs["outputs"]
        scan_in: Dict[str, Any] = {"x": xs, "m": mask_tb,
                                   "xsub": sub_xs, "msub": sub_masks}
        if ctx.rng is not None:
            scan_in["rng"] = jax.random.split(
                ctx.layer_rng(cfg.name + "/group"), T)
        train = ctx.train

        def body(carry, inp):
            feed = dict(static_feed)
            for k, v in inp["x"].items():
                feed[k] = Argument(value=v)
            for k, v in inp["xsub"].items():
                feed[k] = Argument(value=v, mask=inp["msub"][k])
            for mem in memories:
                feed[mem["boundary"]] = Argument(value=carry[mem["boundary"]])
            outs = net.apply(sub_params, feed, train=train,
                             rng=inp.get("rng"))
            m_t = inp["m"]

            def guard(new, old):
                m = m_t.reshape(m_t.shape + (1,) * (new.ndim - 1))
                return jnp.where(m > 0, new, old)

            new_carry = {
                mem["boundary"]: guard(outs[mem["link"]].value,
                                       carry[mem["boundary"]])
                for mem in memories}
            ys = {}
            for o in out_names:
                y = outs[o].value
                m = m_t.reshape(m_t.shape + (1,) * (y.ndim - 1))
                ys[o] = y * m.astype(y.dtype)
            return new_carry, ys

        carry, ys = lax.scan(body, carry0, scan_in, reverse=reverse)
        main = out_names[0]
        extras = {o: jnp.swapaxes(ys[o], 0, 1) for o in out_names[1:]}
        y_main = jnp.swapaxes(ys[main], 0, 1)
        # the output follows the TARGET sub-link's sub-length, not the
        # first one's (they differ when multiple subseq in-links carry
        # different sub-paddings)
        target = cfg.attrs.get("target_boundary")
        sm_ref = (sub_masks.get(target, next(iter(sub_masks.values())))
                  if sub_masks else None)
        sub_t = sm_ref.shape[2] if sm_ref is not None else None
        if sub_xs and (net.shape_infos[main].is_sequence
                       or (y_main.ndim >= 4
                           and y_main.shape[2] == sub_t)):
            # flatten when the per-step output carries a TIME axis —
            # either statically known (is_sequence) or, for runtime-
            # resolved ("auto") sub-sequence in-links, recognized by the
            # output's third axis matching the sub-sequence length
            # the outer step returned a whole sequence per sub-sequence
            # (the reference's nested out_link): concatenate sub-sequences
            # back into one flat sequence, like the reference does when a
            # nested group's output feeds flat-level consumers
            Bq, Sq, Tq = y_main.shape[0], y_main.shape[1], y_main.shape[2]
            flat = y_main.reshape(Bq, Sq * Tq, *y_main.shape[3:])
            sm = jnp.swapaxes(sm_ref, 0, 1)
            # keep the un-flattened 2-level view alongside: TO_SEQUENCE
            # aggregations (seqlastins/pooling with agg_level=seq) need
            # the sub-sequence boundaries the flat layout erases; extra
            # out-links flatten the same way (group_output re-attaches
            # the nested view)
            extras = {
                o: (v.reshape(Bq, Sq * Tq, *v.shape[3:])
                    if v.ndim >= 3 and v.shape[1] == Sq
                    and v.shape[2] == Tq else v)
                for o, v in extras.items()}
            # the nested view rides in state as an Argument so mask-aware
            # machinery (e.g. the trainer's bf16 cast) exempts its mask
            # structurally, by type — not by knowing this layer's keys
            return Argument(value=flat, mask=sm.reshape(Bq, Sq * Tq),
                            state={"group_outputs": extras, "final": carry,
                                   "nested": Argument(value=y_main, mask=sm),
                                   "nested_tq": Tq})
        return Argument(value=y_main, mask=mask,
                        state={"group_outputs": extras, "final": carry})


@register_layer("group_output")
class GroupOutput(LayerImpl):
    """Exposes a non-main output of a recurrent group (the reference allows
    multiple out_links on a recurrent_group)."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=cfg.size, is_sequence=True)

    def apply(self, cfg, params, ins, ctx):
        a = ins[0]
        v = a.state["group_outputs"][cfg.attrs["sub_name"]]
        state = None
        mask = a.mask
        tq = (a.state or {}).get("nested_tq") \
            if isinstance(a.state, dict) else None
        if tq and mask is not None and v.ndim == 3 \
                and v.shape[1] == mask.shape[1] and v.shape[1] % tq == 0:
            # the extra was flattened [B, S*Tq, D] like the main output:
            # re-attach the 2-level view for TO_SEQUENCE consumers
            B, ST = v.shape[0], v.shape[1]
            state = {"nested": Argument(
                        value=v.reshape(B, ST // tq, tq, v.shape[-1]),
                        mask=mask.reshape(B, ST // tq, tq)),
                     "nested_tq": tq}
        elif tq and mask is not None and v.ndim >= 2 \
                and v.shape[1] * tq == mask.shape[1]:
            # a PER-SUB-SEQUENCE extra ([B, S, ...], e.g. last_seq inside
            # the step): the flat [B, S*Tq] mask doesn't apply — its
            # outer-level mask is "sub-sequence has tokens"
            sm = a.state["nested"].mask if "nested" in a.state else \
                mask.reshape(v.shape[0], v.shape[1], tq)
            mask = (jnp.sum(sm, axis=-1) > 0).astype(jnp.float32)
        return Argument(value=v, mask=mask, state=state)


@register_layer("beam_search_group")
class BeamSearchGroup(LayerImpl):
    """Config-time node for a generating recurrent group. Not executable by
    the forward pass — drive it with
    ``paddle_tpu.core.generation.SequenceGenerator`` (the reference
    likewise switches RecurrentGradientMachine into generating mode only
    under ``--job=test``/Inference)."""

    def infer(self, cfg, in_infos):
        _group_subnet(cfg)  # validate the step net early
        return ShapeInfo(size=1, is_sequence=True)

    def params(self, cfg, in_infos):
        net = _group_subnet(cfg)
        return {f"sub:{p}": dataclasses.replace(spec, absolute_name=p)
                for p, spec in net.param_specs.items()}

    def apply(self, cfg, params, ins, ctx):
        raise RuntimeError(
            f"beam_search group {cfg.name!r} cannot run in a training "
            "forward pass; use SequenceGenerator.generate")
