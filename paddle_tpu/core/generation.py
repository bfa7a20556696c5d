"""Sequence generation: greedy and beam search over a recurrent step net.

TPU-native ``RecurrentGradientMachine::generateSequence``
(``RecurrentGradientMachine.cpp:964``): greedy ``oneWaySearch`` (``:1042``)
is the beam_size=1 case of ``beamSearch`` (``:1393``). Where the reference
expands/prunes beams with host-side std::vector bookkeeping per step, here
the whole search is jitted with static beam and length dims: beams live as
a [B, K] axis, finished beams are frozen by masking (-inf over non-EOS
continuations), and parent-beam reordering is a gather.

**Decode cost is proportional to actual output length.** The reference
stops the moment every beam finishes; a single ``lax.scan`` over the full
static ``max_length`` cannot. The default search therefore runs a
``lax.while_loop`` over fixed-size scan *chunks* (``decode_chunk`` steps
each, one compiled chunk body reused for every chunk) and exits as soon as
``finished.all()`` — provably byte-identical to the full scan, because a
step in which every beam is already finished only appends the forced
zero-cost EOS continuation: tokens stay EOS (the buffer is EOS-initialized
and gathers are identity at that point), scores carry unchanged through
``top_k`` (hooks are exempted from the forced continuation), and lengths
read the first EOS. ``full_scan=True`` restores the single length-L scan
(the escape hatch and the A/B baseline). Greedy (K=1) decoding skips the
parent-beam gathers entirely — the parent index is always 0.

The user beam-control hooks (``RecurrentGradientMachine.h:92-145``)
survive as callables traced into the step:

- ``candidate_adjust`` — ``beamSearchCandidateAdjust``: arbitrary
  adjustment of the expanded candidate log-probs before selection.
- ``drop_callback`` — ``DropCallback``: per-node drop decision over the
  expanded candidates (True = prune that (beam, token) node).
- ``norm_or_drop`` — ``NormOrDropNode``: rescoring (e.g. length
  normalization) or dropping (-inf) of a candidate at the moment it
  finishes (picks EOS).
- ``stop_beam_search`` — the ``stopBeamSearch`` flag: a predicate that
  freezes the whole search early (all beams behave as finished from the
  step it first returns True).

Hooks can be pinned in the config (``dsl.beam_search(...,
drop_callback=...)``) — the attrs are the defaults every ``generate``
call (and the serving generation endpoint) honors — or passed per call.
Hook time arguments (``norm_or_drop``'s ``length``,
``stop_beam_search``'s ``t``) are traced scalars in the dedicated search
and per-lane ``[B, 1]`` / ``[B]`` arrays inside a :class:`DecodeSession`
— write hooks with broadcasting ops (``jnp.where``, arithmetic), not
Python branches, and they work identically in both.

Compile-key policy (``docs/generation.md``): one executable per
``(beam_size, max_length, decode_chunk-or-full_scan, hooks)`` key, the
cache LRU-bounded at ``_JIT_CACHE_CAP`` — per-call hook *lambdas* mint
fresh keys every call and would otherwise leak compiled executables; pin
hooks at module level (or in the config) to reuse the cache.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.core.argument import Argument
from paddle_tpu.utils.log import get_logger

logger = get_logger("generation")

#: default number of decoder steps per compiled chunk of the early-exit
#: search; the exit condition is checked every chunk boundary, so a
#: request that finishes at step f pays ceil((f+1)/chunk)*chunk steps
#: instead of max_length.
DEFAULT_DECODE_CHUNK = 8

NEG = jnp.float32(-1e9)

_HOOK_NAMES = ("candidate_adjust", "drop_callback", "norm_or_drop",
               "stop_beam_search")


def _flatten_beams(x):
    return x.reshape((-1,) + x.shape[2:])


def _unflatten_beams(x, B, K):
    return x.reshape((B, K) + x.shape[1:])


class SequenceGenerator:
    """Drives a generation-mode recurrent group (``beam_search`` in the
    DSL). Mirrors the SWIG ``SequenceGenerator`` (api/PaddleAPI.h) surface:
    construct from the model + generating layer, call ``generate``."""

    #: LRU bound on compiled search variants. Hooks are part of the key,
    #: so per-call closures/lambdas would grow the cache without limit —
    #: the bound converts that leak into eviction + one warning.
    _JIT_CACHE_CAP = 16

    def __init__(self, model, gen_layer: str):
        from paddle_tpu.layers.group import _group_subnet

        self.cfg = model.layers[gen_layer]
        if self.cfg.type != "beam_search_group":
            raise ValueError(f"{gen_layer!r} is not a beam_search group")
        self.net = _group_subnet(self.cfg)
        self.gen = self.cfg.attrs["gen"]  # GeneratedInput spec dict
        self._jitted: "OrderedDict[Any, Callable]" = OrderedDict()
        self._evict_warned = False
        #: optional params-view hook applied INSIDE the jitted step (the
        #: single interior site where params are consumed). The serving
        #: predictor installs ``quant.materialize`` here for quantized
        #: artifacts: weights stay in storage dtype as traced arguments
        #: and the dequant converts fuse into their consumers. None =
        #: identity (the traced structure is untouched).
        self._param_view = None
        #: observability for the last ``generate`` call:
        #: ``{decode_steps, steps_saved, max_length, decode_chunk,
        #: full_scan}`` — the serving predictor forwards it per request.
        self.last_info: Dict[str, Any] = {}

    # ------------------------------------------------------------------
    def static_input_layers(self):
        """Outer layer names feeding the group's static/boot inputs —
        the encoder outputs ``generate`` needs in ``outer_outputs``."""
        return [inp.layer_name
                for inp, meta in zip(self.cfg.inputs, self.cfg.attrs["ins"])
                if meta["kind"] in ("static", "boot")]

    def static_feed_from_outer(self, outer_outputs, row=None):
        """Map outer-layer-keyed encoder outputs to boundary-keyed static
        feed; ``row`` (host int) selects a single lane as a batch of 1."""
        static_feed = {}
        for inp, meta in zip(self.cfg.inputs, self.cfg.attrs["ins"]):
            if meta["kind"] in ("static", "boot"):
                a = outer_outputs[inp.layer_name]
                if row is not None:
                    a = jax.tree_util.tree_map(
                        lambda x: x[row:row + 1], a)
                static_feed[meta["boundary"]] = a
        return static_feed

    def _resolve_hooks(self, candidate_adjust, drop_callback, norm_or_drop,
                       stop_beam_search):
        attrs = self.cfg.attrs
        if candidate_adjust is None:
            candidate_adjust = attrs.get("candidate_adjust")
        if drop_callback is None:
            drop_callback = attrs.get("drop_callback")
        if norm_or_drop is None:
            norm_or_drop = attrs.get("norm_or_drop")
        if stop_beam_search is None:
            stop_beam_search = attrs.get("stop_beam_search")
        return (candidate_adjust, drop_callback, norm_or_drop,
                stop_beam_search)

    def _resolve_chunk(self, L: int, decode_chunk, full_scan):
        """(chunk or None-for-full-scan) from per-call args and config
        attrs (``dsl.beam_search(..., decode_chunk=, full_scan=)``).
        Precedence: an explicit ``full_scan`` wins; an explicit
        ``decode_chunk`` is an explicit request for that policy
        (``> 0`` chunked, ``<= 0`` full scan); only when both are unset
        does the config's pinned policy apply."""
        attrs = self.cfg.attrs
        if full_scan is None:
            if decode_chunk is not None:
                full_scan = int(decode_chunk) <= 0
            else:
                full_scan = bool(attrs.get("full_scan", False))
        elif decode_chunk is not None and int(decode_chunk) <= 0:
            full_scan = True  # 0/-1 spell "no chunking" on the CLI
        if decode_chunk is None:
            decode_chunk = attrs.get("decode_chunk")
            if decode_chunk is not None and int(decode_chunk) <= 0:
                full_scan = True
        if full_scan:
            return None
        chunk = int(decode_chunk or DEFAULT_DECODE_CHUNK)
        return max(1, min(chunk, L))

    # ------------------------------------------------------------------
    def generate(self, params, outer_outputs: Dict[str, Argument], *,
                 beam_size: Optional[int] = None,
                 max_length: Optional[int] = None,
                 candidate_adjust: Optional[Callable] = None,
                 drop_callback: Optional[Callable] = None,
                 norm_or_drop: Optional[Callable] = None,
                 stop_beam_search: Optional[Callable] = None,
                 decode_chunk: Optional[int] = None,
                 full_scan: Optional[bool] = None):
        """Run the search.

        params: global parameter table (sub-net params are hoisted names).
        outer_outputs: outer-layer Arguments for static/boot inputs, keyed
            by outer layer name (run your encoder Network first).
        decode_chunk: steps per compiled chunk of the early-exit search
            (default ``DEFAULT_DECODE_CHUNK``, or the config's
            ``decode_chunk`` attr). The search exits at the first chunk
            boundary where every beam is finished — byte-identical
            results to the full scan, at cost proportional to the actual
            output length. ``<= 0`` means full scan.
        full_scan: force the single length-L scan (escape hatch /
            baseline); defaults to the config's ``full_scan`` attr.

        Beam-control hooks (``RecurrentGradientMachine.h:92-145``); each
        defaults to the config attr of the same name so hooks pinned by
        ``dsl.beam_search`` apply to every call, flat or via SWIG:

        - ``candidate_adjust(logp [B*K, V], state) -> logp``
        - ``drop_callback(state, total [B, K, V]) -> bool [B, K, V]``
          (True = drop that expanded node; the forced-EOS continuation
          of an already-finished beam is exempt — its frozen score must
          carry)
        - ``norm_or_drop(eos_scores [B, K], length) -> [B, K]`` applied
          to candidates finishing at this step (``length`` counts the
          EOS); return -inf to drop the ending, or a renormalized score
        - ``stop_beam_search(state, t) -> bool`` (scalar or [B]); True
          freezes the search from this step on

        Returns (tokens [B, K, L] int32, scores [B, K], lengths [B, K]) —
        beams sorted best-first, EOS included in the length. Decode-step
        accounting for the call lands in :attr:`last_info`.
        """
        if beam_size is None:
            beam_size = self.cfg.attrs.get("beam_size", 1)
        if max_length is None:
            max_length = self.cfg.attrs.get("max_length", 100)
        hooks = self._resolve_hooks(candidate_adjust, drop_callback,
                                    norm_or_drop, stop_beam_search)
        chunk = self._resolve_chunk(max_length, decode_chunk, full_scan)
        # key by the callables themselves (strong refs) — an id() key
        # could be recycled after GC and silently serve a stale search
        key = (beam_size, max_length, chunk) + hooks
        fn = self._jit_for(key, beam_size, max_length, hooks, chunk)
        static_feed = self.static_feed_from_outer(outer_outputs)
        tokens, scores, lengths, steps = fn(params, static_feed)
        steps = int(steps)
        self.last_info = {
            "decode_steps": steps, "max_length": int(max_length),
            "steps_saved": int(max_length) - steps,
            "decode_chunk": chunk, "full_scan": chunk is None}
        return tokens, scores, lengths

    def _jit_for(self, key, K, L, hooks, chunk):
        """LRU-bounded lookup of the compiled search for ``key``."""
        fn = self._jitted.get(key)
        if fn is not None:
            self._jitted.move_to_end(key)
            return fn
        # graftlint: jit-cache: LRU-bounded (_JIT_CACHE_CAP) with a
        # loud eviction warning; serving brings the warmed entries
        # under hardened guards via _ensure_engine_guard
        fn = jax.jit(lambda p, feed: self._search(p, feed, K, L, hooks,
                                                  chunk))
        self._jitted[key] = fn
        while len(self._jitted) > self._JIT_CACHE_CAP:
            evicted_key, _ = self._jitted.popitem(last=False)
            if not self._evict_warned:
                self._evict_warned = True
                logger.warning(
                    "SequenceGenerator jit cache passed %d variants; "
                    "evicting the oldest (beam=%s, length=%s). Per-call "
                    "hook lambdas mint a fresh compile key every "
                    "generate() — pin hooks at module level or in the "
                    "config (dsl.beam_search) to reuse compiles.",
                    self._JIT_CACHE_CAP, evicted_key[0], evicted_key[1])
        return fn

    # ------------------------------------------------------------------
    def _make_step(self, B: int, K: int, L: int, hooks, *,
                   per_lane_t: bool):
        """Build the one-decoder-step function shared by the dedicated
        search (``t`` a traced scalar) and :class:`DecodeSession`
        (``t`` a per-lane ``[B]`` vector, ``per_lane_t=True``).

        ``step(params, flat_static, state, t) -> new_state`` where
        ``state`` has keys {tokens, prev, scores, finished, mem} and
        ``flat_static`` maps group boundary -> Argument with
        ``[B*K, ...]`` leaves. ``params`` must be a traced jit argument,
        never closed-over device arrays — XLA treats closure captures as
        program constants, which measurably deoptimizes the loop body
        (~4x per step on XLA:CPU for the session chunk).
        """
        adjust, drop_cb, norm_or_drop, stop_fn = hooks
        cfg, net, gen = self.cfg, self.net, self.gen
        memories = cfg.attrs["memories"]
        out_name = cfg.attrs["outputs"][0]
        eos = gen["eos_id"]
        gen_boundary = gen["boundary"]

        def step(params, flat_static, state, t):
            if self._param_view is not None:
                params = self._param_view(params)
            emb = params[gen["embedding_name"]]
            prev_emb = emb[state["prev"].reshape(-1)]  # [B*K, E]
            feed = dict(flat_static)
            feed[gen_boundary] = Argument(value=prev_emb)
            for m in memories:
                feed[m["boundary"]] = Argument(
                    value=state["mem"][m["boundary"]])
            outs = net.apply(params, feed, train=False)
            prob = outs[out_name].value  # [B*K, V] post-softmax
            logp = jnp.log(jnp.maximum(prob, 1e-20))
            if adjust is not None:
                logp = adjust(logp, state)
            V = logp.shape[-1]
            logp = _unflatten_beams(logp, B, K)  # [B, K, V]
            # finished beams may only "continue" with EOS at zero cost
            fin = state["finished"][:, :, None]
            eos_only = jnp.full((1, 1, V), NEG).at[0, 0, eos].set(0.0)
            logp = jnp.where(fin, eos_only, logp)
            total = state["scores"][:, :, None] + logp  # [B, K, V]
            # the forced EOS continuation of an already-finished beam is
            # bookkeeping, not a candidate — no hook may touch it, or a
            # frozen beam's score would drift after it ended
            forced = fin & (jnp.arange(V) == eos)[None, None, :]
            if norm_or_drop is not None:
                # NormOrDropNode: a candidate that ENDS here (picks EOS at
                # step t, path length t+1 counting the EOS) gets its
                # cumulative score renormalized or dropped (-inf)
                length = (t + 1)[:, None] if per_lane_t else t + 1
                ended = norm_or_drop(total[:, :, eos], length)
                total = total.at[:, :, eos].set(
                    jnp.where(state["finished"], total[:, :, eos], ended))
            if drop_cb is not None:
                drop = drop_cb(state, total)
                total = jnp.where(jnp.logical_and(drop, ~forced), NEG,
                                  total)
            flat = total.reshape(B, K * V)
            top_scores, top_idx = lax.top_k(flat, K)     # [B, K]
            parent = top_idx // V
            token = (top_idx % V).astype(jnp.int32)

            if K == 1:
                # greedy fast path: the single beam is its own parent
                # (parent == idx // V == 0), so every gather below is the
                # identity — skip them all
                def gather_parents(x):
                    return x
                fin_parent = state["finished"]
                tokens = state["tokens"]
            else:
                def gather_parents(x):
                    # x: [B*K, ...] -> per-batch gather along beam axis
                    xb = _unflatten_beams(x, B, K)
                    return _flatten_beams(
                        jnp.take_along_axis(
                            xb,
                            parent.reshape((B, K) + (1,) * (xb.ndim - 2)),
                            axis=1))
                fin_parent = jnp.take_along_axis(state["finished"], parent,
                                                 axis=1)
                tokens = jnp.take_along_axis(
                    state["tokens"], parent[:, :, None], axis=1)

            new_mem = {
                m["boundary"]: gather_parents(
                    outs[m["link"]].value) for m in memories}
            # frozen memories for finished beams
            old_mem_g = {b: gather_parents(v)
                         for b, v in state["mem"].items()}
            finf = _flatten_beams(fin_parent)  # [B*K]
            new_mem = {
                b: jnp.where(finf.reshape((-1,) + (1,) * (v.ndim - 1)),
                             old_mem_g[b], v)
                for b, v in new_mem.items()}
            if per_lane_t:
                # each lane writes at its own position t[b]
                pos = (jnp.arange(L)[None, None, :]
                       == t[:, None, None])  # [B, 1, L]
                tokens = jnp.where(pos, token[:, :, None], tokens)
            else:
                tokens = tokens.at[:, :, t].set(token)
            finished = fin_parent | (token == eos)
            new_state = {"tokens": tokens, "prev": token,
                         "scores": top_scores, "finished": finished,
                         "mem": new_mem}
            if stop_fn is not None:
                # stopBeamSearch: once the predicate fires, every beam
                # behaves as finished — only zero-cost EOS continuations
                # from here on, so the search is over in all but shape
                stop = jnp.asarray(stop_fn(new_state, t), bool)
                if stop.ndim <= 1:  # scalar or per-batch [B] -> [B, K]
                    stop = jnp.broadcast_to(stop.reshape((-1, 1)), (B, K))
                new_state["finished"] = new_state["finished"] | stop
            return new_state

        return step

    def _init_state(self, static_feed, K: int, L: int):
        """(B, flat_static, state0) for a dedicated search over the
        static/boot feed."""
        cfg, net, gen = self.cfg, self.net, self.gen
        memories = cfg.attrs["memories"]
        bos, eos = gen["bos_id"], gen["eos_id"]

        boots = {m["boundary"]: static_feed[m["boundary"]].value
                 for m in memories if m["boundary"] in static_feed}
        some_static = next((a for a in static_feed.values()), None)
        if some_static is None:
            raise ValueError("generation needs at least one static/boot "
                             "input to define the batch size")
        B = some_static.value.shape[0]

        # beams: replicate statics over K and flatten to a [B*K] batch
        def rep(a: Argument) -> Argument:
            def r(x):
                return _flatten_beams(
                    jnp.broadcast_to(x[:, None], (B, K) + x.shape[1:]))
            return Argument(value=r(a.value),
                            mask=None if a.mask is None else r(a.mask))

        flat_static = {
            b: rep(a) for b, a in static_feed.items()
            if b not in boots}

        carry0 = {}
        for m in memories:
            bname = m["boundary"]
            if bname in boots:
                v = boots[bname]
            else:
                size = net.shape_infos[bname].size
                v = jnp.full((B, size), m.get("init", 0.0), jnp.float32)
            carry0[bname] = _flatten_beams(
                jnp.broadcast_to(v[:, None], (B, K) + v.shape[1:]))

        state0 = {
            "tokens": jnp.full((B, K, L), eos, jnp.int32),
            "prev": jnp.full((B, K), bos, jnp.int32),
            # only beam 0 is live at t=0 so duplicates don't fill the beam
            "scores": jnp.concatenate(
                [jnp.zeros((B, 1)), jnp.full((B, K - 1), NEG)], axis=1)
            if K > 1 else jnp.zeros((B, K)),
            "finished": jnp.zeros((B, K), bool),
            "mem": carry0,
        }
        return B, flat_static, state0

    def _search(self, params, static_feed, K: int, L: int, hooks,
                chunk: Optional[int] = None):
        """The jitted search body. ``chunk=None`` = single length-L scan;
        otherwise a ``lax.while_loop`` over ``chunk``-step scan bodies
        exiting at the first chunk boundary where every beam is finished
        (or ``stop_beam_search`` fired — it sets ``finished``).

        Returns (tokens, scores, lengths, steps) with ``steps`` the
        number of decoder steps actually executed (== L for full scan).
        """
        B, flat_static, state0 = self._init_state(static_feed, K, L)
        step = self._make_step(B, K, L, hooks, per_lane_t=False)

        if chunk is None:
            def body(state, t):
                return step(params, flat_static, state, t), None
            state, _ = lax.scan(body, state0, jnp.arange(L))
            steps = jnp.int32(L)
        else:
            C = int(chunk)

            def chunk_body(carry):
                state, t0 = carry

                def body(state, i):
                    t = t0 + i
                    new = step(params, flat_static, state, t)
                    # the last chunk may overhang L (L % C != 0): steps
                    # at t >= L are no-ops so the executed prefix is
                    # exactly t = 0..L-1, same as the full scan
                    new = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(t < L, n, o), new, state)
                    return new, None

                state, _ = lax.scan(body, state, jnp.arange(C))
                return state, t0 + C

            def chunk_cond(carry):
                state, t0 = carry
                return (t0 < L) & ~jnp.all(state["finished"])

            state, t_end = lax.while_loop(
                chunk_cond, chunk_body, (state0, jnp.int32(0)))
            steps = jnp.minimum(t_end, L)

        tokens = state["tokens"]
        # length = index of first EOS + 1 (EOS kept, as the reference's
        # sequence results include the end mark), else L
        eos = self.gen["eos_id"]
        is_eos = tokens == eos
        first = jnp.argmax(is_eos, axis=-1)
        has = jnp.any(is_eos, axis=-1)
        lengths = jnp.where(has, first + 1, L)
        return tokens, state["scores"], lengths, steps

    # ------------------------------------------------------------------
    def session(self, params, width: int, *,
                beam_size: Optional[int] = None,
                max_length: Optional[int] = None,
                decode_chunk: Optional[int] = None,
                candidate_adjust: Optional[Callable] = None,
                drop_callback: Optional[Callable] = None,
                norm_or_drop: Optional[Callable] = None,
                stop_beam_search: Optional[Callable] = None
                ) -> "DecodeSession":
        """A continuous-batching decode session: ``width`` lanes stepped
        ``decode_chunk`` steps per :meth:`DecodeSession.run_chunk`, with
        per-lane admit/retire between chunks (``docs/serving.md``)."""
        if beam_size is None:
            beam_size = self.cfg.attrs.get("beam_size", 1)
        if max_length is None:
            max_length = self.cfg.attrs.get("max_length", 100)
        hooks = self._resolve_hooks(candidate_adjust, drop_callback,
                                    norm_or_drop, stop_beam_search)
        chunk = self._resolve_chunk(max_length, decode_chunk, False)
        if chunk is None:
            chunk = max(1, min(DEFAULT_DECODE_CHUNK, int(max_length)))
        return DecodeSession(self, params, int(width), int(beam_size),
                             int(max_length), int(chunk), hooks)


class DecodeSession:
    """Fixed-width continuous-batching decode state.

    ``width`` lanes share one compiled chunk body; each lane carries its
    own decode clock ``t`` (lanes admitted mid-flight start at 0 while
    neighbors are deep into their outputs). The host loop between chunks
    is the lane lifecycle: :meth:`admit` splices a freshly encoded
    request into a free lane, :meth:`run_chunk` advances every live lane
    ``chunk`` steps, :meth:`finished_lanes` / :meth:`peek` /
    :meth:`release` retire lanes whose beams all finished (or that hit
    ``max_length``). Lanes are independent — every per-step op is
    batched row-wise, so a lane's tokens/scores match the dedicated
    search on the same request regardless of what its neighbors decode.

    All three device functions (admit / chunk / release) are jitted once
    per session with traced lane indices — a session serves any traffic
    with exactly three compiled programs (the serving predictor wraps
    them in hardened ``RecompileGuard``s).
    """

    _CORE = ("tokens", "prev", "scores", "finished", "mem")

    def __init__(self, gen: SequenceGenerator, params, width: int, K: int,
                 L: int, chunk: int, hooks):
        self.gen = gen
        self.params = params
        self.width, self.K, self.L, self.chunk = width, K, L, chunk
        self.hooks = hooks
        self._state = None          # built lazily at first admit
        self._admit_fn = None
        self._chunk_fn = None
        self._release_fn = None

    # ------------------------------------------------------------ state
    def _build(self, static_feed):
        """Build the empty W-lane state + jitted fns from the shapes of
        the first admitted request's static feed."""
        W, K, L = self.width, self.K, self.L
        cfg, net, gen = self.gen.cfg, self.gen.net, self.gen.gen
        memories = cfg.attrs["memories"]
        bos, eos = gen["bos_id"], gen["eos_id"]
        boot_names = {m["boundary"] for m in memories}

        statics = {}
        for b, a in static_feed.items():
            if b in boot_names:
                continue

            def z(x):
                return jnp.zeros((W * K,) + x.shape[1:], x.dtype)
            statics[b] = Argument(
                value=z(a.value),
                mask=None if a.mask is None else z(a.mask))
        mem = {}
        for m in memories:
            bname = m["boundary"]
            if bname in static_feed:
                size = static_feed[bname].value.shape[-1]
            else:
                size = net.shape_infos[bname].size
            mem[bname] = jnp.zeros((W * K, size), jnp.float32)
        self._state = {
            "tokens": jnp.full((W, K, L), eos, jnp.int32),
            "prev": jnp.full((W, K), bos, jnp.int32),
            "scores": jnp.zeros((W, K)),
            # inactive lanes read as finished so they are forced-EOS
            # no-ops inside the chunk body
            "finished": jnp.ones((W, K), bool),
            "mem": mem,
            "static": statics,
            "t": jnp.zeros(W, jnp.int32),
            "active": jnp.zeros(W, bool),
        }

        def _put_rows(dst, src, lane):
            """src [1, ...] broadcast to K rows at dst[lane*K:...]."""
            upd = jnp.broadcast_to(
                src.astype(dst.dtype), (K,) + src.shape[1:])
            return lax.dynamic_update_slice(
                dst, upd, (lane * K,) + (0,) * (dst.ndim - 1))

        def _admit(state, lane, static_row, boot_row):
            state = dict(state)
            new_static = {}
            for b, a in state["static"].items():
                src = static_row[b]
                new_static[b] = Argument(
                    value=_put_rows(a.value, src.value, lane),
                    mask=(None if a.mask is None
                          else _put_rows(a.mask, src.mask, lane)))
            state["static"] = new_static
            new_mem = {}
            for m in memories:
                bname = m["boundary"]
                if bname in boot_row:
                    src = boot_row[bname]
                else:
                    src = jnp.full((1, state["mem"][bname].shape[-1]),
                                   m.get("init", 0.0), jnp.float32)
                new_mem[bname] = _put_rows(state["mem"][bname], src, lane)
            state["mem"] = new_mem
            state["tokens"] = lax.dynamic_update_slice(
                state["tokens"], jnp.full((1, K, L), eos, jnp.int32),
                (lane, 0, 0))
            state["prev"] = lax.dynamic_update_slice(
                state["prev"], jnp.full((1, K), bos, jnp.int32), (lane, 0))
            row_scores = (jnp.concatenate(
                [jnp.zeros((1, 1)), jnp.full((1, K - 1), NEG)], axis=1)
                if K > 1 else jnp.zeros((1, K)))
            state["scores"] = lax.dynamic_update_slice(
                state["scores"], row_scores, (lane, 0))
            state["finished"] = lax.dynamic_update_slice(
                state["finished"], jnp.zeros((1, K), bool), (lane, 0))
            state["t"] = state["t"].at[lane].set(0)
            state["active"] = state["active"].at[lane].set(True)
            return state

        step = self.gen._make_step(W, K, L, self.hooks, per_lane_t=True)
        C = self.chunk

        def _lane_sel(adv, new, old):
            sel = {}
            sel["tokens"] = jnp.where(adv[:, None, None], new["tokens"],
                                      old["tokens"])
            for k in ("prev", "scores", "finished"):
                sel[k] = jnp.where(adv[:, None], new[k], old[k])
            advf = jnp.repeat(adv, K)
            sel["mem"] = {
                b: jnp.where(advf.reshape((-1,) + (1,) * (v.ndim - 1)),
                             new["mem"][b], v)
                for b, v in old["mem"].items()}
            return sel

        def _chunk(params, state):
            def body(state, _):
                # a lane runs while it is live, not past max_length, and
                # not fully finished; everything else is frozen so a
                # retired-but-not-yet-replaced lane cannot drift
                adv = (state["active"] & (state["t"] < L)
                       & ~jnp.all(state["finished"], axis=1))
                core = {k: state[k] for k in DecodeSession._CORE}
                new_core = step(params, state["static"], core,
                                state["t"])
                merged = dict(state)
                merged.update(_lane_sel(adv, new_core, core))
                merged["t"] = jnp.where(adv, state["t"] + 1, state["t"])
                return merged, None

            state, _ = lax.scan(body, state, None, length=C)
            return state

        def _release(state, lane):
            state = dict(state)
            state["active"] = state["active"].at[lane].set(False)
            state["finished"] = lax.dynamic_update_slice(
                state["finished"], jnp.ones((1, K), bool), (lane, 0))
            return state

        # graftlint: jit-cache: exactly 3 compiles per session, exposed
        # via jitted_fns() and hardened by the serving predictor's
        # RecompileGuards after warmup (build_session)
        self._admit_fn = jax.jit(_admit)
        self._chunk_fn = jax.jit(_chunk)  # graftlint: jit-cache: ^
        self._release_fn = jax.jit(_release)  # graftlint: jit-cache: ^

    # ------------------------------------------------------------ lanes
    def jitted_fns(self) -> List[Callable]:
        """The session's compiled device functions, for recompile
        guarding (empty before the first admit)."""
        return [f for f in (self._admit_fn, self._chunk_fn,
                            self._release_fn) if f is not None]

    def poll(self):
        """One fused device->host fetch of the lane flags:
        ``(active [W] bool, all_finished [W] bool, t [W] int)``. The
        continuous batcher calls this once per chunk boundary and derives
        free/expired/finished lanes from the result — per-accessor
        fetches would serialize several host round-trips onto the decode
        hot path."""
        s = self._state
        if s is None:
            return (np.zeros(self.width, bool), np.zeros(self.width, bool),
                    np.zeros(self.width, np.int32))
        active, fin, t = jax.device_get(
            (s["active"], jnp.all(s["finished"], axis=1), s["t"]))
        return np.asarray(active), np.asarray(fin), np.asarray(t)

    def _lane_flags(self):
        return self.poll()

    def free_lanes(self) -> List[int]:
        active, _, _ = self._lane_flags()
        return [i for i in range(self.width) if not active[i]]

    def active_lanes(self) -> List[int]:
        active, _, _ = self._lane_flags()
        return [i for i in range(self.width) if active[i]]

    def finished_lanes(self) -> List[int]:
        """Lanes whose search is over (all beams finished, or the lane
        hit max_length) and which carry an unretired result."""
        active, fin, t = self._lane_flags()
        return [i for i in range(self.width)
                if active[i] and (fin[i] or t[i] >= self.L)]

    def admit(self, lane: int, outer_outputs, row: int = 0):
        """Splice request ``row`` of the encoded ``outer_outputs`` (outer
        layer name -> Argument) into ``lane``, starting its clock at 0."""
        static_feed = self.gen.static_feed_from_outer(outer_outputs,
                                                      row=row)
        if self._state is None:
            self._build(static_feed)
        boot_names = {m["boundary"]
                      for m in self.gen.cfg.attrs["memories"]}
        static_row = {b: a for b, a in static_feed.items()
                      if b not in boot_names}
        boot_row = {b: a.value for b, a in static_feed.items()
                    if b in boot_names}
        self._state = self._admit_fn(self._state, jnp.int32(lane),
                                     static_row, boot_row)

    def run_chunk(self) -> int:
        """Advance every live lane ``chunk`` steps; returns the chunk
        size (0 when nothing was ever admitted)."""
        if self._state is None:
            return 0
        self._state = self._chunk_fn(self.params, self._state)
        return self.chunk

    def lane_steps(self, lane: int) -> int:
        """Decode steps a lane has executed — a scalar fetch, cheap
        enough for hot-loop diagnostics (unlike :meth:`peek`, which
        copies the lane's whole token buffer)."""
        if self._state is None:
            return 0
        return int(np.asarray(self._state["t"][lane]))

    def peek(self, lane: int):
        """(tokens [K, L], scores [K], lengths [K], steps) for a lane —
        host np arrays; lengths use the same first-EOS+1 rule as
        ``generate``."""
        s = self._state
        tokens = np.asarray(s["tokens"][lane])
        scores = np.asarray(s["scores"][lane])
        steps = int(np.asarray(s["t"][lane]))
        eos = self.gen.gen["eos_id"]
        is_eos = tokens == eos
        first = np.argmax(is_eos, axis=-1)
        has = np.any(is_eos, axis=-1)
        lengths = np.where(has, first + 1, self.L).astype(np.int64)
        return tokens, scores, lengths, steps

    def release(self, lane: int):
        """Free a lane (after :meth:`peek`); it reads finished/inactive
        until the next :meth:`admit`."""
        self._state = self._release_fn(self._state, jnp.int32(lane))
