"""Bucketed, AOT-warmed, donation-friendly predictor over a deploy model.

The deploy artifact is the merged model (``trainer/merge_model.py`` —
the same PTM1 file the C API's ``ptc_load`` consumes), or any live
(graph, params) pair. On top of it this module enforces the serving
shape discipline:

- **Closed shape menu.** Batch sizes come from ``batch_buckets`` and
  padded sequence lengths from ``length_buckets`` — the feeder's own
  bucketing machinery (``data/feeder.py``), reused verbatim so serving
  and training pad identically. Unlike training there is NO overflow
  rule: a sequence longer than the largest edge is *inadmissible*
  (typed ``BadRequest``), never a new compile.
- **AOT warmup.** ``warmup()`` drives every (batch, length) bucket pair
  through the jitted forward — and, for generating configs, the jitted
  beam search — before the first request, so startup pays all XLA
  compile time.
- **Hardened recompile guard.** After warmup every guard is
  ``harden()``-ed (``data/prefetch.py:RecompileGuard``): jit-cache
  growth on the hot path raises ``RecompileError`` instead of silently
  serving at compile speed.
- **Donation.** Request feeds are fresh arrays, dead after the call, so
  the jitted forward donates them (TPU/GPU; XLA ignores donation on
  CPU, where it is skipped to avoid warning spam).
- **Collective-free.** The warm path is a single-device program and
  must stay one: graftlint pass 4 compiles ``_infer`` and pins its
  collective manifest EMPTY (``analysis/comm_budget.toml`` — any
  collective the serving step grows is PT501 drift at lint time).
- **Quantized tier.** A ``--quantize`` PTM1 artifact loads with its
  weights in STORAGE dtype (int8 stays int8 in HBM, bf16 stays bf16)
  plus traced per-tensor scale leaves; ``paddle_tpu/quant.py:
  materialize`` rebuilds the f32 view inside each jitted program so
  XLA fuses the dequant converts at point of use — no resident f32
  twin (graftlint pass 5 pins the ``serving_quant`` footprint). At
  warmup the embedded golden-request set replays through the real
  bucketed path and the per-output delta vs the recorded fp32
  references must stay within the artifact's per-dtype tolerance — a
  drifted quantized model raises ``QuantGateError`` and never goes
  READY (the closed-shape-menu discipline applied to accuracy); the
  gate verdict rides ``/healthz`` and the rolling-reload report.
  Masks are feed-side and stay f32 through the quantized funnel
  (``assert_feed_masks_f32`` in ``_convert``, unchanged).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.serving.errors import BadRequest
from paddle_tpu.utils.log import get_logger
from paddle_tpu.utils.masks import assert_feed_masks_f32

logger = get_logger("serving")


def _is_seq(itype) -> bool:
    from paddle_tpu.data import types as T
    return itype.seq_type != T.NO_SEQUENCE


def _synth_sample(itype, length: int):
    """An all-zeros warmup sample for one input slot at padded length
    ``length`` (sequence slots) — shaped exactly like real traffic so
    the warmed jit variants are the ones requests hit."""
    from paddle_tpu.data import types as T
    if itype.seq_type == T.NO_SEQUENCE:
        if itype.type == T.INDEX:
            return 0
        if itype.type in (T.SPARSE_BINARY, T.SPARSE_FLOAT):
            return []
        return np.zeros(itype.dim, dtype=np.float32)
    # SUB_SEQUENCE never reaches here — the predictor refuses nested
    # inputs at construction (unbucketed outer axis)
    if itype.type == T.INDEX:
        return [0] * length
    if itype.type in (T.SPARSE_BINARY, T.SPARSE_FLOAT):
        return [[] for _ in range(length)]
    return [np.zeros(itype.dim, dtype=np.float32) for _ in range(length)]


class ServingPredictor:
    """Loads a model and serves bucketed batches with zero hot-path
    compiles. ``predict_rows`` scores; ``generate_rows`` runs the beam
    search of a generating config (``beam_search_group`` present),
    honoring any beam-control hooks pinned in the config."""

    def __init__(self, graph, params: Dict[str, Any],
                 output_names: Sequence[str],
                 feeding: Dict[str, Any], *,
                 batch_buckets: Sequence[int],
                 length_buckets: Optional[Sequence[int]] = None,
                 gen_beam_size: Optional[int] = None,
                 gen_max_length: Optional[int] = None,
                 gen_decode_chunk: Optional[int] = None,
                 gen_full_scan: Optional[bool] = None,
                 donate: Optional[bool] = None,
                 recompile_warn: int = 64,
                 aot_cache=None, model_hash: Optional[str] = None,
                 quant: Optional[Dict[str, Any]] = None,
                 golden: Optional[Dict[str, Any]] = None):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.core.network import Network
        from paddle_tpu.data.feeder import DataFeeder
        from paddle_tpu.data.prefetch import RecompileGuard

        self.graph = graph
        self.params = {k: jnp.asarray(v) for k, v in params.items()}
        # model identity: the PTM1 digest for merged artifacts (passed by
        # from_merged), else a structural fingerprint — keys the AOT
        # warmup cache and names the version /healthz + rolling reload
        # report
        if model_hash is None:
            from paddle_tpu.serving.aot_cache import model_fingerprint
            model_hash = model_fingerprint(graph, self.params)
        self.model_hash = str(model_hash)
        self.model_version = self.model_hash[:12]
        # quantized artifacts: weights stay in storage dtype, traced
        # scale leaves join the params pytree, and every jitted program
        # sees the f32 view through _materialize (dequant fused at
        # point of use). The dtype suffix makes precision part of the
        # published version so canaries/provenance can tell tiers apart
        # even before reading /healthz's quant block.
        self.quant = dict(quant) if quant else None
        self.golden = golden
        self.quant_gate: Optional[Dict[str, Any]] = None
        self._materialize = None
        if self.quant:
            from paddle_tpu import quant as quant_lib
            self.params.update(
                {k: jnp.asarray(v) for k, v in
                 quant_lib.scale_leaves(self.quant).items()})
            meta = self.quant
            self._materialize = (
                lambda p: quant_lib.materialize(p, meta))
            self.model_version += "+" + str(self.quant["dtype"])
        if isinstance(aot_cache, str):
            from paddle_tpu.serving.aot_cache import AOTCache
            aot_cache = AOTCache(aot_cache, self.model_hash)
        self.aot_cache = aot_cache
        # (name, bucket key) -> jax.stages.Compiled: the warmed menu as
        # ready-to-call executables (populated only when a cache is
        # configured; without one the plain jit path serves as before)
        self._aot: Dict[Tuple[str, str], Any] = {}
        self.feeding = dict(feeding)
        self.names = list(self.feeding)
        self.batch_buckets = sorted(int(b) for b in batch_buckets)
        if not self.batch_buckets or self.batch_buckets[0] < 1:
            raise ValueError(f"bad batch_buckets: {batch_buckets}")
        from paddle_tpu.data import types as T
        nested = [n for n, t in self.feeding.items()
                  if t.seq_type == T.SUB_SEQUENCE]
        if nested:
            # the outer subsequence count is an unbounded shape axis the
            # bucket menu does not close: one well-formed 2-subsequence
            # request would compile on the hot path and (hardened guard)
            # kill the worker. Refuse at build time instead.
            raise ValueError(
                f"serving does not support nested-sequence (SUB_SEQUENCE)"
                f" inputs yet: {nested} — the outer subsequence count is"
                " an unbucketed shape axis")
        self.has_sequences = any(_is_seq(t) for t in self.feeding.values())
        self.length_buckets = (sorted(int(e) for e in length_buckets)
                               if length_buckets and self.has_sequences
                               else None)
        if self.has_sequences and not self.length_buckets:
            # silently unbucketed lengths = every batch pads to its own
            # max = post-warmup compile = worker death on the first real
            # request. A sequence model MUST close the length menu.
            raise ValueError(
                "this model has sequence inputs; serving needs non-empty "
                "length_buckets (--serving_length_buckets) so the shape "
                "menu is closed")
        self.max_seq_len = (self.length_buckets[-1]
                            if self.length_buckets else None)
        # id validation ON: an out-of-range id must be a loud per-lane
        # BadRequest, not a silent zero-row lookup (feeder validate_ids).
        # shared_length_bucket ON: every sequence slot of a batch pads to
        # ONE bucket, so the warmed menu is the bucket list — per-slot
        # independent bucketing would make legal multi-sequence requests
        # hit unwarmed cross-product shapes (hot-path compile)
        self.feeder = DataFeeder(
            self.feeding, batch_buckets=self.batch_buckets,
            length_buckets=self.length_buckets, validate_ids=True,
            shared_length_bucket=True)

        self.output_names = [o.name if hasattr(o, "name") else o
                             for o in output_names]
        # the generation group (if any) is served by the beam-search
        # engine, not the plain forward — score outputs exclude it
        self._gen_name = next(
            (n for n, l in graph.layers.items()
             if l.type == "beam_search_group"), None)
        score_outputs = [n for n in self.output_names
                         if n != self._gen_name]
        self.network = (Network(graph, outputs=score_outputs)
                        if score_outputs else None)

        if donate is None:
            donate = jax.default_backend() in ("tpu", "gpu")
        donate_args = (1,) if donate else ()

        self.guards: List[RecompileGuard] = []
        if self.network is not None:
            def _fwd(p, feed):
                # quantized models: dequant INSIDE the trace, so XLA
                # fuses the converts into each weight's consumer; the
                # fp32 path is structurally untouched (identical jaxpr)
                pp = (self._materialize(p) if self._materialize
                      else p)
                outs = self.network.apply(pp, feed, train=False)
                return {n: outs[n].value for n in score_outputs}

            self._infer = jax.jit(_fwd, donate_argnums=donate_args)
            self.guards.append(RecompileGuard(
                self._infer, warn_after=recompile_warn,
                name="serving_infer"))

        self.engine = None
        self._encode = None
        if self._gen_name is not None:
            from paddle_tpu.core.generation import (
                SequenceGenerator as EngineGenerator)
            self.engine = EngineGenerator(graph, self._gen_name)
            if self._materialize is not None:
                # the generation engine consumes params at exactly one
                # interior site (SequenceGenerator.step); the view hook
                # dequantizes there, inside the jitted search
                self.engine._param_view = self._materialize
            self.gen_beam_size = int(
                gen_beam_size or self.engine.cfg.attrs.get("beam_size", 1))
            self.gen_max_length = int(
                gen_max_length
                or self.engine.cfg.attrs.get("max_length", 100))
            # decode-cost policy: chunked early-exit by default (cost
            # proportional to actual output length), full_scan as the
            # escape hatch / A-B baseline. None everywhere = inherit the
            # config's pinned decode policy (dsl.beam_search attrs) —
            # the same precedence beam-control hooks get. The resolved
            # values are part of the warmed closed menu, like
            # (beam, length).
            if gen_decode_chunk is not None and int(gen_decode_chunk) <= 0:
                gen_full_scan, gen_decode_chunk = True, None
            self.gen_full_scan = gen_full_scan
            self.gen_decode_chunk = (int(gen_decode_chunk)
                                     if gen_decode_chunk else None)
            enc_outputs = self.engine.static_input_layers()
            encoder = Network(graph, outputs=enc_outputs)

            def _enc(p, feed):
                pp = (self._materialize(p) if self._materialize
                      else p)
                outs = encoder.apply(pp, feed, train=False)
                return {n: outs[n] for n in enc_outputs}

            self._encode = jax.jit(_enc, donate_argnums=donate_args)
            self.guards.append(RecompileGuard(
                self._encode, warn_after=recompile_warn,
                name="serving_encode"))

        self.warmed = False

    # ------------------------------------------------------------ loaders
    @classmethod
    def from_merged(cls, path: str, feeding: Dict[str, Any],
                    **kwargs) -> "ServingPredictor":
        """Build from a ``--job=merge`` artifact (PTM1 file). ``feeding``
        still comes from the config — the merged payload carries graph +
        params + output names, not input type declarations. The PTM1
        payload digest becomes the model hash (AOT-cache key + reported
        version), unless the caller pins its own. A ``--quantize``
        artifact's optional sections thread through automatically:
        ``quant`` activates the storage-dtype load + dequant view,
        ``golden`` arms the warmup accuracy gate. The quantized payload
        digest differs from the fp32 merge of the same model, so the
        AOT cache and the published version can never collide across
        precision tiers."""
        from paddle_tpu.trainer.merge_model import load_merged_ex, \
            merged_digest
        graph, params, outputs, extras = load_merged_ex(path)
        kwargs.setdefault("model_hash", merged_digest(path))
        kwargs.setdefault("quant", extras.get("quant"))
        kwargs.setdefault("golden", extras.get("golden"))
        return cls(graph, params, outputs, feeding, **kwargs)

    # ------------------------------------------------------------- warmup
    def warmup(self, log=None) -> int:
        """Compile (or deserialize from the AOT cache) every bucket
        variant ahead of traffic; returns the number of warmup
        executions. Hardens all recompile guards."""
        lengths = self.length_buckets or [None]
        t0 = time.perf_counter()
        runs = 0
        for b in self.batch_buckets:
            for ln in lengths:
                rows = [tuple(_synth_sample(self.feeding[n], ln or 1)
                              for n in self.names)] * b
                if self.network is not None:
                    self._warm_score(rows)
                    runs += 1
                if self.engine is not None:
                    self._warm_generate(rows)
                    runs += 1
        if self.engine is not None:
            # the engine jits lazily per (beam, length, hooks) key; the
            # warmup loop above populated it — bring those under guard
            self._ensure_engine_guard()
        for g in self.guards:
            g.harden()
        # quantized artifacts must PASS the accuracy gate before this
        # predictor may report warmed/READY — a drifted model raises
        # here, exactly like a shape outside the closed menu would
        self._run_quant_gate(log)
        self.warmed = True
        if log:
            cache = ""
            if self.aot_cache is not None:
                s = self.aot_cache.stats
                cache = (f"; aot_cache hits={s['hits']} "
                         f"misses={s['misses'] + s['stale']} "
                         f"quarantined={s['quarantined']}")
            log(f"serving warmup: {runs} bucket variants ready in "
                f"{time.perf_counter() - t0:.1f}s "
                f"(batch={self.batch_buckets}, "
                f"length={self.length_buckets}{cache})")
        return runs

    # ------------------------------------------------------- quant gate
    def quant_health(self) -> Dict[str, Any]:
        """The precision tier + gate verdict ``/healthz`` publishes (a
        canary reads this to know which precision answered)."""
        return {"dtype": (self.quant["dtype"] if self.quant else "fp32"),
                "gate": self.quant_gate}

    def _run_quant_gate(self, log=None):
        """Replay the artifact's golden-request set through the REAL
        bucketed scoring path and compare per-output deltas against the
        recorded fp32 references. Raises ``QuantGateError`` past the
        per-dtype tolerance; records the verdict either way. A
        quantized artifact without a usable golden set (generation-only
        config) stands down with a NAMED warning — never silently."""
        if not self.quant:
            return
        from paddle_tpu import quant as quant_lib
        from paddle_tpu.serving.errors import QuantGateError
        dtype = str(self.quant["dtype"])
        tol = float(self.quant.get("tol",
                                   quant_lib.GATE_TOLERANCES[dtype]))
        golden = self.golden
        if (self.network is None or not golden
                or not golden.get("rows")):
            reason = ("no scoring outputs (generation-only config)"
                      if self.network is None
                      else "artifact carries no golden section")

            self.quant_gate = {"checked": False, "dtype": dtype,
                               "tol": tol, "reason": reason}
            logger.warning(
                "quantized model %s: warmup accuracy gate STOOD DOWN "
                "(%s) — serving %s weights unverified",
                self.model_version, reason, dtype)
            return
        rows = [tuple(r) for r in golden["rows"]]
        refs = golden["outputs"]
        bmax = self.batch_buckets[-1]
        deltas: Dict[str, float] = {n: 0.0 for n in refs}
        try:
            for i in range(0, len(rows), bmax):
                chunk = rows[i:i + bmax]
                outs, _info = self.predict_rows(chunk)
                for name, ref in refs.items():
                    got = outs[name][:len(chunk)]
                    d = quant_lib.gate_delta(got,
                                             ref[i:i + len(chunk)])
                    deltas[name] = max(deltas[name], d)
        except BadRequest as e:
            raise QuantGateError(
                f"warmup accuracy gate could not replay the golden "
                f"set through the serving menu: {e}", dtype=dtype,
                deltas={}, tol=tol) from e
        worst = max(deltas.values())
        passed = worst <= tol
        self.quant_gate = {"checked": True, "dtype": dtype, "tol": tol,
                           "max_delta": worst, "passed": passed,
                           "outputs": dict(deltas)}
        if not passed:
            raise QuantGateError(
                f"quantized model {self.model_version} drifted past "
                f"the warmup accuracy gate: max output delta "
                f"{worst:.4g} > tolerance {tol:g} for {dtype} "
                f"(per-output: {deltas}) — refusing to go READY",
                dtype=dtype, deltas=deltas, tol=tol)
        if log:
            log(f"quant gate PASSED ({dtype}): max output delta "
                f"{worst:.4g} <= tol {tol:g} over "
                f"{len(rows)} golden rows")

    def _aot_executable(self, name: str, sig: str, args, build):
        """One warmed executable: deserialize from the cache when it has
        a valid entry (verified by executing against the warmup
        ``args``), else ``build()`` the live compile and persist it."""
        comp = self.aot_cache.load(name, sig, verify_args=args)
        if comp is not None:
            return comp
        comp = build()
        comp(*args)  # first-call buffer touch, symmetric with the
        # loaded path's verification run
        self.aot_cache.save(name, sig, comp)
        return comp

    def _warm_score(self, rows):
        if self.aot_cache is None:
            self.predict_rows(rows)
            return
        feed = self._convert(rows)
        key, _ = self._bucket_key(feed)
        args = (self.params, feed)
        self._aot[("infer", key)] = self._aot_executable(
            "infer", key, args,
            lambda: self._infer.lower(*args).compile())

    def _warm_generate(self, rows):
        if self.aot_cache is None:
            self.generate_rows(rows)
            return
        feed = self._convert(rows)
        key, _ = self._bucket_key(feed)
        eargs = (self.params, feed)
        enc = self._aot_executable(
            "encode", key, eargs,
            lambda: self._encode.lower(*eargs).compile())
        self._aot[("encode", key)] = enc
        outer = enc(self.params, feed)
        static_feed = self.engine.static_feed_from_outer(outer)
        K, L = self.gen_beam_size, self.gen_max_length
        hooks = self.engine._resolve_hooks(None, None, None, None)
        chunk = self.engine._resolve_chunk(L, self.gen_decode_chunk,
                                           self.gen_full_scan)
        gargs = (self.params, static_feed)
        gsig = f"{key}_k{K}_l{L}" + ("" if chunk is None else f"_c{chunk}")
        self._aot[("generate", key)] = self._aot_executable(
            "generate", gsig, gargs,
            lambda: self.engine._jit_for(
                (K, L, chunk) + hooks, K, L, hooks,
                chunk).lower(*gargs).compile())

    def check_guards(self):
        """Hot-path assertion: raises RecompileError on jit-cache growth
        after warmup (see module docstring)."""
        for g in self.guards:
            g.check()

    # --------------------------------------------------------- admission
    def check_sample(self, sample):
        """Cheap host-side admissibility check, run at enqueue time so a
        doomed request is rejected before it occupies queue space. Raises
        ``BadRequest``; does NOT validate value types (that is conversion
        work, isolated per-lane at batch time)."""
        if not isinstance(sample, (list, tuple)):
            raise BadRequest(
                f"sample must be a list of {len(self.names)} input "
                f"slots ({self.names}), got {type(sample).__name__}")
        if len(sample) != len(self.names):
            raise BadRequest(
                f"sample has {len(sample)} slots, the model needs "
                f"{len(self.names)} ({self.names})")
        for name, slot in zip(self.names, sample):
            itype = self.feeding[name]
            if not _is_seq(itype):
                continue
            if not isinstance(slot, (list, tuple, np.ndarray)):
                raise BadRequest(
                    f"input {name!r} is a sequence slot; got "
                    f"{type(slot).__name__}")
            n = len(slot)
            if self.max_seq_len is not None and n > self.max_seq_len:
                raise BadRequest(
                    f"input {name!r} has length {n}, beyond the largest "
                    f"warmed length bucket {self.max_seq_len}; serving "
                    "shapes are a closed menu (no hot-path compiles)")

    def padding_row(self) -> tuple:
        """A synthetic all-padding row (what batch-bucket padding uses);
        the batcher swaps it in for a malformed lane."""
        return tuple(_synth_sample(self.feeding[n], 1) for n in self.names)

    def probe_rows(self, rows) -> List[Optional[Exception]]:
        """Per-lane conversion probe for the malformed-batch error path:
        converts each row alone (padded to the smallest batch bucket with
        synthetic rows) and returns its exception, or None when clean.
        Only runs after a full-batch conversion already failed, so the
        per-row cost is off the happy path."""
        pad = [self.padding_row()] * (self.batch_buckets[0] - 1)
        out: List[Optional[Exception]] = []
        for row in rows:
            try:
                self.feeder([tuple(row)] + pad)
                out.append(None)
            except Exception as e:  # noqa: BLE001 — typed by the caller
                out.append(e)
        return out

    # ------------------------------------------------------------ scoring
    def _convert(self, rows, lane_valid=None):
        """rows -> feed dict through the bucketing feeder. ``lane_valid``
        (bool per row) zeroes the row mask of known-bad lanes so they are
        exact padding."""
        from paddle_tpu.data.feeder import ROW_MASK_KEY
        feed = self.feeder(list(rows))
        # runtime twin of graftlint PT102: every mask the feeder built
        # must be f32 before it reaches the warmed executables
        assert_feed_masks_f32(feed, "serving feed")
        if lane_valid is not None and ROW_MASK_KEY in feed:
            mask = feed[ROW_MASK_KEY]
            lv = np.ones(mask.value.shape[0], dtype=np.float32)
            lv[:len(lane_valid)] = np.asarray(lane_valid, np.float32)
            # host arithmetic: every leaf stays the feeder's kind (a
            # device array here would be a new entry in the jit cache
            # the hardened guard counts)
            feed[ROW_MASK_KEY] = mask.replace(value=mask.value * lv)
        return feed

    def _bucket_key(self, feed) -> Tuple[str, int]:
        """(metrics bucket label, padded row count) for a converted
        feed."""
        first = feed[self.names[0]].value
        padded = int(first.shape[0])
        key = f"b{padded}"
        for n in self.names:
            if _is_seq(self.feeding[n]):
                key += f"_t{int(feed[n].value.shape[1])}"
                break
        return key, padded

    def predict_rows(self, rows: List[tuple], lane_valid=None):
        """Score a bucketed batch. Returns ``(outs, info)`` where
        ``outs`` maps output layer name -> np array over the PADDED
        batch (caller slices real lanes) and ``info`` carries
        ``{bucket, padded_rows, pad_ms, compute_ms}``."""
        if self.network is None:
            raise BadRequest("this model has no scoring outputs "
                             "(generation-only config)")
        t0 = time.perf_counter()
        feed = self._convert(rows, lane_valid)
        key, padded = self._bucket_key(feed)
        t1 = time.perf_counter()
        # warmed AOT executable when the cache populated one for this
        # bucket; the plain jit path otherwise (and as the fall-through
        # a hardened guard turns into a loud RecompileError)
        comp = self._aot.get(("infer", key))
        out = (comp if comp is not None else self._infer)(
            self.params, feed)
        out = {n: np.asarray(v) for n, v in out.items()}  # host fetch
        t2 = time.perf_counter()
        if self.warmed:
            self.check_guards()
        return out, {"bucket": key, "padded_rows": padded,
                     "pad_ms": (t1 - t0) * 1e3,
                     "compute_ms": (t2 - t1) * 1e3}

    # --------------------------------------------------------- generation
    def gen_effective_full_scan(self) -> bool:
        """The decode policy actually in force: the constructor/CLI
        override when given (an explicit positive chunk requests chunked
        decode), else the config's pinned ``full_scan`` — mirroring
        ``SequenceGenerator._resolve_chunk``'s precedence."""
        if self.gen_full_scan is not None:
            return bool(self.gen_full_scan)
        if self.gen_decode_chunk:
            return False
        return bool(self.engine.cfg.attrs.get("full_scan", False))

    def gen_allowed_menu(self) -> dict:
        """The warmed generation option menu, carried in closed-menu 400s
        (``serving/errors.py`` wire contract) so clients self-correct."""
        return {"beam_size": [self.gen_beam_size],
                "max_length": [self.gen_max_length]}

    def check_gen_opts(self, beam_size=None, max_length=None):
        """Serving pins ONE (beam_size, max_length) pair at warmup — any
        other pair would be a hot-path compile, so it is inadmissible.
        The 400 names the rejected value AND carries the warmed menu
        (``allowed``) so the client can retry without guessing."""
        if self.engine is None:
            raise BadRequest("this model has no generation group")
        if beam_size is not None and int(beam_size) != self.gen_beam_size:
            raise BadRequest(
                f"beam_size={beam_size} is not the warmed value "
                f"{self.gen_beam_size} (closed shape menu)",
                allowed=self.gen_allowed_menu())
        if (max_length is not None
                and int(max_length) != self.gen_max_length):
            raise BadRequest(
                f"max_length={max_length} is not the warmed value "
                f"{self.gen_max_length} (closed shape menu)",
                allowed=self.gen_allowed_menu())

    def encode_rows(self, rows: List[tuple], lane_valid=None):
        """Run just the encoder over a bucketed batch: rows -> outer
        layer name -> Argument (padded batch). The continuous batcher
        encodes each request ONCE here at admission, then splices the
        result into the live decode state."""
        if self.engine is None:
            raise BadRequest("this model has no generation group")
        feed = self._convert(rows, lane_valid)
        comp = (self._aot.get(("encode", self._bucket_key(feed)[0]))
                if self._aot else None)
        outer = (comp if comp is not None else self._encode)(
            self.params, feed)
        if self.warmed:
            self.check_guards()
        return outer

    def generate_rows(self, rows: List[tuple], lane_valid=None):
        """Beam-search a bucketed batch of encoder inputs. Returns
        ``((tokens, scores, lengths), info)`` — each np, [B, K, ...] over
        the padded batch. Config-pinned beam-control hooks apply (the
        engine reads them from the group attrs). ``info`` carries the
        early-exit accounting: ``decode_steps`` actually executed and
        ``steps_saved`` (= max_length - decode_steps)."""
        if self.engine is None:
            raise BadRequest("this model has no generation group")
        t0 = time.perf_counter()
        feed = self._convert(rows, lane_valid)
        key, padded = self._bucket_key(feed)
        t1 = time.perf_counter()
        enc = self._aot.get(("encode", key))
        outer = (enc if enc is not None else self._encode)(
            self.params, feed)
        comp = self._aot.get(("generate", key))
        if comp is not None:
            # warmed AOT search executable: same program the engine
            # would jit for the pinned (beam, length, chunk, hooks) key
            static_feed = self.engine.static_feed_from_outer(outer)
            tokens, scores, lengths, steps = comp(self.params,
                                                  static_feed)
            steps = int(steps)
            gen_info = {"decode_steps": steps,
                        "steps_saved": self.gen_max_length - steps}
        else:
            tokens, scores, lengths = self.engine.generate(
                self.params, outer, beam_size=self.gen_beam_size,
                max_length=self.gen_max_length,
                decode_chunk=self.gen_decode_chunk,
                full_scan=self.gen_full_scan)
            gen_info = self.engine.last_info
        tokens, scores, lengths = (np.asarray(tokens), np.asarray(scores),
                                   np.asarray(lengths))
        t2 = time.perf_counter()
        if self.warmed:
            # the serving key set is pinned and fully populated at
            # warmup (warmup() ran _ensure_engine_guard) — only the
            # cheap cache-size check belongs on the hot path
            self.check_guards()
        return (tokens, scores, lengths), {
            "bucket": key + f"_k{self.gen_beam_size}",
            "padded_rows": padded,
            "pad_ms": (t1 - t0) * 1e3,
            "compute_ms": (t2 - t1) * 1e3,
            "decode_steps": gen_info.get("decode_steps"),
            "steps_saved": gen_info.get("steps_saved")}

    def build_session(self, width: int):
        """A warmed continuous-batching :class:`DecodeSession` of
        ``width`` lanes (``core/generation.py``): admits one synthetic
        request, runs one chunk, releases it — so the session's three
        device programs (admit / chunk / release) are compiled — then
        brings them under hardened recompile guards. The engine calls
        this from ``start()`` when ``continuous_batching`` is on.

        Returns ``None`` (warn + stand down to convoy batching) when the
        model's static/boot inputs change shape across length buckets —
        a sequence-valued ``StaticInput`` (e.g. seq2seq's encoded
        source) pads to its request's bucket, but a session's lane
        buffers have ONE fixed shape; admitting a larger-bucket request
        would be a trace error surfacing as a spurious per-request 400.
        Fail loudly at startup instead (the closed-menu discipline)."""
        from paddle_tpu.data.prefetch import RecompileGuard
        if self.engine is None:
            raise BadRequest("this model has no generation group")
        outers, shapes = [], set()
        for warm_len in (self.length_buckets or [1]):
            row = tuple(_synth_sample(self.feeding[n], warm_len)
                        for n in self.names)
            outer = self.encode_rows([row])
            feed = self.engine.static_feed_from_outer(outer, row=0)
            shapes.add(tuple(sorted(
                (b, a.value.shape[1:],
                 None if a.mask is None else a.mask.shape[1:])
                for b, a in feed.items())))
            outers.append(outer)
        if len(shapes) > 1:
            logger.warning(
                "continuous batching stood down: this model's "
                "static/boot generation inputs change shape across the "
                "%d warmed length buckets (a sequence-valued "
                "StaticInput pads per bucket), but a decode session's "
                "lane buffers have one fixed shape. Serving falls back "
                "to convoy batching; use a single "
                "--serving_length_buckets entry to enable continuous "
                "batching for this model.", len(self.length_buckets))
            return None
        if self.gen_effective_full_scan():
            # full-scan decode has no chunk boundaries to admit/retire
            # at — continuous batching would silently override the
            # requested policy; refuse loudly instead
            logger.warning(
                "continuous batching stood down: the decode policy is "
                "full_scan (--decode_chunk 0, or pinned in the config) "
                "and a full-length scan has no chunk boundaries to "
                "admit/retire at. Serving falls back to convoy "
                "batching; drop the full-scan override to enable "
                "continuous batching.")
            return None
        sess = self.engine.session(
            self.params, width, beam_size=self.gen_beam_size,
            max_length=self.gen_max_length,
            decode_chunk=self.gen_decode_chunk)
        sess.admit(0, outers[0], row=0)
        sess.run_chunk()
        # the lane-flag reductions and result fetch compile on first
        # use too — pay them here, not inside the first request's decode
        sess.free_lanes()
        sess.finished_lanes()
        sess.peek(0)
        sess.release(0)
        for fn in sess.jitted_fns():
            g = RecompileGuard(fn, name="serving_decode_session")
            g.harden()
            self.guards.append(g)
        return sess

    def _ensure_engine_guard(self):
        from paddle_tpu.data.prefetch import RecompileGuard
        watched = {id(g.fn) for g in self.guards}
        for fn in self.engine._jitted.values():
            if id(fn) not in watched:
                g = RecompileGuard(fn, name="serving_generate")
                g.harden()
                self.guards.append(g)
