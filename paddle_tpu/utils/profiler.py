"""Host-side step-time breakdown, its spans, and device-memory accounting.

The reference brackets regions with ``hl_profiler_start/end`` +
``GpuProfiler`` (``paddle/utils/Stat.h:282-300``, ``WITH_PROFILER``); the
TPU-native equivalent is a jax profiler session
(``jax.profiler.start_trace`` / ``stop_trace``, or the benchmark's
``--trace 1``): every op lands in a TensorBoard-loadable trace with the
per-layer ``named_scope`` annotations from the graph executor, and every
site of :class:`StepBreakdown` lands beside them as a host span on the
same clock.

:class:`StepBreakdown` is the one span site of the train path: the
trainer's loop and the prefetch thread time each part of a step through
``measure``, which feeds a counter (always), a span in the profiler's
trace (when a session runs) and a span in ``obs.trace``'s buffer (when a
``Tracer`` is armed), so the first-order utilization question — is the
chip waiting on the host, and for which part of it? — is answerable from
whichever of the three is at hand. The trainer feeds it
(``--show_step_breakdown``), the benchmark reads its ``totals``.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import jax

from paddle_tpu.obs import trace as _trace
from paddle_tpu.utils.stat import StatRegistry, global_stat


def _leaf_device_bytes(leaf) -> int:
    """Bytes ONE device holds for an array: the shard size under its
    NamedSharding (a replicated array costs full size per device; a
    ZeRO-1 slot or model-sharded table costs 1/N)."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return 0
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(sharding, "shard_shape"):
        try:
            shape = sharding.shard_shape(tuple(shape))
        except (TypeError, ValueError):
            pass
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def tree_device_bytes(tree) -> int:
    """Per-device bytes of a pytree of (possibly sharded) arrays."""
    return sum(_leaf_device_bytes(x)
               for x in jax.tree_util.tree_leaves(tree))


def device_peak_bytes():
    """Device-reported peak allocation (TPU/GPU ``memory_stats``).

    Returns ``None`` — NOT 0 — on backends that don't expose the
    counter (XLA:CPU among them, so every CPU run): ``None``
    means "unmeasured", and treating it as 0 would make a CPU dryrun
    look like it fits any admission budget. Callers must branch on
    ``is None`` (``memory_stats`` omits the key entirely in that
    case)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — absent on some backends
        return None
    if not stats:
        return None
    return stats.get("peak_bytes_in_use")


def memory_stats(params, opt_state=None, activations=None,
                 temp_estimator=None, gather_peak=None) -> dict:
    """Per-device memory accounting for the training state. The
    bench's ``--zero1`` A/B, ``--show_step_breakdown``, and graftlint
    pass 5 (PT605 reconciles the compiled manifest against this exact
    accounting) all read it, so the return schema is a contract:

    - ``param_bytes_per_device`` (always) — parameter bytes one
      device holds under the leaves' shardings (an FSDP run's packed
      ``(N, chunk)`` leaves carry ``P(fsdp)``, so the ~1/N drop reads
      straight off the real placement — no special case).
    - ``slot_bytes_per_device`` (when ``opt_state`` is a dict) —
      optimizer-slot bytes (``opt_state["slots"]``; the quantity
      ZeRO-1 divides by the data-parallel degree).
    - ``avg_bytes_per_device`` (when ``opt_state`` carries ``avg``) —
      model-averaging shadow bytes.
    - ``act_bytes_per_device`` (when ``activations`` is given) —
      bytes of a representative input batch / activation pytree, the
      live-input side of the serving admission number.
    - ``temp_bytes_per_device`` (when ``temp_estimator`` is given and
      returns a number) — XLA scratch estimate for the compiled step;
      pass e.g. ``lambda: compiled.memory_analysis()
      .temp_size_in_bytes`` so admission can account scratch without
      this module importing the executable.
    - ``device_peak_bytes`` (only when the backend reports one) — the
      device's peak allocation; ABSENT on XLA:CPU (see
      ``device_peak_bytes`` — None/absent means unmeasured, never 0).
    - ``gathered_peak_bytes_per_device`` (when ``gather_peak`` is
      given) — the FSDP transient gathered-buffer peak: ONE layer's
      full parameter under the sync gather spelling, the largest
      adjacent schedule PAIR under overlap (two layers live while the
      next gather flies behind the current compute) — pass
      ``FsdpUpdater.gather_peak_bytes()`` so this report and the
      compiled truth agree under ``--fsdp_overlap``.
    """
    out = {"param_bytes_per_device": tree_device_bytes(params)}
    if opt_state is not None and isinstance(opt_state, dict):
        out["slot_bytes_per_device"] = tree_device_bytes(
            opt_state.get("slots", {}))
        if "avg" in opt_state:
            out["avg_bytes_per_device"] = tree_device_bytes(opt_state["avg"])
    if activations is not None:
        out["act_bytes_per_device"] = tree_device_bytes(activations)
    if temp_estimator is not None:
        temp = temp_estimator()
        if temp is not None:
            out["temp_bytes_per_device"] = int(temp)
    if gather_peak is not None:
        out["gathered_peak_bytes_per_device"] = int(gather_peak)
    peak = device_peak_bytes()
    if peak is not None:
        out["device_peak_bytes"] = int(peak)
    return out


def _fmt_bytes(v: int) -> str:
    return f"{v / 1e6:.2f}MB" if v >= 1e5 else f"{v / 1e3:.2f}KB"


def memory_status(params, opt_state=None, gather_peak=None) -> str:
    s = memory_stats(params, opt_state, gather_peak=gather_peak)
    parts = " ".join(f"{k.replace('_bytes_per_device', '')}="
                     f"{_fmt_bytes(v)}" for k, v in s.items()
                     if k.endswith("_bytes_per_device"))
    if "device_peak_bytes" in s:
        parts += f" peak={_fmt_bytes(s['device_peak_bytes'])}"
    return f"DeviceMemory(per-device): {parts}"


def pipeline_bubble_stats(n_stages: int, n_microbatches: int) -> dict:
    """GPipe schedule occupancy accounting (``parallel/pipeline.py``).

    The fill-drain schedule runs ``S + M - 1`` ticks; stage ``s`` computes
    a real microbatch on M of them and idles ``s`` ticks while the pipe
    fills plus ``S - 1 - s`` while it drains — so every stage idles
    exactly ``S - 1`` microbatch slots of the ``S + M - 1`` total, and the
    per-stage bubble fraction (idle slots / total slots) is the classic
    ``(S-1)/(S+M-1)``, uniform across stages. The backward pipeline
    (``jax.grad`` of the scan) replays the drain in reverse, doubling both
    numerator and denominator — the fraction is unchanged, which is why
    one number serves the whole step."""
    S, M = int(n_stages), int(n_microbatches)
    ticks = S + M - 1
    # per-stage idle is s (fill) + S-1-s (drain) = S-1 for EVERY stage:
    # the per-stage list is uniform by construction, kept as a list so
    # bench consumers get one entry per stage
    per_stage = [(S - 1) / ticks] * S
    return {
        "pipeline_stages": S,
        "pipeline_microbatches": M,
        "pipeline_ticks": ticks,
        "pipeline_bubble_frac": (S - 1) / ticks,
        "pipeline_bubble_frac_per_stage": per_stage,
    }


def fsdp_overlap_stats(n_gathers: int, overlap: bool) -> dict:
    """FSDP exposed-communication accounting (``optim/zero1.py:
    FsdpUpdater``), the collective-plane analogue of
    ``pipeline_bubble_stats``.

    The step issues one all-gather per planned parameter on the forward
    and one reduce-scatter (the gather's transpose) on the backward —
    ``2L`` collectives for ``L = n_gathers``. Under the sync spelling
    every one of them sits exposed on the critical path. Under the
    double-buffer chain (``full_params`` overlap spelling) gather k+1
    flies behind layer k's compute and reduce-scatter k-1 behind layer
    k's backward, so only the FIRST forward gather (nothing to hide it
    behind) and the LAST backward reduce-scatter (its producer is the
    final backward op) stay exposed — 2 of 2L, the double-buffering
    steady state. Analytic by construction, like the pipeline bubble:
    the 1-core CPU host can't measure real collective/compute overlap,
    and on TPU the schedule, not the wall clock, is the contract."""
    L = int(n_gathers)
    exposed = (2 if L else 0) if overlap else 2 * L
    return {
        "fsdp_gathers_per_step": L,
        "fsdp_overlap": bool(overlap),
        "fsdp_exposed_collectives": exposed,
        "fsdp_exposed_comm_frac": (exposed / (2 * L)) if L else 0.0,
    }


# part -> (span name, Stat name). The span name is what the profiler's
# trace and the Tracer's buffer show; its prefix tells the thread (both
# host lines of a trace are called "python"). The Stat is what the
# ``log_period`` dump prints: the reference's own timer names where it
# had one (``prepareBatchData``, ``trainBatch``: ``TrainerInternal.cpp``).
# ``compute`` is a counter with no span of its own: dispatch's and
# device_wait's cover it.
SITES = {
    "data_wait": ("train.data_wait", "step/data_wait"),
    "h2d": ("train.h2d", "prepareBatchData"),
    "dispatch": ("train.dispatch", "step/dispatch"),
    "device_wait": ("train.device_wait", "step/device_wait"),
    "compute": (None, "trainBatch"),
    "callback": ("train.callback", "step/callback"),
    "prefetch_read": ("prefetch.read", "prefetch/read"),
    "prefetch_decode": ("prefetch.decode", "prefetch/decode"),
    "prefetch_h2d": ("prefetch.h2d", "prefetch/h2d"),
    "prefetch_put_wait": ("prefetch.put_wait", "prefetch/put_wait"),
}

# perf_counter -> wall clock, for the ``ts`` of a Tracer span: one
# offset for the process, so a site reads one clock, once at each end
_EPOCH = time.time() - time.perf_counter()


class _Site:
    """One timed site: ``with bd.measure(part): ...``. A class, not a
    generator, because it runs ten times a step."""

    __slots__ = ("bd", "part", "name", "step", "t0", "seconds", "_ann")

    def __init__(self, bd, part, step):
        self.bd, self.part, self.step = bd, part, step
        self.name = SITES[part][0]
        self.seconds = 0.0

    def start(self):
        # records nothing (and reads no clock) with no profiler session
        self._ann = jax.profiler.TraceAnnotation(self.name, step=self.step)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def stop(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        self._ann.__exit__(None, None, None)
        bd = self.bd
        bd.add(self.part, self.seconds)
        if _trace._TRACER is not None:
            bd._span(self.name, self.step, self.t0, self.seconds)

    # ``with`` where the bracket is a block; start/stop where it spans
    # most of a loop's body (a bracket left open by an exception is lost
    # with its step: ``step_abandon``)
    __enter__, __exit__ = start, stop


class StepBreakdown:
    """Per-step host-side wall-time split, and the train path's span site.

    Parts timed on the trainer's thread (span ``train.<part>``):

    - ``data_wait`` — blocked pulling the next batch (the reader's own
      cost when synchronous; queue-wait when the async pipeline runs —
      near zero once prefetch keeps up).
    - ``h2d``      — feed conversion + device placement done on the
      trainer thread (``prepareBatchData``): the feeder builds host
      arrays and the bracket holds the ``device_put`` (or
      ``shard_batch``) that places them; with prefetch on this moves
      into the worker and the trainer-side number collapses.
    - ``dispatch`` — from the feed in hand to the jitted step's return:
      the rng split, the step's scalars, the jit call's argument
      handling and enqueue. The device may already be running.
    - ``device_wait`` — the host read of the cost alone: the trainer's
      thread blocked on the device.
    - ``compute``  — the jitted step's call through the cost fetch, the
      bracket the reference calls ``trainBatch``: the tail of
      ``dispatch`` plus ``device_wait``. A counter, no span.
    - ``callback`` — host evaluators, event handlers, periodic logging.

    Parts timed on the prefetch thread (span ``prefetch.<part>``, key
    ``prefetch_<part>``), concurrent with the trainer's and so outside
    every sum over a step: ``read`` (the reader's ``next``), ``decode``
    (the feeder building the batch in host memory; it places nothing),
    ``h2d`` (the ``device_put`` *call* on those host arrays: measured on
    the v5e's host it returns in 0.4-0.6 ms for a 154 MB batch, whose
    copy then runs 17 ms beside the worker's next batch; a copy still
    under way when its step starts would show as that step's
    ``device_wait``) and ``put_wait`` (blocked on the full queue: the
    room the pipeline has over the trainer).

    ``measure(part)`` does three things with one pair of clock reads:
    adds the seconds to ``totals[part]`` and the part's ``Stat`` (so the
    ``log_period`` dump shows the same numbers); runs the body under
    ``jax.profiler.TraceAnnotation(<span>, step=n)``, which lands on the
    host line of a profiler session's trace, on the clock of the
    device's ``XLA Ops``, and records nothing without a session; and,
    only when ``obs.trace`` has a ``Tracer`` armed
    (``$PADDLE_TPU_TRACE_DIR``), keeps the span until the step is done
    and then records it under that step's ``train.step`` span, one trace
    per step. ``n`` is the batch's sequence number in its pass: the
    prefetch thread's spans for batch n and the trainer's for step n
    carry the same ``step``. Spans of a step that never finished are
    dropped, never left with a dangling parent.
    """

    # the four that partition a step; the other keys of ``totals`` are
    # finer or concurrent brackets and stay outside ``total``
    PARTS = ("data_wait", "h2d", "compute", "callback")
    PENDING_STEPS = 256     # steps whose spans the Tracer's sink holds

    def __init__(self, registry: StatRegistry = None):
        self.registry = registry or global_stat
        # a registry's reset() zeroes its Stats in place, so they can be
        # looked up once
        self._stats = {part: self.registry.get(stat)
                       for part, (_span, stat) in SITES.items()}
        # Tracer sink only: spans waiting for their step to finish
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        self.steps = 0
        self.wall = 0.0  # true per-step wall time, when the caller times it
        # every key is here from the start: a reader that snapshots the
        # totals (the benchmark's window) may subtract any of them
        self.totals = dict.fromkeys(SITES, 0.0)
        # most recent single measurement per part: the health plane's
        # per-step timeline reads {data_wait, compute} from here
        # without having to delta the cumulative totals
        self.last = dict(self.totals)
        self._step = None   # the open step: (n, t0, annotations, attrs)
        self.new_stream()
        # set by SGD.enable_pipeline; reset() survives it (a pass reset
        # must not silently drop the schedule identity from summaries)
        if not hasattr(self, "pipeline"):
            self.pipeline = None
        # set by SGD.enable_fsdp; survives reset() like the pipeline
        if not hasattr(self, "fsdp"):
            self.fsdp = None

    def set_pipeline(self, n_stages: int, n_microbatches: int):
        """Record the active GPipe schedule so ``summary()`` carries the
        bubble-fraction estimate next to steps/s (None disables)."""
        self.pipeline = ((int(n_stages), int(n_microbatches))
                         if n_stages else None)

    def set_fsdp(self, n_gathers: int, overlap: bool):
        """Record the active FSDP gather plan so ``summary()`` carries
        the exposed-comm estimate (``fsdp_overlap_stats``) next to
        steps/s (0 gathers disables)."""
        self.fsdp = ((int(n_gathers), bool(overlap))
                     if n_gathers else None)

    # ------------------------------------------------------------ sites
    def add(self, part: str, seconds: float):
        self.totals[part] += seconds
        self.last[part] = seconds
        self._stats[part].add(seconds)

    def add_counters(self, counters):
        """What the layers counted in the open step, by name (a layer
        reports through its output's ``state["counters"]``; the step
        hands back each name's mean over the layers that report it):
        summed into ``totals`` and, with a ``Tracer`` armed, attributes
        of the step's span."""
        counters = {name: float(v) for name, v in counters.items()}
        for name, value in counters.items():
            self.totals[name] = self.totals.get(name, 0.0) + value
        if _trace._TRACER is not None and self._step is not None:
            self._step[3].update(counters)

    def measure(self, part: str, step: Optional[int] = None) -> _Site:
        """Time one part of step ``step`` (default: the open step)."""
        if step is None and self._step is not None:
            step = self._step[0]
        return _Site(self, part, step)

    def step_begin(self, n: int):
        """Open step ``n`` of the pass: ``train.step`` in the traces,
        under a ``StepTraceAnnotation`` so that TensorBoard's step
        analysis finds it."""
        anns = (jax.profiler.StepTraceAnnotation("train", step_num=n),
                jax.profiler.TraceAnnotation("train.step", step=n))
        for a in anns:
            a.__enter__()
        self._step = (n, time.perf_counter(), anns, {})

    def mark_step(self, **attrs):
        """Attributes of the open step's span (``recompiled=True``)."""
        self._step[3].update(attrs)
        self._step[2][1].set_metadata(**attrs)

    def step_done(self):
        """Count the open step as finished. Its true wall time, since
        ``step_begin``, is the denominator of throughput and fractions —
        work outside the measured brackets then shows up as a shortfall
        from 1.0 instead of silently inflating steps/s."""
        n, t0, _anns, attrs = self._close_step()
        wall = time.perf_counter() - t0
        self.steps += 1
        self.wall += wall
        tracer = _trace._TRACER
        if tracer is not None:
            self._flush(tracer, n, t0, wall, attrs)

    def step_abandon(self):
        """Close an open step without counting it (end of pass found, or
        the step raised); its spans are dropped. Idempotent."""
        step = self._close_step()
        if step is not None and _trace._TRACER is not None:
            with self._lock:
                self._pending.pop(step[0], None)

    def _close_step(self):
        step, self._step = self._step, None
        if step is not None:
            for a in reversed(step[2]):
                a.__exit__(None, None, None)
        return step

    # ------------------------------------------------- the Tracer's sink
    def new_stream(self):
        """A new stream numbers its batches from 0 (each pass's
        ``PrefetchPipeline`` calls this): spans the last one left
        waiting belong to steps that never finished, and go."""
        with self._lock:
            self._pending = {}      # step -> [(name, t0, seconds)]
            self._flushed = None    # (step, trace_id, span_id), the last

    def _span(self, name, step, t0, seconds):
        """Keep a finished span until its step is done. The prefetch
        thread's ``put_wait`` for batch n can end just after step n
        did: that one goes straight under the step's recorded span."""
        with self._lock:
            late = self._flushed
            if late is None or late[0] != step:
                late = None
                self._pending.setdefault(step, []).append(
                    (name, t0, seconds))
                if len(self._pending) > self.PENDING_STEPS:
                    # steps nobody finishes (batches skipped on a
                    # resume, a pipeline with no trainer behind it)
                    del self._pending[next(iter(self._pending))]
        tracer = _trace._TRACER
        if late is not None and tracer is not None:
            self._emit(tracer, late[1], late[2], name, step, t0, seconds)

    @staticmethod
    def _emit(tracer, trace_id, parent_id, name, step, t0, seconds):
        tracer.record_span(name, trace_id=trace_id, parent_id=parent_id,
                           ts=_EPOCH + t0, dur_ms=1e3 * seconds, step=step)

    def _flush(self, tracer, n, t0, wall, attrs):
        """Record step ``n``: its children first, so that the bounded
        buffer never evicts a parent before its child."""
        trace_id, span_id = _trace.new_trace_id(), _trace.new_span_id()
        with self._lock:
            spans = self._pending.pop(n, ())
            self._flushed = (n, trace_id, span_id)
        for name, s0, seconds in spans:
            self._emit(tracer, trace_id, span_id, name, n, s0, seconds)
        tracer.record("train.step",
                      _trace.TraceContext(trace_id, span_id, None),
                      ts=_EPOCH + t0, dur_ms=1e3 * wall, step=n, **attrs)

    # ---------------------------------------------------------- readings
    @property
    def total(self) -> float:
        return self.wall if self.wall > 0 else sum(
            self.totals[p] for p in self.PARTS)

    def summary(self) -> dict:
        total = self.total
        out = {"steps": self.steps,
               "steps_per_sec": (self.steps / total) if total > 0 else 0.0}
        for p in self.totals:
            out[f"{p}_frac"] = (self.totals[p] / total) if total > 0 else 0.0
            out[f"{p}_ms_per_step"] = (
                1e3 * self.totals[p] / self.steps if self.steps else 0.0)
        if self.pipeline is not None:
            out.update(pipeline_bubble_stats(*self.pipeline))
        if self.fsdp is not None:
            out.update(fsdp_overlap_stats(*self.fsdp))
        return out

    def status(self) -> str:
        s = self.summary()
        parts = " ".join(
            f"{p}={s[f'{p}_ms_per_step']:.2f}ms({s[f'{p}_frac'] * 100:.1f}%)"
            for p in self.totals)
        pipe = ""
        if self.pipeline is not None:
            pipe = (f" pipeline=S{s['pipeline_stages']}/M"
                    f"{s['pipeline_microbatches']}"
                    f" bubble={s['pipeline_bubble_frac'] * 100:.1f}%")
        if self.fsdp is not None:
            pipe += (f" fsdp_gathers={s['fsdp_gathers_per_step']}"
                     f" overlap={'on' if s['fsdp_overlap'] else 'off'}"
                     f" exposed_comm="
                     f"{s['fsdp_exposed_comm_frac'] * 100:.1f}%")
        return (f"StepBreakdown: steps={self.steps} "
                f"steps/s={s['steps_per_sec']:.3f} {parts}{pipe}")
