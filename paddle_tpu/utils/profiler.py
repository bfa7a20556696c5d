"""Device profiling bracket + host-side step-time breakdown.

The reference brackets regions with ``hl_profiler_start/end`` +
``GpuProfiler`` (``paddle/utils/Stat.h:282-300``, ``WITH_PROFILER``); the
TPU-native equivalent is a jax profiler trace: every op inside the bracket
lands in a TensorBoard-loadable trace with the per-layer ``named_scope``
annotations from the graph executor.

:class:`StepBreakdown` is the coarse host-side complement: per-step wall
time split into {data-wait, h2d, compute, callback} so the first-order
utilization question — is the chip waiting on the host? — is answerable
without a trace. The trainer feeds it (``--show_step_breakdown``), the
bench emits its summary as the CPU-side input-pipeline metric.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import jax

from paddle_tpu.utils.stat import StatRegistry, global_stat


@contextmanager
def profiler_trace(log_dir: str):
    """``with profiler_trace("/tmp/trace"): step()`` — the
    ``REGISTER_GPU_PROFILER`` bracket."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


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


class StepBreakdown:
    """Per-step host-side wall-time split.

    Parts:

    - ``data_wait`` — blocked pulling the next batch (the reader's own
      cost when synchronous; queue-wait when the async pipeline runs —
      near zero once prefetch keeps up).
    - ``h2d``      — feed conversion + device placement done on the
      trainer thread (``prepareBatchData``); with prefetch on this moves
      into the worker (``prefetch/decode`` / ``prefetch/h2d`` stats) and
      the trainer-side number collapses.
    - ``compute``  — step dispatch through the device fetch
      (``block_until_ready``-equivalent: a host read of the cost).
    - ``callback`` — host evaluators, event handlers, periodic logging.

    Every ``add`` also lands in the stat registry (``step/<part>``) so
    the existing ``log_period`` dump shows the same numbers. ``summary``
    yields the bench metrics: ``steps_per_sec`` and ``data_wait_frac``.
    """

    PARTS = ("data_wait", "h2d", "compute", "callback")

    def __init__(self, registry: StatRegistry = None):
        self.registry = registry or global_stat
        self.reset()

    def reset(self):
        self.steps = 0
        self.wall = 0.0  # true per-step wall time, when the caller times it
        self.totals = {p: 0.0 for p in self.PARTS}
        # most recent single measurement per part: the health plane's
        # per-step timeline reads {data_wait, compute} from here
        # without having to delta the cumulative totals
        self.last = {p: 0.0 for p in self.PARTS}
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

    def add(self, part: str, seconds: float):
        self.totals[part] += seconds
        self.last[part] = seconds
        self.registry.get(f"step/{part}").add(seconds)

    @contextmanager
    def measure(self, part: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(part, time.perf_counter() - t0)

    def step_done(self, wall_seconds: float = None):
        """Count a finished step; pass the step's true wall time so
        throughput and fractions use it as the denominator — work outside
        the four measured brackets then shows up as a shortfall from 1.0
        instead of silently inflating steps/s."""
        self.steps += 1
        if wall_seconds is not None:
            self.wall += wall_seconds

    @property
    def total(self) -> float:
        return self.wall if self.wall > 0 else sum(self.totals.values())

    def summary(self) -> dict:
        total = self.total
        out = {"steps": self.steps,
               "steps_per_sec": (self.steps / total) if total > 0 else 0.0}
        for p in self.PARTS:
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
            for p in self.PARTS)
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
