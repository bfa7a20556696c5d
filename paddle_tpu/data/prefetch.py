"""Async input pipeline: host data work overlapped with device compute.

The reference dedicates a native double-buffer thread to exactly this —
``PyDataProvider2``'s async pool and ``DataProvider.h:249,343``
(``--use_async_load_data``): while the GPU steps batch N, a host thread
decodes and stages batch N+1. Under JAX the equivalent overlap is a
bounded background-thread pipeline that finishes each batch with a
**sharded ``jax.device_put``** so the H2D copy (and any cross-device
scatter) is already in flight when the trainer asks for the batch; XLA's
async dispatch does the rest (the jitted step for batch N executes while
the host prepares N+1). The feeder hands the worker host arrays and the
worker starts the one transfer: the call returns at once and the copy
runs beside the assembly of the next batch, so a batch costs the worker
the larger of the two, not their sum.

Three pieces:

- :class:`PrefetchPipeline` — wraps any batched reader (PyDP2
  ``@provider`` readers, ProtoData, RecordIO, v2 readers: anything the
  trainer can consume) with decode → pad/bucket (the feeder) → shard →
  ``device_put`` in a worker thread, keeping ``depth`` batches in flight
  (double-buffer default). Bounded queue = backpressure; worker
  exceptions re-raise in the consumer; ``close()`` (or the context
  manager / generator ``close``) shuts the worker down cleanly.
- :class:`LengthBuckets` — the recompile-guard's shape policy: pad
  ragged lengths up to a small fixed set of bucket edges so a ragged
  corpus compiles at most ``len(edges)+1`` step variants instead of one
  per length (the feeder's ``pad_multiple`` ceiling is the degenerate
  single-bucket case). Padding stays exactly ignored because masks are
  f32 count data the layers already honor (``core/argument.py``).
- :class:`RecompileGuard` — a compilation-cache monitor over the jitted
  step: warns (once) when the cache exceeds ``warn_after`` entries, so
  shape thrash is loud instead of silently eating XLA compile time.

The native C++ pool (``native/src/native.cc``, ``ptr_pool_*``) is the
record-level backend of the same bounded-queue interface: it prefetches
raw records off disk; this module prefetches *prepared device batches*.
Stack them freely — reader decorators compose.
"""

from __future__ import annotations

import bisect
import threading
import time
from queue import Empty, Full, Queue
from typing import Callable, Optional, Sequence

from paddle_tpu.obs import flight as _flight
from paddle_tpu.utils.log import get_logger
from paddle_tpu.utils.profiler import StepBreakdown
from paddle_tpu.utils.stat import StatRegistry, global_stat

logger = get_logger("prefetch")

_END = object()


class _Failure:
    """Worker-thread exception, carried through the queue to the consumer."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


# ---------------------------------------------------------------- buckets
class LengthBuckets:
    """Pad-to-bucket policy for ragged sequence lengths.

    ``edges`` is a small ascending set of padded lengths (e.g.
    ``[32, 64, 128, 256]``). A raw max-length pads to the smallest edge
    that holds it; lengths beyond the last edge pad to the next multiple
    of it (so the variant count stays bounded by
    ``len(edges) + ceil(true_max / edges[-1])``, not by the corpus's
    length distribution). This is the TPU answer to the reference's
    ragged ``sequenceStartPositions`` offsets: XLA wants static shapes,
    so shapes come from a fixed menu."""

    def __init__(self, edges: Sequence[int]):
        edges = sorted(int(e) for e in edges)
        if not edges or edges[0] < 1:
            raise ValueError(f"bucket edges must be positive ints: {edges}")
        if len(set(edges)) != len(edges):
            raise ValueError(f"duplicate bucket edges: {edges}")
        self.edges = edges

    def pad_len(self, n: int) -> int:
        """Smallest bucket holding a raw length ``n``."""
        n = max(int(n), 1)
        i = bisect.bisect_left(self.edges, n)
        if i < len(self.edges):
            return self.edges[i]
        last = self.edges[-1]
        return ((n + last - 1) // last) * last

    def __repr__(self):
        return f"LengthBuckets({self.edges})"


# ----------------------------------------------------------- the pipeline
class PrefetchPipeline:
    """Bounded background-thread input pipeline over one pass of data.

    ``reader``: zero-arg callable returning an iterable of raw batches
    (the trainer's usual minibatch reader). ``feeder``: optional
    batch -> feed-dict converter (``DataFeeder`` or any callable) run in
    the worker — this is where decode/pad/bucket cost lives; it returns
    host arrays. ``mesh``: when given, batches go from the host straight
    to each device's shard of the data axis
    (``parallel/mesh.py:shard_batch``); otherwise a plain
    ``jax.device_put`` starts the H2D copy early. ``place=False`` hands
    the feeder's host arrays on as they are. ``depth``: batches in
    flight (2 = the reference's double buffer).

    Iterate it (or call :meth:`get`) to consume; iteration ends at the
    reader's end. A worker exception re-raises at the consumer's next
    pull, after already-prepared batches drain (ordering is preserved —
    a single worker thread feeds a FIFO queue). ``close()`` is
    idempotent and safe mid-stream; the context manager and generator
    ``close`` call it.

    Timing: the worker times each batch's four parts through
    ``breakdown`` (``utils/profiler.py:StepBreakdown``): ``prefetch.read``
    (the reader's ``next``), ``prefetch.decode`` (the feeder: building
    the batch in host memory, nothing else), ``prefetch.h2d`` (the
    ``device_put`` call that starts the copy of those host arrays; on
    the v5e's host it returns in 0.4-0.6 ms for 154 MB and the copy takes
    another 17 ms beside the worker, PERF.md PR 26) and
    ``prefetch.put_wait`` (blocked on the full queue), each a
    ``totals`` key, a ``Stat`` (``prefetch/decode`` ...) and a span that
    carries the batch's sequence number as ``step``. ``SGD.train`` hands
    in its own breakdown, so the trainer's spans for step n sit beside
    the worker's for batch n; a pipeline built elsewhere keeps a private
    one over ``registry``. Consumer-side blocked time accumulates into
    ``prefetch/wait`` and :attr:`data_wait` — the numerator of the
    bench's ``data_wait_frac``.
    """

    def __init__(self, reader: Callable, feeder: Optional[Callable] = None,
                 mesh=None, depth: int = 2,
                 registry: Optional[StatRegistry] = None,
                 place: bool = True,
                 breakdown: Optional[StepBreakdown] = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._reader = reader
        self._feeder = feeder
        self._mesh = mesh
        self._place = place
        self._registry = registry or global_stat
        self._bd = breakdown or StepBreakdown(self._registry)
        self._bd.new_stream()
        self._q: Queue = Queue(maxsize=depth)
        self._stop = threading.Event()
        self._closed = False
        self.depth = depth
        self.data_wait = 0.0  # consumer seconds blocked on the queue
        self.batches = 0
        self._thread = threading.Thread(
            target=self._work, name="prefetch-worker", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- worker
    def _prepare(self, raw, n: int):
        if self._feeder is not None:
            with self._bd.measure("prefetch_decode", n):
                raw = self._feeder(raw)
        if self._place:
            with self._bd.measure("prefetch_h2d", n):
                raw = self._device_put(raw)
        return raw

    def _device_put(self, feed):
        import jax
        if self._mesh is not None:
            from paddle_tpu.parallel import mesh as mesh_lib
            return mesh_lib.shard_batch(feed, self._mesh)
        return jax.device_put(feed)

    def _work(self):
        try:
            source = iter(self._reader())
            n = 0       # the batch's sequence number: the trainer's step
            while not self._stop.is_set():
                with self._bd.measure("prefetch_read", n):
                    raw = next(source, _END)
                if raw is _END:
                    self._put(_END)
                    return
                item = self._prepare(raw, n)
                with self._bd.measure("prefetch_put_wait", n):
                    if not self._put(item):
                        return
                n += 1
        except BaseException as e:  # noqa: BLE001 — crosses the thread
            self._put(_Failure(e))

    def _put(self, item) -> bool:
        """Blocking put that honors close(); False when shut down."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except Full:
                continue
        return False

    # ----------------------------------------------------------- consumer
    def get(self):
        """Next prepared batch; raises StopIteration at end of pass and
        re-raises a worker exception (chained) at its queue position."""
        if self._closed:
            raise StopIteration
        t0 = time.perf_counter()
        item = self._q.get()
        dt = time.perf_counter() - t0
        self.data_wait += dt
        self._registry.get("prefetch/wait").add(dt)
        if item is _END:
            self._closed = True
            raise StopIteration
        if isinstance(item, _Failure):
            self._closed = True
            raise item.exc
        self.batches += 1
        return item

    def __iter__(self):
        try:
            while True:
                try:
                    yield self.get()
                except StopIteration:
                    return
        finally:
            self.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self):
        """Stop the worker and release its blocked put; idempotent."""
        self._closed = True
        self._stop.set()
        # drain so a worker blocked on a full queue sees the stop flag
        while True:
            try:
                self._q.get_nowait()
            except Empty:
                break
        self._thread.join(timeout=5.0)


def prefetch_reader(reader: Callable, feeder: Optional[Callable] = None,
                    mesh=None, depth: int = 2,
                    place: bool = True) -> Callable:
    """Decorator form: wrap a batched reader so each call streams through
    a fresh :class:`PrefetchPipeline`. The result yields *prepared feeds*
    (already through the feeder and, unless ``place=False``, on device),
    so it marks itself ``is_prefetched`` — the trainer skips its own
    feeder/shard step."""

    pass_aware = getattr(reader, "pass_aware", False)

    def prefetched(*args):
        src = (lambda: reader(*args)) if args else reader
        pipe = PrefetchPipeline(src, feeder=feeder, mesh=mesh, depth=depth,
                                place=place)
        return iter(pipe)

    prefetched.is_prefetched = True
    prefetched.pass_aware = pass_aware
    prefetched.input_types = getattr(reader, "input_types", None)
    return prefetched


# ---------------------------------------------------------------- guard
def jit_cache_size(fn) -> Optional[int]:
    """Number of compiled variants a jitted callable holds, or None when
    ``fn`` carries no cache probe (jax 0.9.0's ``jax.jit`` objects do:
    the private ``_cache_size``)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    return int(probe())


class RecompileError(RuntimeError):
    """A hardened :class:`RecompileGuard` saw the jit cache grow — a
    shape escaped the warmed bucket menu and compiled on the hot path."""


class RecompileGuard:
    """Compilation-cache monitor for a jitted step function.

    The XLA failure mode this guards is *silent*: a ragged corpus with
    unbucketed shapes retraces/recompiles the step every batch, and
    training limps along at compile speed with no error anywhere. The
    guard polls the jit cache (``check()`` per step is cheap) and logs
    one loud warning when the variant count passes ``warn_after`` —
    pointing at the bucketing knobs that bound it.

    Serving escalates the warning to a hard error: after AOT warmup has
    compiled every bucket, :meth:`harden` records the cache size as the
    closed set of legal variants and any later growth raises
    :class:`RecompileError` — a stray shape can never pay XLA compile
    time on the request hot path (it is a bug in admission control, not
    a slow request)."""

    def __init__(self, fn, warn_after: int = 8, name: str = "train_step"):
        self.fn = fn
        self.warn_after = int(warn_after)
        self.name = name
        self.warned = False
        self.hard_baseline: Optional[int] = None
        # did the cache grow between the last two checks: the step in
        # between compiled (the first check only takes the baseline)
        self.grew = False
        self._seen: Optional[int] = None

    @property
    def count(self) -> Optional[int]:
        return jit_cache_size(self.fn)

    def harden(self) -> int:
        """Freeze the current variant count as the complete set (serving
        mode, post-warmup); returns it. A callable without the cache
        probe cannot be hardened: that is an error here, never a guard
        that silently stays advisory."""
        n = self.count
        if n is None:
            raise RuntimeError(
                f"{self.name}: no jit-cache probe on {self.fn!r} — a "
                "hardened RecompileGuard over it would never trip. This "
                "jax no longer offers jit._cache_size; repair "
                "data/prefetch.py:jit_cache_size before serving.")
        self.hard_baseline = n
        return n

    def check(self) -> Optional[int]:
        n = self.count
        self.grew = (n is not None and self._seen is not None
                     and n > self._seen)
        self._seen = n
        if (self.hard_baseline is not None and n is not None
                and n > self.hard_baseline):
            if _flight._ACTIVE is not None:
                # a guard trip is exactly the kind of transition a
                # postmortem wants dated: which request/step first
                # escaped the warmed menu
                _flight._ACTIVE.record("recompile_guard_trip",
                                       guard=self.name,
                                       baseline=self.hard_baseline,
                                       count=n)
            raise RecompileError(
                f"{self.name}: jit cache grew {self.hard_baseline} -> {n} "
                "after warmup — a shape outside the warmed bucket menu "
                "compiled on the hot path. Admission control must reject "
                "(or the warmup must cover) that shape.")
        if (n is not None and not self.warned and self.warn_after > 0
                and n > self.warn_after):
            self.warned = True
            if _flight._ACTIVE is not None:
                _flight._ACTIVE.record("recompile_guard_warn",
                                       guard=self.name, count=n)
            logger.warning(
                "%s recompiled %d times — the input shapes are thrashing "
                "XLA's compile cache. Bucket your batch shapes (DataFeeder "
                "length_buckets/batch_buckets, or a coarser pad_multiple) "
                "so a ragged corpus compiles a bounded set of variants.",
                self.name, n)
        return n
