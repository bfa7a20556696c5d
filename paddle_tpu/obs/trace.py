"""Distributed tracing: TraceContext propagation + bounded span buffer.

One request (or one training-side RPC) gets ONE trace. The context is
three ids — ``trace_id`` names the request end to end, ``span_id``
names the current operation, ``parent_id`` links it under its caller —
carried across process boundaries as an ``X-Trace-Id: <trace>-<span>``
header (traceparent-style, minus flags) on the serving HTTP plane and
as a ``trace`` envelope field on the master RPC codec.

Span taxonomy (``docs/observability.md`` is the catalog):

- ``client.request``       — one ServingClient HTTP attempt (the root
  span of a serving trace; its wall time IS the client-observed
  latency, which the replica-side children must reconstruct).
- ``router.dispatch``      — the router's whole routing decision.
- ``router.attempt``       — ONE attempt at ONE replica (attrs:
  ``replica``, ``outcome``, ``hedge``). A failover is two sibling
  attempts under one dispatch; a hedge is a sibling with
  ``hedge=True``.
- ``replica.score`` / ``replica.generate`` — one request's life inside
  a replica engine (enqueue → answer), with the four phase children
  ``phase.queue_wait`` / ``phase.pad_overhead`` / ``phase.compute`` /
  ``phase.decode`` synthesized from the batcher's timing split (they
  partition the parent by construction).
- ``rpc.<method>`` / ``rpc.server.<method>`` — one master RPC exchange
  as seen by the trainer client / the master handler (get_task,
  task_finished, heartbeat, commit_tasks, ...).

Zero-cost discipline: recording guards on the module global
``_TRACER`` (None == off). Context/id *generation* is NOT gated — the
``X-Trace-Id`` echo contract needs ids whether or not anyone records —
but it is plain ``os.urandom`` string work (its cost on the chip:
not measured).

Buffers are bounded (deque, default 4096 spans; evictions counted in
``Tracer.dropped``); ``dump_jsonl`` writes spans sorted by wall-clock
start so the TRACE_* artifact schema (PT401) can require monotone
timestamps and resolvable parent refs.
"""

from __future__ import annotations

import atexit
import contextvars
import json
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Iterator, List, Optional

HEADER = "X-Trace-Id"
ENV_DIR = "PADDLE_TPU_TRACE_DIR"

# the one global the hook sites poll; None == tracing disabled
_TRACER: Optional["Tracer"] = None

# the ambient context of the CURRENT logical operation (per thread /
# task): set by span() and use(); read by child sites and by the
# structured log formatter (utils/log.py) to stamp records
_CTX: contextvars.ContextVar[Optional["TraceContext"]] = \
    contextvars.ContextVar("paddle_tpu_trace_ctx", default=None)


def new_trace_id() -> str:
    return uuid.uuid4().hex  # 32 hex chars


def new_span_id() -> str:
    return os.urandom(8).hex()  # 16 hex chars


class TraceContext:
    """(trace_id, span_id, parent_id) — the unit of propagation."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id

    def to_header(self) -> str:
        return f"{self.trace_id}-{self.span_id}"

    def __repr__(self):
        return (f"TraceContext({self.trace_id[:8]}…, {self.span_id}, "
                f"parent={self.parent_id})")

    @classmethod
    def from_header(cls, value: Optional[str]
                    ) -> Optional["TraceContext"]:
        """Parse ``<trace>-<span>`` (or a bare trace id). None on a
        missing/garbled header — the receiver then roots a fresh
        trace, so a malformed header can never 500 a request."""
        if not value:
            return None
        tid, _, sid = str(value).strip().partition("-")
        if not tid or any(c not in "0123456789abcdef"
                          for c in tid.lower()):
            return None
        return cls(tid.lower(), (sid or new_span_id()).lower())


def child(parent: Optional[TraceContext]) -> TraceContext:
    """A new context under ``parent`` (same trace, fresh span), or a
    fresh ROOT context when there is nothing to parent under."""
    if parent is None:
        return TraceContext(new_trace_id(), new_span_id(), None)
    return TraceContext(parent.trace_id, new_span_id(), parent.span_id)


def current() -> Optional[TraceContext]:
    return _CTX.get()


def ctx_from_headers(headers) -> TraceContext:
    """The receiver-side context for one HTTP request: the sender's
    context parsed from ``X-Trace-Id``, or a fresh root when the caller
    sent none (the server then NAMES the trace — the echo contract
    needs a trace id on every response)."""
    ctx = TraceContext.from_header(
        headers.get(HEADER) if headers is not None else None)
    return ctx if ctx is not None else child(None)


@contextmanager
def use(ctx: Optional[TraceContext]) -> Iterator[Optional[TraceContext]]:
    """Scope the ambient context (no span recorded): transports use
    this to hand the per-attempt context to duck-typed callees without
    widening their signatures."""
    tok = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(tok)


@contextmanager
def span(name: str, parent: Optional[TraceContext] = None,
         **attrs) -> Iterator[TraceContext]:
    """One timed span. Yields the span's OWN context (propagate it to
    children / remote callees); records into the installed tracer on
    exit (status "error" when the body raises). With no tracer
    installed the context still flows — only the record is skipped."""
    ctx = child(parent if parent is not None else _CTX.get())
    tok = _CTX.set(ctx)
    ts = time.time()
    t0 = time.perf_counter()
    status = "ok"
    try:
        yield ctx
    except BaseException:
        status = "error"
        raise
    finally:
        _CTX.reset(tok)
        tracer = _TRACER
        if tracer is not None:
            tracer.record(name, ctx, ts=ts,
                          dur_ms=1e3 * (time.perf_counter() - t0),
                          status=status, **attrs)


class Tracer:
    """Bounded in-process span buffer + JSONL export.

    Lock discipline (graftlint pass-3 scope): the tracer lock guards
    the deque append/snapshot ONLY — record() builds its dict outside
    and calls nothing while holding it, so the lock is pinned
    edge-free in the static lock graph."""

    def __init__(self, service: str = "", buffer: int = 4096):
        self.service = str(service)
        self.pid = os.getpid()
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=int(buffer))
        self.dropped = 0

    # ------------------------------------------------------------ record
    def record(self, name: str, ctx: TraceContext, *, ts: float,
               dur_ms: float, status: str = "ok", **attrs):
        """Append one completed span (span() calls this; synthesized
        spans — the batcher's phase split — call record_span)."""
        rec = {"trace_id": ctx.trace_id, "span_id": ctx.span_id,
               "parent_id": ctx.parent_id, "name": name,
               "service": self.service, "pid": self.pid,
               "ts": round(ts, 6), "dur_ms": round(max(0.0, dur_ms), 4),
               "status": status}
        if attrs:
            rec["attrs"] = {k: v for k, v in attrs.items()
                            if v is not None}
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(rec)

    def record_span(self, name: str, *, trace_id: str,
                    parent_id: Optional[str], ts: float, dur_ms: float,
                    status: str = "ok", **attrs) -> str:
        """Record a span that was never a live context manager — e.g.
        the four phase children the batcher reconstructs from its
        timing split after a request is answered. Returns the new
        span_id so callers can chain children under it."""
        sid = new_span_id()
        self.record(name, TraceContext(trace_id, sid, parent_id),
                    ts=ts, dur_ms=dur_ms, status=status, **attrs)
        return sid

    # ------------------------------------------------------------ export
    def spans(self, trace_id: Optional[str] = None) -> List[dict]:
        with self._lock:
            out = list(self._spans)
        if trace_id is not None:
            out = [s for s in out if s["trace_id"] == trace_id]
        return sorted(out, key=lambda s: s["ts"])

    def clear(self):
        with self._lock:
            self._spans.clear()

    def dump_jsonl(self, path: Optional[str] = None,
                   trace_id: Optional[str] = None) -> Optional[str]:
        """Write the buffer (sorted by start time — the TRACE_* schema
        requires monotone file order) as one span per line. Default
        path: ``$PADDLE_TPU_TRACE_DIR/trace-<service>-<pid>.jsonl``;
        None (and no env dir) skips quietly so atexit can always call
        this."""
        if path is None:
            d = os.environ.get(ENV_DIR, "")
            if not d:
                return None
            os.makedirs(d, exist_ok=True)
            path = os.path.join(
                d, f"trace-{self.service or 'proc'}-{self.pid}.jsonl")
        spans = self.spans(trace_id)
        with open(path, "w", encoding="utf-8") as f:
            for s in spans:
                f.write(json.dumps(s) + "\n")
        return path


# ------------------------------------------------------------- install

def install(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Make ``tracer`` the active tracer (None disables recording)."""
    global _TRACER
    _TRACER = tracer
    return tracer


def active() -> Optional[Tracer]:
    return _TRACER


def arm_from_env(service: str) -> Optional[Tracer]:
    """Install a tracer (and an atexit JSONL dump) when
    ``$PADDLE_TPU_TRACE_DIR`` is set; no-op otherwise."""
    if not os.environ.get(ENV_DIR, ""):
        return None
    tracer = install(Tracer(service))

    def _dump_quietly(t=tracer):
        # a full/unwritable $PADDLE_TPU_TRACE_DIR must not turn a
        # clean exit into an atexit traceback (flight.py contract)
        try:
            t.dump_jsonl()
        except Exception:  # noqa: BLE001
            pass

    atexit.register(_dump_quietly)
    return tracer
