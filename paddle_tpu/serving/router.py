"""Front-tier replica router: health-aware failover, circuit breakers,
hedged score retries, respawn, and rolling hot-swap reload.

One serving process owns one device; a fleet needs N replicas behind a
tier that (a) never routes to a replica that cannot answer, (b) turns a
replica dying mid-request into a retry the client never sees, and (c)
can swap model versions without dropping a single queued request. This
module is that tier, over two replica transports:

- :class:`EngineTransport` — an in-process :class:`~paddle_tpu.serving.
  batcher.ServingEngine` (the ``--job=serve --replicas N`` shape: N
  engines, one process, each with its own predictor warmed from the
  shared AOT cache).
- :class:`HTTPTransport` — a separately-launched single-replica server
  reached over HTTP (the multi-process / multi-host shape; pass a
  ``proc`` handle and drain uses the real SIGTERM machinery).

Dispatch policy (one request through :meth:`ReplicaRouter.dispatch`):

- **pick** — least-inflight READY replica (round-robin tiebreak);
  WARMING / DRAINING / EJECTED / DEAD replicas are never candidates, so
  ``begin_drain()`` stops new traffic at the router, not at the
  replica's refused-request surface.
- **failover** — a *definite* replica failure (connection error,
  worker-died 500, an injected ``route_dispatch`` drop) re-dispatches
  the request to the next replica: serving is stateless, so re-running
  is safe for both kinds. A replica's 429 shed is "busy, not broken":
  the router tries the next replica without charging the breaker, and
  only when EVERY ready replica sheds does the client see a 429 — with
  ``retry_after_ms`` set to the FLEET-wide capacity estimate (the min
  over replica drain hints: a request needs one free slot and queues
  drain in parallel), not one replica's private EWMA.
- **hedging** — idempotent ``score`` requests past ``hedge_ms`` with no
  answer fire a capped second attempt at another replica; first answer
  wins, the loser's compute is sunk (and still scored for breaker
  accounting when it completes). NEVER for ``generate``: a speculative
  duplicate of a long beam search is the one workload where hedging
  costs more capacity than it saves.
- **circuit breaker** — ``eject_after`` consecutive failures opens the
  replica's breaker (EJECTED, no dispatch) for ``breaker_cooldown_ms``;
  the health loop then HALF-OPENs it with a single probe — success
  closes the breaker, failure re-opens it with doubled cooldown
  (capped), so a flapping replica converges to rare probes instead of
  eating live traffic.
- **typed 4xx/504 pass through** — a BadRequest or DeadlineExceeded is
  the CLIENT's outcome from a healthy replica; it is never failed over
  (the retry would fail identically) and never charges the breaker.

The health loop polls every replica's readiness (``/healthz`` payload /
``ServingEngine.health()``) on ``health_poll_ms``; a replica whose
worker died (liveness false) is DEAD and — when a ``spawn`` factory is
configured — respawned in place (chaos site ``replica_spawn``). With the
AOT warmup cache a respawned replica deserializes its whole bucket menu
instead of re-tracing it, which is what keeps kill-and-respawn under
load from dropping requests (``tests/test_serving_fleet.py``).

Rolling reload (:meth:`ReplicaRouter.rolling_reload`) hot-swaps model
versions replica by replica: mark DRAINING (router dispatch stops
immediately), drain through the existing SIGTERM machinery (every queued
request completes — zero drops by construction), swap in the new
version's transport, wait READY, next. The fleet serves mixed versions
mid-roll by design; ``/healthz`` reports each replica's
``model_version``.

Lock discipline (graftlint pass-3 scope): the router lock guards replica
state bookkeeping ONLY — dispatch, transport calls, chaos hits, and
metrics all happen outside it, so the router adds no lock-order edges
over the engine/metrics graph.
"""

from __future__ import annotations

import threading
import time
from http.server import ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple

from paddle_tpu.obs import flight as _flight
from paddle_tpu.obs import trace as _trace
from paddle_tpu.serving.errors import (BadRequest, ConfigRejected,
                                       DeadlineExceeded, Overloaded,
                                       ServingError, ShuttingDown,
                                       Unavailable)
from paddle_tpu.serving.metrics import RouterMetrics
from paddle_tpu.serving.server import JSONHandler
from paddle_tpu.testing import chaos as _chaos
from paddle_tpu.utils.log import event as log_event
from paddle_tpu.utils.log import get_logger

logger = get_logger("serving.router")

# replica states; only READY receives dispatches
WARMING, READY, DRAINING, EJECTED, HALF_OPEN, DEAD = (
    "warming", "ready", "draining", "ejected", "half_open", "dead")


def _get_json(host: str, port: int, path: str,
              timeout: float) -> Tuple[int, dict]:
    """One bounded GET returning ``(status, parsed body)`` — the body
    is read WHATEVER the status (health/metrics payloads ride 503s
    too). The one wire block behind ``HTTPTransport.healthz`` /
    ``.metrics_snapshot`` and ``RouterHA._poll_peer``; callers apply
    their own payload validation."""
    import http.client
    import json
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


class PendingCall:
    """One in-flight attempt at one replica. ``outcome()`` classifies
    the completed attempt:

    - ``("ok", result)``      — answer for the client
    - ``("client", error)``   — typed 400/429-wire/504 that belongs to
      the CLIENT (never failed over, never charges the breaker)
    - ``("busy", error)``     — the replica shed or is draining; the
      request never ran — try another replica, no breaker charge
    - ``("failed", exc)``     — definite replica failure (connection
      reset, worker died); failover + breaker charge
    """

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.error: Optional[ServingError] = None
        self.transport_failure: Optional[BaseException] = None
        self._req = None  # EngineTransport bridges the engine _Request
        self.is_hedge = False  # launched as a hedge (win attribution)
        # span bookkeeping for this attempt (set by dispatch.launch):
        # the attempt's own TraceContext + launch times, recorded as a
        # router.attempt span when the outcome settles — failovers and
        # hedges then read as SIBLING attempts under one dispatch span
        self.trace_ctx = None
        self.t0_wall = 0.0
        self.t0_perf = 0.0

    def outcome(self) -> Tuple[str, object]:
        if self._req is not None:
            self.error, self.result = self._req.error, self._req.result
        if self.transport_failure is not None:
            return "failed", self.transport_failure
        e = self.error
        if e is None:
            return "ok", self.result
        if isinstance(e, (ShuttingDown, Overloaded)):
            return "busy", e
        if isinstance(e, (BadRequest, DeadlineExceeded)):
            return "client", e
        if e.status >= 500:
            return "failed", e  # "serving worker died" and kin
        return "client", e


class EngineTransport:
    """In-process replica: one started :class:`ServingEngine`."""

    def __init__(self, engine):
        self.engine = engine

    def ready_hint(self) -> bool:
        """Lock-free instantaneous readiness — consulted at pick time
        so dispatch stops THE MOMENT ``begin_drain()`` fires (or the
        worker dies), without waiting for the next health sweep. Plain
        attribute reads: no lock, no lock-order edge."""
        e = self.engine
        return (e.fatal is None and not e.draining
                and e.predictor.warmed)

    def start_call(self, kind: str, sample, deadline_ms,
                   gen_opts: Dict) -> PendingCall:
        p = PendingCall()
        try:
            req = self.engine.submit(
                sample, kind=kind, deadline_ms=deadline_ms,
                beam_size=gen_opts.get("beam_size"),
                max_length=gen_opts.get("max_length"))
        except ServingError as e:
            p.error = e
            p.event.set()
            return p
        # share the engine request's completion event — zero polling
        p.event = req.event
        p._req = req
        return p

    def healthz(self) -> dict:
        return self.engine.health()

    def metrics_snapshot(self) -> dict:
        """This replica's serving metrics — the router's ``/metrics``
        federates these so one scrape shows the whole fleet."""
        return self.engine.metrics.snapshot()

    def begin_drain(self):
        self.engine.begin_drain()

    def drain_wait(self, timeout: float = 60.0):
        """Blocks until every queued + in-flight request of this replica
        is answered (the zero-drop half of rolling reload)."""
        self.engine.shutdown(drain=True, timeout=timeout)

    def apply_config(self, cfg) -> dict:
        """Apply an engine-knob delta to this replica (typed refusal
        propagates to the router's fan-out rollback)."""
        return self.engine.apply_config(cfg)


class HTTPTransport:
    """A replica reached over HTTP — a separately-launched single-
    replica server process. Drain is uniform whether or not we hold the
    process handle: ``begin_drain`` POSTs the replica's
    ``/admin/drain`` (admission closes, queued + in-flight work
    completes), so a supervisor-owned and an externally-launched
    replica drain identically; ``proc`` (a ``subprocess.Popen``) lets
    ``drain_wait`` additionally SIGTERM and reap the drained process,
    while a Popen-less transport watches ``/healthz`` until
    ``queue_depth`` and ``inflight`` are dry. The wire layer is
    :class:`ServingClient`'s (retries=0 — retry policy belongs to the
    router's failover, not the transport)."""

    def __init__(self, host: str, port: int, timeout: float = 120.0,
                 proc=None, healthz_timeout: float = 5.0):
        from paddle_tpu.serving.client import ServingClient
        self.host, self.port = host, int(port)
        self.timeout = timeout
        self.proc = proc
        # the supervisor probes with a SHORT deadline (a hung replica
        # must not stall the sweep for the default 5 s)
        self.healthz_timeout = float(healthz_timeout)
        self._client = ServingClient(host, port, timeout=timeout)

    def start_call(self, kind: str, sample, deadline_ms,
                   gen_opts: Dict) -> PendingCall:
        p = PendingCall()
        path = {"score": "/v1/score", "generate": "/v1/generate"}[kind]
        body = {"sample": sample}
        if deadline_ms is not None:
            body["deadline_ms"] = deadline_ms
        for k in ("beam_size", "max_length"):
            if gen_opts.get(k) is not None:
                body[k] = gen_opts[k]

        # contextvars do NOT flow into new threads: capture the
        # dispatcher's ambient attempt context here and re-scope it in
        # the call thread, so the wire hop's X-Trace-Id carries the
        # attempt's span and the remote replica parents under it
        tctx = _trace.current()

        def run():
            try:
                with _trace.use(tctx):
                    p.result = self._client._request_once(
                        "POST", path, body)
                if isinstance(p.result, dict):
                    # the inner client attached ITS provenance (the
                    # replica's X-Trace-Id echo) to the body; forwarded
                    # verbatim it would pre-empt the end client's
                    # setdefault and eat the router's replica/failover
                    # provenance — this hop's details are not the
                    # caller's provenance
                    p.result.pop("provenance", None)
            except ServingError as e:
                p.error = e
            except Exception as e:  # noqa: BLE001 — conn reset/refused
                p.transport_failure = e
            finally:
                p.event.set()

        threading.Thread(target=run, daemon=True,
                         name="router-http-call").start()
        return p

    def healthz(self) -> dict:
        # NOT _request_once: that raises on any >=400 status, but a 503
        # healthz still carries the {live, ready, draining, ...} split
        # the router routes on
        status, data = _get_json(self.host, self.port, "/healthz",
                                 self.healthz_timeout)
        if not isinstance(data, dict) or "live" not in data:
            raise ConnectionError(
                f"healthz from {self.host}:{self.port} is not a "
                f"health payload (HTTP {status})")
        return data

    def metrics_snapshot(self) -> dict:
        """The remote replica's ``/metrics?format=json`` snapshot (the
        federation hook; probe-timeout bounded like healthz)."""
        status, data = _get_json(self.host, self.port,
                                 "/metrics?format=json",
                                 self.healthz_timeout)
        if status >= 400 or not isinstance(data, dict):
            raise ConnectionError(
                f"metrics from {self.host}:{self.port} unavailable "
                f"(HTTP {status})")
        return data

    def begin_drain(self):
        """Close the replica's admission via ``POST /admin/drain`` —
        the ONE drain path for supervisor-owned and externally-launched
        replicas alike. Falls back to SIGTERM when the endpoint is
        unreachable and we hold the process handle (e.g. the listener
        already died but the process lingers)."""
        try:
            self._client._request_once("POST", "/admin/drain")
            return
        except Exception as e:  # noqa: BLE001 — endpoint unreachable
            if self.proc is not None and self.proc.poll() is not None:
                return  # the process already exited (an earlier drain
                # completed, or it died): nothing left to drain
            if self.proc is None:
                logger.warning(
                    "HTTPTransport %s:%d drain endpoint unreachable "
                    "(%r) and no process handle; drain must be driven "
                    "out of band", self.host, self.port, e)
                return
            logger.warning(
                "HTTPTransport %s:%d drain endpoint unreachable (%r); "
                "falling back to SIGTERM", self.host, self.port, e)
            import signal
            try:
                self.proc.send_signal(signal.SIGTERM)
            except (ProcessLookupError, OSError):
                pass  # already gone — drain_wait reaps

    def apply_config(self, cfg) -> dict:
        """Forward an engine-knob delta to the remote replica's
        ``POST /admin/config``. A 409 comes back as the typed
        :class:`~paddle_tpu.serving.errors.ConfigRejected` via
        ``from_wire`` — the router's rollback branches on it exactly
        like the in-process case."""
        body = cfg if isinstance(cfg, dict) else cfg.to_dict()
        return self._client._request_once("POST", "/admin/config", body)

    def drain_wait(self, timeout: float = 60.0):
        """Block until every queued + in-flight request is answered.
        With a process handle the drained replica is then SIGTERMed and
        reaped (the rolling-reload / shutdown contract); without one we
        watch ``/healthz`` until the drain runs dry — an unreachable
        replica counts as drained (it can hold no queued work)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                h = self.healthz()
            except Exception:  # noqa: BLE001 — gone = drained
                break
            if (h.get("draining") and not h.get("queue_depth")
                    and not h.get("inflight")):
                break
            time.sleep(0.02)
        if self.proc is not None:
            import signal
            try:
                self.proc.send_signal(signal.SIGTERM)
            except (ProcessLookupError, OSError):
                pass
            self.proc.wait(timeout=max(1.0,
                                       deadline - time.monotonic()))


class Replica:
    """Router-side state for one replica slot. The transport may be
    swapped (respawn, rolling reload); the slot identity persists."""

    def __init__(self, replica_id: str, transport):
        self.id = str(replica_id)
        self.transport = transport
        self.state = WARMING
        self.inflight = 0
        self.consecutive_failures = 0
        self.poll_failures = 0
        self.breaker_until = 0.0  # monotonic deadline while EJECTED
        self.breaker_cooldown_ms: Optional[float] = None  # doubles
        self.last_health: dict = {}
        self.last_spawn_ms: Optional[float] = None

    def snapshot(self) -> dict:
        t = self.transport
        # HTTP-reachable replicas advertise their address so a warm
        # standby router can rebuild this fleet from /healthz polls
        # alone (router HA: adoption is re-poll + re-arm, no shared db)
        addr = (f"{t.host}:{t.port}"
                if getattr(t, "host", None) is not None
                and getattr(t, "port", None) is not None else None)
        return {"id": self.id, "state": self.state,
                "inflight": self.inflight,
                "consecutive_failures": self.consecutive_failures,
                "model_version": self.last_health.get("model_version"),
                "queue_depth": self.last_health.get("queue_depth"),
                "backlog_ms": self.last_health.get("backlog_ms"),
                "last_spawn_ms": self.last_spawn_ms,
                "addr": addr}


class ReplicaRouter:
    """Owns admission for a fleet of replicas. See the module docstring
    for the dispatch/breaker/hedge/reload policies."""

    def __init__(self, transports, *,
                 spawn: Optional[Callable[[str], object]] = None,
                 health_poll_ms: float = 100.0,
                 eject_after: int = 3,
                 breaker_cooldown_ms: float = 1000.0,
                 breaker_cooldown_max_ms: float = 30000.0,
                 hedge_ms: Optional[float] = None,
                 max_hedges: int = 1,
                 wait_timeout: float = 120.0,
                 fence=None,
                 metrics: Optional[RouterMetrics] = None):
        self.replicas: List[Replica] = [
            t if isinstance(t, Replica) else Replica(f"r{i}", t)
            for i, t in enumerate(transports)]
        if len({r.id for r in self.replicas}) != len(self.replicas):
            raise ValueError("replica ids must be unique")
        self.spawn = spawn
        # optional role fence (a RoleLease, or anything with .valid()):
        # dispatch refuses while the fence is invalid, so a partitioned
        # old ACTIVE router provably stops dispatching within one lease
        # ttl of losing the role (router HA; the r11 epoch-guard idea)
        self.fence = fence
        self.health_poll_ms = float(health_poll_ms)
        self.eject_after = int(eject_after)
        self.breaker_cooldown_ms = float(breaker_cooldown_ms)
        self.breaker_cooldown_max_ms = float(breaker_cooldown_max_ms)
        self.hedge_ms = hedge_ms if hedge_ms is None else float(hedge_ms)
        self.max_hedges = int(max_hedges)
        self.wait_timeout = float(wait_timeout)
        self.metrics = metrics or RouterMetrics()
        self._lock = threading.Lock()
        self._rr = 0  # round-robin tiebreak counter
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._reloading = False
        # set by adopt_replicas: the NEXT successful dispatch records
        # the flight event closing a takeover postmortem (lease expiry
        # -> adoption -> first standby answer); plain attr, read on the
        # dispatch hot path without the lock
        self._first_answer_pending = False
        # monotonic id source for scale-up slots: ids never recycle, so
        # a drained-away "r2" and a later scale-up replica can never be
        # confused in logs/metrics/provenance
        self._next_id = len(self.replicas)
        # optional attachments for the hot-reconfig / tuning plane:
        # an Autoscaler whose watermarks apply_config may retarget, and
        # a WorkloadRecorder tapping the admission stream (both plain
        # attrs — set by the owner, read without the lock)
        self.autoscaler = None
        self.workload_recorder = None

    # ------------------------------------------------------------ control
    def start(self, poll_now: bool = True) -> "ReplicaRouter":
        if poll_now:
            self.poll_once()
        self._thread = threading.Thread(target=self._health_loop,
                                        name="router-health", daemon=True)
        self._thread.start()
        return self

    def shutdown(self, drain: bool = True, timeout: float = 60.0):
        """Stop the health loop and drain every replica (zero queued
        drops, same as single-replica SIGTERM)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        for rep in self.replicas:
            with self._lock:
                rep.state = DRAINING
            try:
                rep.transport.begin_drain()
            except Exception as e:  # noqa: BLE001 — best-effort drain
                logger.warning("drain of %s failed: %r", rep.id, e)
        if drain:
            for rep in self.replicas:
                try:
                    rep.transport.drain_wait(timeout=timeout)
                except Exception as e:  # noqa: BLE001
                    logger.warning("drain wait of %s failed: %r",
                                   rep.id, e)

    # ------------------------------------------------------------- health
    def _health_loop(self):
        while not self._stop.wait(self.health_poll_ms / 1e3):
            try:
                self.poll_once()
            except Exception as e:  # noqa: BLE001 — the loop must live
                logger.error("router health poll crashed: %r", e)

    def poll_once(self):
        """One health sweep: readiness transitions, breaker half-open
        probes, dead-replica respawn. Also callable inline (tests, and
        ``start(poll_now=True)`` so the first dispatch has states)."""
        now = time.monotonic()
        with self._lock:
            snapshot = list(self.replicas)
        for rep in snapshot:
            if rep.state == DEAD:
                self._maybe_respawn(rep)
                continue
            if rep.state == EJECTED:
                if now < rep.breaker_until:
                    continue
                with self._lock:
                    rep.state = HALF_OPEN
                log_event(logger, "breaker_half_open",
                          "router: %s breaker half-open, probing",
                          rep.id, level=20, replica=rep.id)
            try:
                h = rep.transport.healthz()
            except Exception as e:  # noqa: BLE001 — any probe failure
                self._poll_failed(rep, e)
                continue
            self._apply_health(rep, h)

    def _poll_failed(self, rep: Replica, exc: BaseException):
        with self._lock:
            rep.poll_failures += 1
            half_open = rep.state == HALF_OPEN
            should_eject = (rep.poll_failures >= self.eject_after
                            and rep.state in (READY, WARMING, DRAINING))
        if half_open:
            self._reopen_breaker(rep)
        elif should_eject:
            logger.warning("router: ejecting %s after %d failed health "
                           "probes (%r)", rep.id, rep.poll_failures, exc)
            self._eject(rep)

    def _apply_health(self, rep: Replica, h: dict):
        closed = False
        with self._lock:
            rep.poll_failures = 0
            rep.last_health = dict(h)
            if not h.get("live", True):
                dead = rep.state != DEAD
                rep.state = DEAD
            elif h.get("draining"):
                rep.state = DRAINING
                dead = False
            elif not h.get("ready", False):
                if rep.state != HALF_OPEN:
                    rep.state = WARMING
                dead = False
            else:
                closed = rep.state in (HALF_OPEN, EJECTED)
                rep.state = READY
                rep.consecutive_failures = 0
                if closed:
                    rep.breaker_cooldown_ms = None
                dead = False
        # events (log + flight) outside the router lock
        if closed:
            log_event(logger, "breaker_close",
                      "router: %s breaker closed (probe ok)", rep.id,
                      level=20, replica=rep.id)
        if dead:
            log_event(logger, "replica_dead",
                      "router: replica %s is dead (worker fatal: %s)",
                      rep.id, h.get("fatal"), replica=rep.id,
                      fatal=h.get("fatal"))
            self.metrics.inc("replica_deaths_total")
            self._maybe_respawn(rep)

    def _maybe_respawn(self, rep: Replica):
        """Replace a dead replica's transport via the spawn factory.
        Synchronous on the health thread: the fleet serves on the other
        replicas while the new one warms (ms with the AOT cache)."""
        if self.spawn is None:
            return
        try:
            if _chaos._ACTIVE is not None:
                _chaos._ACTIVE.hit("replica_spawn", replica=rep.id)
            t0 = time.perf_counter()
            new = self.spawn(rep.id)
            spawn_ms = 1e3 * (time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001 — retry next sweep
            log_event(logger, "respawn_failed",
                      "router: respawn of %s failed (%r); will retry",
                      rep.id, e, replica=rep.id, error=repr(e))
            return
        with self._lock:
            rep.transport = new
            rep.state = WARMING
            rep.consecutive_failures = 0
            rep.poll_failures = 0
            rep.breaker_cooldown_ms = None
            rep.last_spawn_ms = spawn_ms
        self.metrics.inc("respawns_total")
        log_event(logger, "respawn",
                  "router: respawned %s in %.1f ms", rep.id, spawn_ms,
                  level=20, replica=rep.id,
                  spawn_ms=round(spawn_ms, 1))
        try:
            self._apply_health(rep, rep.transport.healthz())
        except Exception:  # noqa: BLE001 — next sweep will see it
            pass

    # ------------------------------------------------------------ breaker
    def _eject(self, rep: Replica):
        with self._lock:
            cooldown = rep.breaker_cooldown_ms or self.breaker_cooldown_ms
            rep.breaker_cooldown_ms = min(2 * cooldown,
                                          self.breaker_cooldown_max_ms)
            rep.state = EJECTED
            rep.breaker_until = time.monotonic() + cooldown / 1e3
        self.metrics.inc("ejections_total")
        self.metrics.inc("breaker_open_total")
        log_event(logger, "breaker_open",
                  "router: %s breaker opened (cooldown %.0f ms)",
                  rep.id, cooldown, replica=rep.id,
                  cooldown_ms=round(cooldown, 1))

    def _reopen_breaker(self, rep: Replica):
        logger.warning("router: %s failed its half-open probe; breaker "
                       "re-opened", rep.id)
        self._eject(rep)

    def _record_failure(self, rep: Replica, exc: BaseException):
        with self._lock:
            rep.consecutive_failures += 1
            eject = (rep.consecutive_failures >= self.eject_after
                     and rep.state == READY)
        log_event(logger, "dispatch_failed",
                  "router: dispatch to %s failed (%r)", rep.id, exc,
                  replica=rep.id, error=repr(exc))
        if eject:
            logger.warning("router: ejecting %s after %d consecutive "
                           "dispatch failures", rep.id,
                           rep.consecutive_failures)
            self._eject(rep)

    def _record_success(self, rep: Replica):
        with self._lock:
            rep.consecutive_failures = 0

    # ----------------------------------------------------------- dispatch
    def _pick(self, exclude) -> Optional[Replica]:
        with self._lock:
            # state is the health loop's view; ready_hint (where the
            # transport offers one — in-process engines) is the LIVE
            # view, so a begin_drain or worker death stops dispatch
            # immediately, not at the next poll
            ready = [r for r in self.replicas
                     if r.state == READY and r.id not in exclude
                     and getattr(r.transport, "ready_hint",
                                 lambda: True)()]
            if not ready:
                return None
            self._rr += 1
            rr = self._rr
            n = len(self.replicas)
            rep = min(ready, key=lambda r: (
                r.inflight, (self.replicas.index(r) + rr) % n))
            rep.inflight += 1
            return rep

    def _end_inflight(self, rep: Replica):
        with self._lock:
            rep.inflight = max(0, rep.inflight - 1)

    def _abandon(self, rep: Replica, pend: PendingCall):
        """A hedge lost the race: its compute is sunk, but its outcome
        still matters to the breaker, so reap it off-thread."""

        def run():
            settled = pend.event.wait(self.wait_timeout)
            self._end_inflight(rep)
            kind, payload = pend.outcome()
            # a reap that timed out never answered: outcome() would
            # read ("ok", None) from the empty call — the span must
            # say "unanswered", and neither breaker counter may move
            # (crediting a hung replica with a success would mask it)
            self._record_attempt(rep.id, pend.trace_ctx, pend.t0_wall,
                                 pend.t0_perf,
                                 kind if settled else "unanswered",
                                 pend.is_hedge, abandoned=True)
            if not settled:
                return
            if kind == "failed":
                self._record_failure(rep, payload)
            elif kind == "ok":
                self._record_success(rep)

        threading.Thread(target=run, daemon=True,
                         name="router-abandoned-hedge").start()

    def fleet_retry_after_ms(self, hints=()) -> float:
        """Earliest-capacity estimate across the fleet: the MIN over
        per-replica drain hints — a request needs ONE free slot and
        replica queues drain in parallel, so the fleet frees up as fast
        as its least-loaded member, not as slow as its average."""
        # None-checks, not truthiness: 0.0 is a legitimate hint (an
        # idle replica IS the fleet's earliest capacity)
        vals = [float(h) for h in hints if h is not None]
        with self._lock:
            for r in self.replicas:
                if r.state in (READY, DRAINING, WARMING):
                    b = r.last_health.get("backlog_ms")
                    if b is not None:
                        vals.append(float(b))
        return min(vals) if vals else 50.0

    def _record_attempt(self, rep_id: str, ctx, t0_wall: float,
                        t0_perf: float, outcome: str, hedge: bool,
                        abandoned: bool = False):
        """One settled attempt -> one ``router.attempt`` span. Sibling
        attempts under one dispatch span ARE the failover/hedge story a
        trace tells; "ok"/"client" are healthy-replica outcomes."""
        tracer = _trace._TRACER
        if tracer is None or ctx is None:
            return
        tracer.record("router.attempt", ctx, ts=t0_wall,
                      dur_ms=1e3 * (time.perf_counter() - t0_perf),
                      status=("ok" if outcome in ("ok", "client")
                              else "error"),
                      replica=rep_id, outcome=outcome,
                      hedge=True if hedge else None,
                      abandoned=True if abandoned else None)

    def dispatch(self, sample, *, kind: str = "score",
                 deadline_ms: Optional[float] = None,
                 beam_size=None, max_length=None,
                 trace_parent=None) -> Tuple[dict, dict]:
        """Route one request; returns ``(result, provenance)`` or raises
        the typed error the client should see. ``provenance`` =
        ``{"replica", "failovers", "hedges"}`` (the HTTP frontend
        surfaces it as ``X-Replica-Id`` / ``X-Failovers`` /
        ``X-Hedged``). ``trace_parent`` roots the routing decision's
        ``router.dispatch`` span (and its per-attempt children) under
        the caller's context — the HTTP frontend passes the parsed
        ``X-Trace-Id``."""
        with _trace.span("router.dispatch", parent=trace_parent,
                         kind=kind):
            return self._dispatch(sample, kind=kind,
                                  deadline_ms=deadline_ms,
                                  beam_size=beam_size,
                                  max_length=max_length)

    def _dispatch(self, sample, *, kind: str, deadline_ms,
                  beam_size, max_length) -> Tuple[dict, dict]:
        if kind not in ("score", "generate"):
            raise BadRequest(f"unknown request kind {kind!r}")
        rec = self.workload_recorder
        if rec is not None:
            # admission-stream tap for the trace-replay harness: one
            # lock-free deque append, off the latency path (the r20
            # replay-sink discipline applied at the front tier)
            rec.observe(sample, kind=kind, deadline_ms=deadline_ms,
                        beam_size=beam_size, max_length=max_length)
        if self.fence is not None and not self.fence.valid():
            # fenced: we lost (or never held) the active-role lease —
            # a zombie active must NOT keep dispatching while a standby
            # serves the same fleet. 503 so clients re-resolve to the
            # other endpoint (ServingClient rotates on Unavailable).
            self.metrics.inc("fenced_total")
            if _flight._ACTIVE is not None:
                _flight._ACTIVE.record("fenced_dispatch", kind=kind)
            raise Unavailable(
                "router fenced: not the active role holder (the lease "
                "lapsed or a standby adopted the fleet); retry against "
                "the other router endpoint", retry_after_ms=50.0)
        gen_opts = {"beam_size": beam_size, "max_length": max_length}
        t0 = time.perf_counter()
        tried: set = set()
        busy: List[ServingError] = []
        prov = {"replica": None, "failovers": 0, "hedges": 0}
        live: List[Tuple[Replica, PendingCall]] = []
        self.metrics.inc("dispatches_total")

        def launch(as_hedge: bool = False) -> str:
            """Start one attempt. Returns "live" (attempt in flight),
            "consumed" (a replica was tried but the dispatch itself
            failed — recorded as a failover, NOT as a fired hedge), or
            "none" (no untried ready replica)."""
            rep = self._pick(tried)
            if rep is None:
                return "none"
            tried.add(rep.id)
            # one attempt = one child span of the dispatch span; the
            # ambient context is scoped around start_call so both
            # transports (engine submit / HTTP hop) parent under it
            actx = _trace.child(_trace.current())
            t0_wall, t0_perf = time.time(), time.perf_counter()
            try:
                if _chaos._ACTIVE is not None:
                    # seeded fault site: a "drop" here is a dispatch
                    # that never reached the replica — the failover
                    # path, deterministic from the plan seed
                    _chaos._ACTIVE.hit("route_dispatch",
                                       replica=rep.id, kind=kind)
                with _trace.use(actx):
                    pend = rep.transport.start_call(
                        kind, sample, deadline_ms, gen_opts)
            except Exception as e:  # noqa: BLE001 — incl. ChaosDropped
                self._end_inflight(rep)
                self._record_failure(rep, e)
                prov["failovers"] += 1
                self.metrics.inc("failovers_total")
                self._record_attempt(rep.id, actx, t0_wall, t0_perf,
                                     "failed", as_hedge)
                return "consumed"
            pend.is_hedge = as_hedge
            pend.trace_ctx = actx
            pend.t0_wall, pend.t0_perf = t0_wall, t0_perf
            if as_hedge:
                prov["hedges"] += 1
                self.metrics.inc("hedges_total")
            live.append((rep, pend))
            return "live"

        launch()
        hedge_at = (t0 + self.hedge_ms / 1e3
                    if (kind == "score" and self.hedge_ms is not None)
                    else None)
        hedges = 0
        while True:
            now = time.perf_counter()
            if now - t0 > self.wait_timeout:
                for rep, pend in live:
                    self._abandon(rep, pend)
                raise DeadlineExceeded(
                    f"router got no replica answer within "
                    f"{self.wait_timeout}s")
            progressed = False
            for rep, pend in list(live):
                if not pend.event.is_set():
                    continue
                progressed = True
                live.remove((rep, pend))
                self._end_inflight(rep)
                okind, payload = pend.outcome()
                self._record_attempt(rep.id, pend.trace_ctx,
                                     pend.t0_wall, pend.t0_perf,
                                     okind, pend.is_hedge)
                if okind == "ok":
                    self._record_success(rep)
                    prov["replica"] = rep.id
                    prov["model_version"] = rep.last_health.get(
                        "model_version")
                    if self._first_answer_pending:
                        # the first answer after a standby takeover is
                        # the postmortem's closing bracket (lease
                        # expiry -> adoption -> THIS); the unlocked
                        # read keeps the hot path cheap, the locked
                        # swap keeps the event singular when two
                        # dispatches race past the read
                        with self._lock:
                            won = self._first_answer_pending
                            self._first_answer_pending = False
                        if won and _flight._ACTIVE is not None:
                            _flight._ACTIVE.record(
                                "first_answer_after_takeover",
                                replica=rep.id)
                    if pend.is_hedge:
                        # only a HEDGE beating its primary is a win; a
                        # primary outrunning its hedge is not
                        self.metrics.inc("hedge_wins_total")
                    for orep, opend in live:
                        self._abandon(orep, opend)
                    self.metrics.observe_dispatch(
                        rep.id, 1e3 * (time.perf_counter() - t0))
                    return payload, prov
                if okind == "client":
                    # a typed 400/504 from a healthy replica IS the
                    # answer; failing over would fail identically
                    self._record_success(rep)
                    prov["replica"] = rep.id
                    prov["model_version"] = rep.last_health.get(
                        "model_version")
                    for orep, opend in live:
                        self._abandon(orep, opend)
                    payload.provenance = prov
                    raise payload
                if okind == "busy":
                    busy.append(payload)
                    launch()
                    continue
                # definite failure -> failover
                self._record_failure(rep, payload)
                prov["failovers"] += 1
                self.metrics.inc("failovers_total")
                launch()
            if not live:
                if launch() != "none":
                    continue
                self.metrics.inc("shed_total")
                retry = self.fleet_retry_after_ms(
                    [getattr(e, "retry_after_ms", None) for e in busy])
                err: ServingError
                if busy:
                    err = Overloaded(
                        "every ready replica is shedding load "
                        f"({len(busy)} tried); fleet at capacity",
                        retry_after_ms=retry)
                else:
                    err = Unavailable(
                        "no ready replica to dispatch to",
                        retry_after_ms=retry)
                err.provenance = prov
                raise err
            if (hedge_at is not None and now >= hedge_at
                    and hedges < self.max_hedges):
                st = launch(as_hedge=True)
                if st == "live":
                    hedges += 1
                    hedge_at = now + self.hedge_ms / 1e3
                    if hedges >= self.max_hedges:
                        hedge_at = None
                elif st == "none":
                    hedge_at = None  # nobody to hedge at; stop trying
                # "consumed": the attempt burned as a failover before
                # any hedge fired — the hedge budget is NOT spent; the
                # next loop iteration may try another replica
                continue
            # wait on the oldest pending attempt's event: up to the
            # hedge deadline when one is armed, a short poll while
            # several attempts race, else the full remaining budget —
            # the common single-attempt case must not spin at 200 Hz
            if hedge_at is not None:
                timeout = max(0.001, hedge_at - now)
            elif len(live) > 1:
                timeout = 0.005
            else:
                timeout = max(0.001, self.wait_timeout - (now - t0))
            live[0][1].event.wait(timeout)

    # ------------------------------------------------------------- reload
    def rolling_reload(self, build: Callable[[str], object],
                       wait_ready_s: float = 300.0,
                       fallback_build: Optional[
                           Callable[[str], object]] = None) -> List[str]:
        """Hot-swap the model one replica at a time, zero queued drops:
        DRAINING (dispatch stops now) -> drain via the SIGTERM machinery
        (queued + in-flight requests all complete) -> swap in
        ``build(replica_id)`` (a started transport for the new version;
        ms-fast when its predictor warms from the AOT cache) -> wait
        READY -> next replica. Returns the per-replica model versions
        after the roll.

        **Rollback**: when ``build`` itself raises — a corrupt artifact,
        or a quantized model refused by the warmup accuracy gate
        (``QuantGateError``) — and ``fallback_build`` is given, the
        drained replica is REBUILT on the previous artifact and the roll
        aborts with a typed :class:`~paddle_tpu.serving.errors.
        ReloadRejected` naming the refusal: the bad version is never
        published and the fleet stays whole on the old one. Without a
        fallback the old behavior stands (the replica is left drained;
        the caller must reload again with a good artifact). Raises if a
        swapped replica never turns ready — earlier replicas stay
        swapped (mixed-version fleet; roll back by reloading again with
        the old artifact)."""
        with self._lock:
            if self._reloading:
                raise RuntimeError("a rolling reload is already running")
            self._reloading = True
        try:
            versions = []
            for rep in list(self.replicas):
                with self._lock:
                    rep.state = DRAINING
                logger.info("rolling reload: draining %s", rep.id)
                rep.transport.begin_drain()
                rep.transport.drain_wait()
                try:
                    new = build(rep.id)
                except Exception as e:  # noqa: BLE001 — typed below
                    if fallback_build is None:
                        raise
                    from paddle_tpu.serving.errors import ReloadRejected
                    logger.warning(
                        "rolling reload: new artifact REFUSED on %s "
                        "(%s); rolling back to the previous artifact",
                        rep.id, e)
                    old = fallback_build(rep.id)
                    with self._lock:
                        rep.transport = old
                        rep.state = WARMING
                        rep.consecutive_failures = 0
                        rep.poll_failures = 0
                        rep.breaker_cooldown_ms = None
                    self.metrics.inc("reload_rollbacks_total")
                    self._wait_replica_ready(rep, wait_ready_s)
                    raise ReloadRejected(
                        f"reload rejected: replica {rep.id} refused the "
                        f"new artifact ({e}); fleet rolled back to the "
                        "previous version (no replica serves the bad "
                        "artifact)") from e
                with self._lock:
                    rep.transport = new
                    rep.state = WARMING
                    rep.consecutive_failures = 0
                    rep.poll_failures = 0
                    rep.breaker_cooldown_ms = None
                self.metrics.inc("reloads_total")
                versions.append(self._wait_replica_ready(rep,
                                                         wait_ready_s))
                logger.info("rolling reload: %s ready on version %s",
                            rep.id, versions[-1])
            return versions
        finally:
            with self._lock:
                self._reloading = False

    def _wait_replica_ready(self, rep, wait_ready_s: float):
        """Poll one replica until READY; returns its reported model
        version. Raises RuntimeError past the deadline."""
        deadline = time.monotonic() + wait_ready_s
        while True:
            try:
                h = rep.transport.healthz()
                self._apply_health(rep, h)
                if rep.state == READY:
                    return h.get("model_version")
            except Exception:  # noqa: BLE001 — keep waiting
                pass
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"rolling reload: replica {rep.id} did not "
                    f"turn ready within {wait_ready_s}s; roll "
                    "halted (earlier replicas are on the new "
                    "version)")
            time.sleep(0.01)

    # ------------------------------------------------------ hot reconfig
    def current_config(self) -> dict:
        """The router's own incumbent knob values (the replicas' live
        via their ``current_config``/``/admin/config`` answers)."""
        return {"hedge_ms": self.hedge_ms,
                "max_hedges": self.max_hedges}

    def apply_config(self, cfg) -> dict:
        """Apply a :class:`~paddle_tpu.serving.tuner.FleetConfig` delta
        fleet-wide: engine knobs fan out to every non-dead replica's
        transport, router knobs (``hedge_ms``, ``max_hedges``) commit
        locally, autoscale watermarks retarget the attached
        ``Autoscaler``.

        All-or-nothing like a rolling reload: local knobs validate
        BEFORE the fan-out, and when replica K refuses the delta (typed
        409 — e.g. an off-menu ``max_batch``), replicas 0..K-1 are
        rolled back to their incumbent values and the call raises
        :class:`~paddle_tpu.serving.errors.ConfigRejected` — no replica
        serves the refused config, the fleet stays on the incumbent."""
        from paddle_tpu.serving.tuner import (FleetConfig,
                                              record_tune_decision,
                                              rollback_delta)
        cfg = FleetConfig.coerce(cfg)
        before = self.current_config()

        def reject(reason: str, allowed=None, cause=None):
            self.metrics.inc("config_rejected_total")
            record_tune_decision(action="apply_rejected", reason=reason,
                                 requested=cfg.to_dict(), before=before)
            raise ConfigRejected(
                f"{reason}; incumbent config keeps serving",
                allowed=allowed) from cause

        # ---- validate the locally-owned knobs before any side effect
        router_changes = cfg.router_items()
        if "max_hedges" in router_changes \
                and router_changes["max_hedges"] < 0:
            reject(f"max_hedges {router_changes['max_hedges']} must "
                   "be >= 0")
        auto = cfg.autoscale_items()
        scaler = self.autoscaler
        if auto:
            if scaler is None:
                reject("autoscale watermarks were sent but this router "
                       "has no autoscaler attached")
            scaler.check_config(auto)  # raises ConfigRejected itself
        # ---- fan the engine knobs out, rollback on refusal
        engine_cfg = cfg.engine_subset()
        applied: List[Tuple[Replica, dict]] = []
        if engine_cfg.set_fields():
            with self._lock:
                targets = [r for r in self.replicas if r.state != DEAD]
            for rep in targets:
                try:
                    res = rep.transport.apply_config(engine_cfg)
                except ServingError as e:
                    for prep, prior in applied:
                        try:
                            prep.transport.apply_config(prior)
                        except Exception as re:  # noqa: BLE001
                            logger.error(
                                "config rollback of %s failed: %r "
                                "(replica may hold the refused delta)",
                                prep.id, re)
                    reject(f"replica {rep.id} refused the config ({e}); "
                           f"{len(applied)} earlier replica(s) rolled "
                           "back", allowed=e.allowed, cause=e)
                applied.append((rep, rollback_delta(
                    res.get("before", {}), engine_cfg.set_fields())))
        # ---- commit the local knobs (plain attrs, read per-dispatch)
        if "hedge_ms" in router_changes:
            self.hedge_ms = router_changes["hedge_ms"]
        if "max_hedges" in router_changes:
            self.max_hedges = int(router_changes["max_hedges"])
        if auto:
            scaler.commit_config(auto)
        after = self.current_config()
        changed = cfg.set_fields()
        self.metrics.inc("config_applies_total")
        if _flight._ACTIVE is not None:
            _flight._ACTIVE.record("config_applied", tier="router",
                                   changed=",".join(changed),
                                   replicas=len(applied),
                                   before=before, after=after)
        log_event(logger, "config_applied",
                  "router: config applied (%s) to %d replica(s)",
                  changed, len(applied), level=20,
                  changed=",".join(changed), replicas=len(applied))
        return {"status": "ok", "before": before, "after": after,
                "replicas": len(applied), "applied": cfg.to_dict()}

    # ------------------------------------------------------ elastic fleet
    def set_transport(self, replica_id: str, transport,
                      state: str = WARMING) -> bool:
        """Swap a replica slot's transport in place (the supervisor's
        respawn push: it killed and relaunched the process, the slot
        identity persists). Resets the slot's failure/breaker state —
        the new process has no history. False when the slot is unknown
        (the caller should ``add_replica`` instead)."""
        with self._lock:
            rep = next((r for r in self.replicas
                        if r.id == str(replica_id)), None)
            if rep is None:
                return False
            rep.transport = transport
            rep.state = state
            rep.consecutive_failures = 0
            rep.poll_failures = 0
            rep.breaker_cooldown_ms = None
        return True

    def add_replica(self, transport, replica_id: Optional[str] = None,
                    state: str = WARMING) -> str:
        """Grow the fleet by one slot (autoscale scale-up, standby
        adoption). The new replica starts WARMING (or the given state)
        and enters dispatch at the next health observation — callers
        that need it routable NOW follow with ``poll_once()``. Returns
        the slot id (monotonic, never recycled)."""
        with self._lock:
            rid = str(replica_id) if replica_id is not None \
                else f"r{self._next_id}"
            if any(r.id == rid for r in self.replicas):
                raise ValueError(f"replica id {rid!r} already exists")
            self._next_id += 1
            rep = Replica(rid, transport)
            rep.state = state
            self.replicas.append(rep)
        logger.info("router: replica %s added (fleet size %d)", rid,
                    len(self.replicas))
        return rid

    def remove_replica(self, replica_id: str, drain: bool = True,
                       timeout: float = 60.0):
        """Shrink the fleet by one slot (autoscale scale-down): the
        replica leaves the dispatch set IMMEDIATELY (state DRAINING
        under the lock), then — outside the lock — drains via the
        uniform ``begin_drain`` path so zero queued requests drop, and
        is popped from the table. Returns the removed transport (the
        caller owns reaping its process)."""
        with self._lock:
            rep = next((r for r in self.replicas if r.id == replica_id),
                       None)
            if rep is None:
                raise KeyError(f"no replica {replica_id!r}")
            rep.state = DRAINING
        if drain:
            try:
                rep.transport.begin_drain()
                rep.transport.drain_wait(timeout=timeout)
            except Exception as e:  # noqa: BLE001 — best-effort drain
                logger.warning("drain of removed replica %s failed: %r",
                               replica_id, e)
        with self._lock:
            self.replicas = [r for r in self.replicas
                             if r.id != replica_id]
        logger.info("router: replica %s removed (fleet size %d)",
                    replica_id, len(self.replicas))
        return rep.transport

    def adopt_replicas(self, pairs) -> List[str]:
        """Replace the whole replica set — the standby's takeover path
        (``RouterHA``). ``pairs`` = ``[(replica_id, transport), ...]``
        mirrored from the dead active's last ``/healthz`` snapshot.
        State is tiny by design: breakers and inflight counts
        reconstruct from the ``poll_once()`` the caller issues next —
        adoption is re-poll + re-arm, not state transfer."""
        with self._lock:
            self.replicas = []
            self._rr = 0
            for rid, t in pairs:
                rep = Replica(str(rid), t)
                self.replicas.append(rep)
            if len({r.id for r in self.replicas}) != len(self.replicas):
                raise ValueError("adopted replica ids must be unique")
            self._next_id = max(self._next_id, len(self.replicas))
            self._first_answer_pending = True
        logger.info("router: adopted %d replica(s): %s",
                    len(self.replicas),
                    [r.id for r in self.replicas])
        return [r.id for r in self.replicas]

    def load_backlog_ms(self) -> Optional[float]:
        """Fleet pressure signal for the autoscaler: the MEAN backlog
        over routable replicas (capacity needs the average — the
        fleet-min is the 429 retry hint's business, not sizing's).
        None when no replica has reported health yet."""
        with self._lock:
            vals = [float(r.last_health["backlog_ms"])
                    for r in self.replicas
                    if r.state in (READY, WARMING)
                    and r.last_health.get("backlog_ms") is not None]
        return sum(vals) / len(vals) if vals else None

    def replica_metrics(self) -> Dict[str, dict]:
        """Per-replica serving-metrics snapshots — ONE router scrape
        then shows the whole fleet (metrics federation). Transports
        without the hook (duck-typed fakes) and unreachable replicas
        report an ``error`` entry instead of failing the scrape;
        transport calls run outside the router lock, and CONCURRENTLY —
        a wedged replica costs the scrape one probe timeout, not one
        per sick replica in series."""
        with self._lock:
            pairs = [(r.id, r.transport) for r in self.replicas]
        out: Dict[str, dict] = {}

        def one(rid, transport):
            try:
                out[rid] = transport.metrics_snapshot()
            except Exception as e:  # noqa: BLE001 — one sick replica
                # must not take down the fleet scrape
                out[rid] = {"error": repr(e)}

        threads = []
        for rid, transport in pairs:
            if not callable(getattr(transport, "metrics_snapshot",
                                    None)):
                continue
            th = threading.Thread(target=one, args=(rid, transport),
                                  daemon=True,
                                  name=f"metrics-scrape-{rid}")
            th.start()
            threads.append((rid, th))
        deadline = time.monotonic() + 5.0
        for rid, th in threads:
            th.join(max(0.0, deadline - time.monotonic()))
            if th.is_alive():
                # the transport outlived its own probe timeout; the
                # scrape moves on (the thread dies with its socket)
                out.setdefault(rid, {"error": "metrics scrape timed "
                                              "out"})
        return out

    # ------------------------------------------------------------- health
    def fleet_health(self) -> dict:
        with self._lock:
            reps = [r.snapshot() for r in self.replicas]
        ready = sum(1 for r in reps if r["state"] == READY)
        fenced = self.fence is not None and not self.fence.valid()
        return {
            "status": ("fenced" if fenced
                       else "ok" if ready else "unavailable"),
            "ready": ready > 0 and not fenced,
            "live": True,
            "ready_replicas": ready,
            "replicas": reps,
            "reloading": self._reloading,
            "role_held": (None if self.fence is None else not fenced),
            "role_epoch": getattr(self.fence, "epoch", None),
        }


class RouterHA:
    """Active/standby controller for one :class:`ReplicaRouter` — the
    warm-standby half of router HA.

    Two router processes front one fleet; a :class:`~paddle_tpu.dist.
    master.RoleLease` over a shared Store elects the ACTIVE. Each side
    runs a ``RouterHA`` over its (fenced) router:

    - **holding the role** — renew the lease every ``interval_ms``
      (chaos site ``lease_renew``: a drop is a lost renewal — enough of
      them and the lease lapses, the router's fence trips, and dispatch
      stops within one ttl: the partitioned-zombie-active guarantee).
    - **standing by** — poll the peer router's ``/healthz`` every
      ``interval_ms``, mirroring its replica snapshot (ids + addrs).
      The standby is WARM: its HTTP frontend is bound and answering
      (503 ``Unavailable`` while fenced, which ``ServingClient``
      rotates away from), so takeover needs no process start.
    - **takeover** — after ``adopt_after`` consecutive failed peer
      polls, ``try_acquire`` the role; the lease gates it (a live
      active's renewals make acquisition impossible, so a standby that
      merely cannot REACH the active cannot split-brain the fleet).
      On winning: chaos site ``router_failover`` fires, the mirrored
      replica set is adopted (default: one :class:`HTTPTransport` per
      advertised addr; in-process fleets inject ``adopt``), and one
      inline ``poll_once`` re-arms states/breakers — adoption is
      re-poll + re-arm because router state is tiny by design.

    ``step()`` runs one iteration inline (deterministic tests);
    ``start()`` runs it on a daemon thread at ``interval_ms``.
    """

    def __init__(self, router: ReplicaRouter, lease, *,
                 peer: Optional[Tuple[str, int]] = None,
                 peer_healthz: Optional[Callable[[], dict]] = None,
                 adopt: Optional[Callable[[List[dict]], List[Tuple[str, object]]]] = None,
                 interval_ms: float = 100.0,
                 adopt_after: int = 2):
        if router.fence is None:
            router.fence = lease
        self.router = router
        self.lease = lease
        self.peer = peer
        self._peer_healthz = peer_healthz
        self._adopt_builder = adopt
        self.interval_ms = float(interval_ms)
        self.adopt_after = int(adopt_after)
        self.peer_failures = 0
        self.last_peer_snapshot: List[dict] = []
        self.adoptions = 0
        self.adopted_at: Optional[float] = None  # monotonic
        # True while the last step held a valid active role: the
        # active→lapsed transition must be DATED even when the lease
        # dies silently (renewals dropped by a partition never reach
        # the store, so no refusal ever fires) — the postmortem's
        # "lease expiry" bracket comes from exactly this edge
        self._was_active = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ----------------------------------------------------------- control
    def start(self, take_role: bool = False) -> "RouterHA":
        if take_role:
            self.lease.try_acquire()
        self._thread = threading.Thread(target=self._loop,
                                        name="router-ha", daemon=True)
        self._thread.start()
        return self

    def shutdown(self, release: bool = True):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        if release and self.lease.valid():
            try:
                self.lease.release()
            except Exception as e:  # noqa: BLE001 — best-effort
                logger.warning("role release failed: %r", e)

    def _loop(self):
        while not self._stop.wait(self.interval_ms / 1e3):
            try:
                self.step()
            except Exception as e:  # noqa: BLE001 — the loop must live
                logger.error("router HA step crashed: %r", e)

    # -------------------------------------------------------------- duty
    def step(self):
        """One HA iteration: renew while active, watch + maybe adopt
        while standing by."""
        if self.lease.valid():
            try:
                renewed = self.lease.renew()
            except ConnectionError as e:
                # an injected lease_renew drop or a store hiccup: the
                # renewal is LOST (validity keeps ticking down; enough
                # losses and the fence trips) — never fatal here
                logger.warning("active-role renewal lost: %r", e)
                renewed = False
            if not renewed and not self.lease.valid():
                self._was_active = False
                log_event(
                    logger, "role_fenced",
                    "router %s FENCED: lost the active role (epoch "
                    "moved or lease lapsed); dispatch now refuses",
                    self.lease.holder_id, holder=self.lease.holder_id,
                    epoch=self.lease.epoch)
            else:
                self._was_active = True
            self.peer_failures = 0
            return
        if self._was_active:
            # the lease lapsed BETWEEN steps (e.g. every renewal was
            # partitioned away and never refused): this edge is the
            # only place the silent expiry can be dated
            self._was_active = False
            log_event(
                logger, "role_fenced",
                "router %s FENCED: active-role lease lapsed (renewals "
                "lost); dispatch now refuses",
                self.lease.holder_id, holder=self.lease.holder_id,
                epoch=self.lease.epoch)
        # ------------------------------------------------ standby watch
        try:
            h = self._poll_peer()
        except Exception as e:  # noqa: BLE001 — peer unreachable
            self.peer_failures += 1
            logger.debug("peer poll failed (%d/%d): %r",
                         self.peer_failures, self.adopt_after, e)
        else:
            reps = h.get("replicas") or []
            if reps:
                self.last_peer_snapshot = reps
            # a peer that answers but cannot serve (fenced, no ready
            # replica, dead) counts as failed — but the LEASE decides:
            # a healthy active's renewals make try_acquire impossible
            self.peer_failures = (0 if h.get("ready")
                                  else self.peer_failures + 1)
        if self.peer_failures >= self.adopt_after \
                and self.lease.try_acquire():
            self._take_over()

    def _poll_peer(self) -> dict:
        if self._peer_healthz is not None:
            return self._peer_healthz()
        if self.peer is None:
            raise RuntimeError("standby has no peer to watch (pass "
                               "peer=(host, port) or peer_healthz=)")
        host, port = self.peer
        # a 503 body still carries the fleet snapshot — _get_json reads
        # it whatever the status (same contract as replica healthz)
        status, data = _get_json(host, port, "/healthz", 2.0)
        if not isinstance(data, dict) or "live" not in data:
            raise ConnectionError(
                f"peer {host}:{port} healthz is not a health "
                f"payload (HTTP {status})")
        return data

    def _take_over(self):
        """Adopt the fleet: rebuild the replica set from the last peer
        snapshot, re-arm via one poll, start answering."""
        if _chaos._ACTIVE is not None:
            _chaos._ACTIVE.hit("router_failover",
                               holder=self.lease.holder_id,
                               epoch=self.lease.epoch)
        snaps = self.last_peer_snapshot
        if self._adopt_builder is not None:
            pairs = self._adopt_builder(snaps)
        else:
            pairs = []
            for s in snaps:
                addr = s.get("addr")
                if not addr:
                    logger.warning(
                        "adoption: replica %s advertises no addr "
                        "(in-process transport?); skipped",
                        s.get("id"))
                    continue
                host, _, port = addr.rpartition(":")
                pairs.append((s["id"], HTTPTransport(host, int(port))))
        if pairs:
            self.router.adopt_replicas(pairs)
        self.router.poll_once()
        self.adoptions += 1
        self.adopted_at = time.monotonic()
        self.peer_failures = 0
        self.router.metrics.inc("adoptions_total")
        log_event(
            logger, "ha_takeover",
            "router %s ADOPTED the fleet (epoch %d): %d replica(s), "
            "%d ready", self.lease.holder_id, self.lease.epoch,
            len(self.router.replicas),
            self.router.fleet_health()["ready_replicas"],
            holder=self.lease.holder_id, epoch=self.lease.epoch,
            replicas=len(self.router.replicas))


# ------------------------------------------------------------- HTTP tier

class RouterHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def __init__(self, addr, router: ReplicaRouter, reload_builder=None,
                 registry=None, model_path=None):
        super().__init__(addr, _RouterHandler)
        self.router = router
        self.reload_builder = reload_builder
        # the artifact path the fleet currently serves — the rollback
        # anchor for /admin/reload (a refused artifact rolls the fleet
        # back to this path instead of leaving a replica down)
        self.current_model_path = model_path
        # optional obs.MetricsRegistry: extra federated providers (the
        # serve_fleet supervisor + autoscaler) riding this frontend's
        # /metrics so one scrape covers the whole process
        self.registry = registry


class _RouterHandler(JSONHandler):
    """The router's HTTP frontend: same endpoint contract as the single-
    replica server (a client cannot tell them apart), plus routing
    provenance headers (``X-Replica-Id``, ``X-Failovers``, ``X-Hedged``)
    and the fleet admin surface (``POST /admin/reload``)."""

    # -------------------------------------------------------------- GET
    def do_GET(self):
        self._tctx = _trace.ctx_from_headers(self.headers)
        router: ReplicaRouter = self.server.router
        path = self.path.split("?", 1)[0]
        if path == "/healthz":
            h = router.fleet_health()
            self._send(200 if h["ready"] else 503, h)
        elif path == "/livez":
            self._send(200, {"status": "ok", "live": True})
        elif path == "/metrics":
            registry = getattr(self.server, "registry", None)
            if "format=json" in self.path:
                snap = router.metrics.snapshot()
                snap["fleet"] = router.fleet_health()
                # federation: per-replica serving snapshots + any extra
                # registered providers — one scrape, the whole fleet
                snap["replicas_metrics"] = router.replica_metrics()
                if registry is not None:
                    snap["federation"] = registry.snapshot()
                self._send(200, snap)
            else:
                from paddle_tpu.obs.registry import prom_from_dict
                chunks = [router.metrics.to_prometheus().rstrip("\n")]
                for rid, rsnap in sorted(
                        router.replica_metrics().items()):
                    chunks.extend(prom_from_dict(
                        "paddle_tpu_replica", rsnap,
                        labels={"replica": rid}))
                if registry is not None:
                    chunks.append(registry.to_prometheus().rstrip("\n"))
                self._send(200, ("\n".join(chunks) + "\n").encode(),
                           content_type="text/plain; version=0.0.4")
        else:
            self._send(404, {"error": {"code": "not_found",
                                       "message": self.path}})

    # ------------------------------------------------------------- POST
    def do_POST(self):
        self._tctx = _trace.ctx_from_headers(self.headers)
        router: ReplicaRouter = self.server.router
        path = self.path.split("?", 1)[0]
        if path == "/admin/reload":
            self._admin_reload()
            return
        if path == "/admin/config":
            self._admin_config()
            return
        kind = {"/v1/score": "score", "/v1/generate": "generate"}.get(path)
        if kind is None:
            self._send(404, {"error": {"code": "not_found",
                                       "message": self.path}})
            return
        prov: Dict = {}
        try:
            body = self._body()
            deadline_ms = body.get("deadline_ms")
            gen = ({"beam_size": body.get("beam_size"),
                    "max_length": body.get("max_length")}
                   if kind == "generate" else {})
            if "rows" in body:
                self._rows(router, kind, body, deadline_ms, gen)
                return
            if "sample" not in body:
                raise BadRequest("need \"sample\" (one request) or "
                                 "\"rows\" (a list)")
            result, prov = router.dispatch(
                body["sample"], kind=kind, deadline_ms=deadline_ms,
                trace_parent=self._tctx, **gen)
            self._send(200, result, headers=self._prov_headers(prov))
        except ServingError as e:
            prov = getattr(e, "provenance", prov)
            self._send_error(e, headers=self._prov_headers(prov))
        except Exception as e:  # noqa: BLE001 — the only 500 source
            logger.error("unhandled router error: %r", e)
            self._send_error(ServingError(repr(e)))

    @staticmethod
    def _prov_headers(prov: Dict) -> Dict:
        if not prov:
            return {}
        return {"X-Replica-Id": prov.get("replica"),
                "X-Model-Version": prov.get("model_version"),
                "X-Failovers": prov.get("failovers"),
                "X-Hedged": prov.get("hedges")}

    def _rows(self, router, kind, body, deadline_ms, gen):
        if not isinstance(body["rows"], list) or not body["rows"]:
            raise BadRequest("\"rows\" must be a non-empty list")
        # rows dispatch CONCURRENTLY: the replicas' batchers coalesce
        # same-kind rows landing together, so a rows call keeps the
        # batching win it has on the single-replica server (sequential
        # dispatch would serialize one device launch per row)
        rows = body["rows"]
        results = [None] * len(rows)
        any_err = [False]

        tctx = self._tctx  # worker threads get no ambient contextvars

        def one(i, row):
            try:
                result, prov = router.dispatch(
                    row, kind=kind, deadline_ms=deadline_ms,
                    trace_parent=tctx, **gen)
                result = dict(result)
                result["replica"] = prov.get("replica")
                results[i] = result
            except ServingError as e:
                results[i] = e.to_wire()
                any_err[0] = True

        workers = [threading.Thread(target=one, args=(i, row),
                                    daemon=True)
                   for i, row in enumerate(rows)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(120.0)
        for i, r in enumerate(results):
            if r is None:  # a worker thread hung past the join bound
                results[i] = DeadlineExceeded(
                    "no answer within the server wait bound").to_wire()
                any_err[0] = True
        self._send(200 if not any_err[0] else 207, {"results": results})

    def _admin_config(self):
        """Fleet-wide hot reconfig: the body is a
        :class:`~paddle_tpu.serving.tuner.FleetConfig` knob delta.
        Synchronous; 200 carries before/after, a refusal answers the
        typed 409 ``config_rejected`` with the incumbent still serving
        on every replica (``ReplicaRouter.apply_config`` rolled back
        any partially-applied fan-out)."""
        try:
            self._send(200, self.server.router.apply_config(
                self._body()))
        except ServingError as e:
            self._send_error(e)
        except Exception as e:  # noqa: BLE001
            logger.error("config apply failed: %r", e)
            self._send(500, {"error": {"code": "config_failed",
                                       "message": repr(e)}})

    def _admin_reload(self):
        """Rolling hot-swap to a new merged model: ``{"model_path":
        "/path/new.ptmodel"}``. Synchronous — the response carries the
        per-replica versions after the roll (long request by design; the
        fleet keeps serving throughout). When the new artifact refuses a
        replica (warmup failure — notably a quantized artifact drifting
        past the accuracy gate), the fleet ROLLS BACK to the previously
        served path and the call answers a typed 409 ``reload_rejected``
        carrying the refusal; the bad artifact is never published."""
        builder = self.server.reload_builder
        try:
            if builder is None:
                raise BadRequest(
                    "this router was started without a reload builder "
                    "(--job=serve --replicas N wires one); rolling "
                    "reload over HTTP is unavailable")
            body = self._body()
            path = body.get("model_path")
            if not path:
                raise BadRequest("need \"model_path\" (a merged PTM1 "
                                 "artifact)")
            prev = self.server.current_model_path
            fallback = ((lambda rid: builder(prev, rid))
                        if prev else None)
            versions = self.server.router.rolling_reload(
                lambda rid: builder(path, rid), fallback_build=fallback)
            self.server.current_model_path = path
            self._send(200, {"status": "ok", "versions": versions})
        except ServingError as e:
            self._send_error(e)
        except Exception as e:  # noqa: BLE001
            logger.error("rolling reload failed: %r", e)
            self._send(500, {"error": {"code": "reload_failed",
                                       "message": repr(e)}})


def make_router_server(router: ReplicaRouter, host: str = "127.0.0.1",
                       port: int = 0, reload_builder=None,
                       registry=None, model_path=None):
    """Bind the router frontend (port=0 = ephemeral, for tests); the
    bound port is ``server.server_address[1]``. ``registry`` federates
    extra metric providers (supervisor, autoscaler) into ``/metrics``;
    ``model_path`` seeds the rollback anchor for ``/admin/reload``."""
    return RouterHTTPServer((host, port), router,
                            reload_builder=reload_builder,
                            registry=registry, model_path=model_path)


def install_router_signal_handlers(router: ReplicaRouter,
                                   server=None):
    """SIGTERM/SIGINT -> drain EVERY replica (zero queued drops), then
    stop the router listener. Returns the previous handlers (tests and
    embedders restore them) — the fleet twin of ``server.py:
    install_signal_handlers``."""
    import signal

    def _drain(signum, frame):
        logger.info("signal %d: draining the fleet", signum)

        def _finish():
            router.shutdown(drain=True)
            if server is not None:
                server.shutdown()

        threading.Thread(target=_finish, daemon=True,
                         name="router-drain").start()

    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(sig, _drain)
    return prev


def serve_router_forever(router: ReplicaRouter, host: str = "127.0.0.1",
                         port: int = 8000, reload_builder=None,
                         ready_line: bool = True, registry=None,
                         model_path=None):
    """CLI entry for ``--job=serve --replicas N``: start the health
    loop, bind, install SIGTERM handlers that drain EVERY replica (zero
    queued drops), serve until drained."""
    router.start()
    server = make_router_server(router, host, port,
                                reload_builder=reload_builder,
                                registry=registry, model_path=model_path)
    install_router_signal_handlers(router, server)
    if ready_line:
        h = router.fleet_health()
        print(f"router serving on http://{host}:"
              f"{server.server_address[1]} "
              f"({h['ready_replicas']}/{len(router.replicas)} replicas "
              "ready)", flush=True)
    try:
        server.serve_forever(poll_interval=0.2)
    finally:
        server.server_close()
        router.shutdown(drain=True)
    return 0
