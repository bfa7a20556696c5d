"""Serving observability plane: latency split, occupancy, bucket hits.

The training side answers "is the chip waiting on the host?" with
``utils/profiler.StepBreakdown``; the serving side's first-order
questions are different — *where does a request's latency go* and *how
full are the batches the chip actually runs*. Four phases partition a
request's life:

- ``queue_wait``    — enqueue until the batcher picks it into a batch
  (the dynamic-batching tax; grows with ``batch_timeout`` and load).
- ``pad_overhead``  — batch assembly: feeder convert, pad-to-bucket,
  host→device placement.
- ``compute``       — the jitted forward (or beam search) through the
  device→host fetch.
- ``decode``        — slicing the batch back into per-request rows and
  converting to wire types.

Batch occupancy (real rows / padded rows) is the padding waste the
bucket menu costs — the serving analogue of the feeder's exactly-ignored
row masking; per-bucket hit counts show which compiled variants earn
their warmup. Shed/deadline/bad-request counters complete the picture.

The generate path adds the decode economics (chunked early-exit search +
continuous batching, ``docs/generation.md``): per-request
``decode_steps`` actually executed vs ``max_length`` (with
``decode_steps_saved_total`` the steps the early exit refused to pay)
and the ``lane_occupancy`` series — live lanes / session width sampled
at every chunk boundary, the continuous-batching analogue of batch
occupancy (how full the decode batch the chip actually runs is, now
that lanes retire and admit mid-flight).

The fleet tier adds :class:`RouterMetrics` — the front-tier router's
view: per-replica dispatch counts, failovers, hedges (fired vs won),
ejections/respawns/reloads, and the fleet-wide end-to-end latency
reservoir (what a CLIENT sees through the router, queue + failover +
hedge wait included — the number the kill-and-respawn bench reports as
fleet p99).

Exported two ways: :meth:`ServingMetrics.snapshot` (the ``/metrics``
JSON) and :meth:`to_prometheus` (text format, ``# TYPE`` lines
included, for scrapers).

Quantiles come from a bounded reservoir of the most recent samples
(deque, default 4096) — honest recent-window p50/p95/p99 without
unbounded memory; counts and sums are exact over the process lifetime.
"""

from __future__ import annotations

import threading
from collections import Counter, deque
from typing import Dict, Optional

PHASES = ("queue_wait", "pad_overhead", "compute", "decode")


class LatencyStat:
    """Exact count/sum + recent-window quantiles for one phase (ms)."""

    def __init__(self, window: int = 4096):
        self.count = 0
        self.sum_ms = 0.0
        self._recent = deque(maxlen=window)

    def add(self, ms: float):
        self.count += 1
        self.sum_ms += ms
        self._recent.append(ms)

    def quantile(self, q: float) -> Optional[float]:
        if not self._recent:
            return None
        vals = sorted(self._recent)
        idx = min(int(q * len(vals)), len(vals) - 1)
        return vals[idx]

    def snapshot(self) -> dict:
        out = {"count": self.count,
               "sum_ms": round(self.sum_ms, 3),
               "mean_ms": round(self.sum_ms / self.count, 3)
               if self.count else None}
        for name, q in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99)):
            v = self.quantile(q)
            out[f"{name}_ms"] = round(v, 3) if v is not None else None
        return out


class ServingMetrics:
    """Thread-safe metric registry for one serving engine."""

    COUNTERS = ("requests_total", "responses_total", "batches_total",
                "shed_total", "deadline_exceeded_total",
                "bad_request_total", "internal_error_total",
                "decode_chunks_total", "continuous_admissions_total",
                "decode_steps_total", "decode_steps_saved_total",
                # hot-reconfig plane (r21): knob deltas applied vs
                # refused typed (off-menu max_batch etc.), and SLO-
                # controller decisions when one targets this engine
                "config_applies_total", "config_rejected_total",
                "tune_decisions_total")

    def __init__(self, window: int = 4096):
        self._lock = threading.Lock()
        self.latency: Dict[str, LatencyStat] = {
            p: LatencyStat(window) for p in PHASES + ("total",)}
        self.occupancy = LatencyStat(window)  # unit: fraction, not ms
        self.decode_steps = LatencyStat(window)  # unit: steps, not ms
        self.lane_occupancy = LatencyStat(window)  # unit: fraction
        self.bucket_hits: Counter = Counter()
        self.counters = {c: 0 for c in self.COUNTERS}
        self.real_rows_total = 0
        self.padded_rows_total = 0

    # ------------------------------------------------------------ record
    def inc(self, name: str, n: int = 1):
        with self._lock:
            self.counters[name] += n

    def observe_request(self, phases_ms: Dict[str, float]):
        """One answered request's per-phase latency (ms); ``total`` is
        derived as the sum so the split always partitions it."""
        with self._lock:
            total = 0.0
            for p in PHASES:
                ms = float(phases_ms.get(p, 0.0))
                self.latency[p].add(ms)
                total += ms
            self.latency["total"].add(total)
            self.counters["responses_total"] += 1

    def observe_batch(self, bucket_key: str, real_rows: int,
                      padded_rows: int):
        with self._lock:
            self.counters["batches_total"] += 1
            self.bucket_hits[bucket_key] += 1
            self.real_rows_total += int(real_rows)
            self.padded_rows_total += int(padded_rows)
            if padded_rows:
                self.occupancy.add(real_rows / padded_rows)

    def observe_decode(self, steps, saved):
        """One request's decode-step accounting: ``steps`` actually
        executed, ``saved`` = max_length - steps the early exit (or
        mid-flight retirement) refused to pay."""
        if steps is None:
            return
        with self._lock:
            self.decode_steps.add(float(steps))
            self.counters["decode_steps_total"] += int(steps)
            self.counters["decode_steps_saved_total"] += int(saved or 0)

    def observe_lanes(self, live: int, width: int):
        """Continuous-batching lane occupancy at one chunk boundary."""
        with self._lock:
            self.counters["decode_chunks_total"] += 1
            if width:
                self.lane_occupancy.add(live / width)

    # ------------------------------------------------------------ export
    def snapshot(self) -> dict:
        with self._lock:
            occ = self.occupancy.snapshot()
            dec = self.decode_steps.snapshot()
            lanes = self.lane_occupancy.snapshot()
            return {
                "latency_ms": {p: s.snapshot()
                               for p, s in self.latency.items()},
                "batch_occupancy": {
                    "mean": round(self.real_rows_total
                                  / self.padded_rows_total, 4)
                    if self.padded_rows_total else None,
                    "p50": occ["p50_ms"],  # fraction, reservoir window
                    "real_rows_total": self.real_rows_total,
                    "padded_rows_total": self.padded_rows_total,
                },
                # the *_ms suffixes below come from LatencyStat's generic
                # snapshot; units here are decoder steps / lane fraction
                "decode_steps": {
                    "count": dec["count"], "mean": dec["mean_ms"],
                    "p50": dec["p50_ms"], "p95": dec["p95_ms"],
                    "p99": dec["p99_ms"],
                },
                "lane_occupancy": {
                    "count": lanes["count"], "mean": lanes["mean_ms"],
                    "p50": lanes["p50_ms"],
                },
                "bucket_hits": dict(self.bucket_hits),
                **self.counters,
            }

    def to_prometheus(self, prefix: str = "paddle_tpu_serving") -> str:
        return _serving_prometheus(self, prefix)


class RouterMetrics:
    """Thread-safe metric registry for one replica router."""

    COUNTERS = ("dispatches_total", "responses_total", "failovers_total",
                "hedges_total", "hedge_wins_total", "ejections_total",
                "breaker_open_total", "respawns_total", "reloads_total",
                "reload_rollbacks_total", "shed_total",
                "replica_deaths_total",
                # HA + elastic-capacity plane (r14): fenced dispatch
                # refusals (the old active provably stopped), standby
                # fleet adoptions, autoscale actions, supervisor kills
                "fenced_total", "adoptions_total",
                "scale_up_total", "scale_down_total",
                "replica_kills_total", "lease_renew_lost_total",
                # hot-reconfig plane (r21): fleet-wide knob deltas
                # applied vs refused (fan-out rolled back), and SLO-
                # controller decisions when one targets this router
                "config_applies_total", "config_rejected_total",
                "tune_decisions_total")

    def __init__(self, window: int = 4096):
        self._lock = threading.Lock()
        self.counters = {c: 0 for c in self.COUNTERS}
        # fleet-wide end-to-end latency (ms) as seen THROUGH the router:
        # replica service time + failover/hedge overhead
        self.fleet_latency = LatencyStat(window)
        self.replica_dispatches: Counter = Counter()

    def inc(self, name: str, n: int = 1):
        with self._lock:
            self.counters[name] += n

    def observe_dispatch(self, replica_id: str, ms: Optional[float]):
        with self._lock:
            self.counters["responses_total"] += 1
            self.replica_dispatches[replica_id] += 1
            if ms is not None:
                self.fleet_latency.add(ms)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "fleet_latency_ms": self.fleet_latency.snapshot(),
                "replica_dispatches": dict(self.replica_dispatches),
                **self.counters,
            }

    def to_prometheus(self, prefix: str = "paddle_tpu_router") -> str:
        s = self.snapshot()
        lines = []
        for c in self.COUNTERS:
            lines.append(f"# TYPE {prefix}_{c} counter")
            lines.append(f"{prefix}_{c} {s[c]}")
        lines.append(f"# TYPE {prefix}_fleet_latency_ms summary")
        lat = s["fleet_latency_ms"]
        for q, key in (("0.5", "p50_ms"), ("0.95", "p95_ms"),
                       ("0.99", "p99_ms")):
            if lat[key] is not None:
                lines.append(
                    f'{prefix}_fleet_latency_ms{{quantile="{q}"}} '
                    f'{lat[key]}')
        lines.append(f"{prefix}_fleet_latency_ms_count {lat['count']}")
        lines.append(f"# TYPE {prefix}_replica_dispatches counter")
        for rid, n in sorted(s["replica_dispatches"].items()):
            lines.append(
                f'{prefix}_replica_dispatches{{replica="{rid}"}} {n}')
        return "\n".join(lines) + "\n"


def _serving_prometheus(m: "ServingMetrics", prefix: str) -> str:
    s = m.snapshot()
    lines = []
    for c in m.COUNTERS:
        lines.append(f"# TYPE {prefix}_{c} counter")
        lines.append(f"{prefix}_{c} {s[c]}")
    lines.append(f"# TYPE {prefix}_latency_ms summary")
    for phase, st in s["latency_ms"].items():
        for q, key in (("0.5", "p50_ms"), ("0.95", "p95_ms"),
                       ("0.99", "p99_ms")):
            v = st[key]
            if v is not None:
                lines.append(
                    f'{prefix}_latency_ms{{phase="{phase}",'
                    f'quantile="{q}"}} {v}')
        lines.append(
            f'{prefix}_latency_ms_count{{phase="{phase}"}} '
            f'{st["count"]}')
        lines.append(
            f'{prefix}_latency_ms_sum{{phase="{phase}"}} '
            f'{st["sum_ms"]}')
    occ = s["batch_occupancy"]
    lines.append(f"# TYPE {prefix}_batch_occupancy gauge")
    if occ["mean"] is not None:
        lines.append(f"{prefix}_batch_occupancy {occ['mean']}")
    lines.append(f"# TYPE {prefix}_decode_steps summary")
    for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
        v = s["decode_steps"][key]
        if v is not None:
            lines.append(
                f'{prefix}_decode_steps{{quantile="{q}"}} {v}')
    lines.append(
        f'{prefix}_decode_steps_count {s["decode_steps"]["count"]}')
    lines.append(f"# TYPE {prefix}_lane_occupancy gauge")
    if s["lane_occupancy"]["mean"] is not None:
        lines.append(
            f"{prefix}_lane_occupancy {s['lane_occupancy']['mean']}")
    lines.append(f"# TYPE {prefix}_bucket_hits counter")
    for bucket, hits in sorted(s["bucket_hits"].items()):
        lines.append(
            f'{prefix}_bucket_hits{{bucket="{bucket}"}} {hits}')
    return "\n".join(lines) + "\n"
