"""Evidence-artifact schema check (PT401): ``BENCH_*.json``,
``MULTICHIP_*.json``, ``ACCURACY_*.json``, ``MEM_*.json`` and
``HEALTH_*.json``.

These artifacts are the evidence trail of the rounds before the chip
(CPU-side structure runs, multichip dryruns, real-corpus accuracy
runs); the program that wrote the ``BENCH_*`` / ``WORKLOAD_*`` families
is gone and nothing regenerates them (ROADMAP D1b) — the numbers users
are told live in ``PERF_LEDGER.jsonl``, written by ``python3 -m
benchmark.run``. A malformed artifact —
truncated JSON, a NaN ratio, an A/B metric missing its sides — should
fail at *lint* time, not at ROADMAP-review time when the run that
produced it is long gone.

The artifact FAMILY is keyed by filename (content sniffing would let a
truncated artifact of one family quietly validate against another's
looser schema):

- ``MULTICHIP_*``: ``{"n_devices": int, "rc": int, "ok": bool,
  "skipped": bool, "tail": str}`` — the ``dryrun_multichip`` capture;
  the tail is the re-checkable evidence and must be present even on a
  skip.
- ``ACCURACY_*``: ``{"platform": str, ...}`` plus at least one named
  run section (a dict) — an accuracy artifact with no run sections
  recorded nothing.
- ``TRACE_*`` (distributed-trace evidence, an ``obs.trace``
  ``dump_jsonl`` bundled as one object): ``{"spans": [...]}`` with a
  NON-EMPTY span list, every span carrying string ``trace_id`` /
  ``span_id`` / ``name``, numeric ``ts`` and ``dur_ms >= 0``, spans
  sorted by ``ts`` (monotone file order), and every non-null
  ``parent_id`` resolving to another span's ``span_id`` in the same
  file — a trace whose parents dangle reconstructs nothing.
- ``HEALTH_*`` (committed training-health timelines: a snapshot of
  an ``obs/events.py`` JSONL bundled as one object):
  ``{"run": str, "period": int >= 0, "events": [...]}`` with a
  NON-EMPTY events list, every event carrying an int ``step >= 0``
  in monotone non-decreasing order and a finite numeric ``loss`` —
  a timeline with no steps, shuffled steps, or NaN losses recorded
  nothing diffable (``tools/healthview.py`` is the consumer).
- ``MEM_*`` (optional trend snapshots of graftlint pass 5's
  per-program per-device byte manifests, emitted by
  ``python -m paddle_tpu.analysis --json | jq .mem_manifest``):
  ``{"programs": {name: {field: int >= 0, ...}, ...}}`` with a
  non-empty programs map — a malformed snapshot is a finding, not a
  silently unplottable file.
- ``WORKLOAD_*`` (committed request traces, replayed by
  ``tests/test_workload_replay.py``):
  ``{"workload": str, "version": 1, "n_events": int, "duration_s":
  num >= 0, "events": [...]}`` with a NON-EMPTY events list whose
  length matches ``n_events``, every event carrying the full replay
  key set (``serving/workload.py:EVENT_KEYS``), a ``kind`` in
  {score, generate}, and numeric ``t >= 0`` in monotone non-decreasing
  order — a trace that cannot be re-offered at its recorded offsets
  tunes nothing.
- ``BENCH_*`` (shape-sniffed among its real generations):
  **metric style** (r07+, also BENCH_LIVE) ``{"metric": str,
  "platform": str, ...}`` where every ``*_vs_*`` ratio key must be a
  finite number (or null when a side was skipped) with both A/B sides
  present; **harness style** (r01–r05) ``{"n": ..., "cmd": str, "rc":
  int, ...}``; **watcher style** (r06) ``{"round": ..., "cmd": ...,
  "parsed": dict, ...}``. Metric-style artifacts whose metric starts
  with ``serving_fleet`` (BENCH_r13, the kill-and-respawn bench) must
  additionally carry the cold-start A/B sides (``cold_start_live_ms`` /
  ``cold_start_cache_ms``), ``fleet_p99_ms``, and the
  ``fleet_failovers_total`` / ``fleet_failed_non_shed`` counters — the
  failover and zero-drop evidence. Metrics starting with
  ``serving_fleet_autoscale`` (BENCH_r14, the self-operating fleet)
  must FURTHER carry ``autoscale_replica_trajectory`` (a non-empty list
  of replica counts — did the count follow the ramp inside the
  bounds?), ``autoscale_p99_ms``, and ``fleet_failed_non_shed`` summed
  across rounds. Metrics starting with ``overlap`` (BENCH_r18, the
  FSDP gather-overlap x fused-kernel 2x2) must carry the step-time A/B
  sides (``overlap_on_steps_per_sec`` / ``overlap_off_steps_per_sec``),
  the int exposed-collective counts
  (``exposed_collectives_overlap_on`` / ``..._off``) and the numeric
  exposed-comm fractions (``exposed_comm_frac_overlap_on`` /
  ``..._off``) — the structural overlap evidence. Metrics starting
  with ``serving_quant`` (BENCH_r19, the quantized serving three-way)
  must carry all three precision sides (``quant_fp32_p50_ms`` /
  ``quant_bf16_p50_ms`` / ``quant_int8_p50_ms``), FINITE gate deltas
  (``quant_gate_delta_bf16`` / ``quant_gate_delta_int8``) and the
  bool ``quant_gate_passed`` — an un-gated speedup is not evidence.
  Metrics starting with ``serving_autotune`` (BENCH_r21, the
  self-tuning loop) must carry ``autotune_workloads`` — a non-empty
  list of ``WORKLOAD_*.json`` filenames each resolving to a file NEXT
  TO the artifact (the trace/score JOIN: a tune score whose trace is
  gone is unreplayable evidence), the per-mix A/B score sides
  (``autotune_<mix>_default_score`` / ``..._tuned_score``), each mix's
  ``autotune_<mix>_replay_drift`` within the declared
  ``autotune_drift_bound``, and the int ``fleet_failed_non_shed``
  summed over every replay. Metrics starting with ``serve_train``
  (BENCH_r20, the online
  learning loop) must carry ``serve_train_error_trajectory`` (a
  non-empty list of finite held-out error numbers, one per published
  version — the does-online-training-actually-learn evidence), the
  int ``fleet_failed_non_shed`` summed over every round (the fleet
  stayed up through the hot-swaps), and the int ``publishes_total`` /
  ``rollbacks_total`` counters (how many versions went live, and how
  many refused artifacts rolled back to the incumbent).

Everything must parse as one JSON object with finite numbers
throughout (NaN/Infinity are emitted by a crashed averaging step and
json.dumps happily writes them).
"""

from __future__ import annotations

import glob
import json
import math
import os
from typing import Any, List, Optional, Sequence

from paddle_tpu.analysis.findings import Finding


def _walk_numbers(obj: Any, path: str = "$"):
    if isinstance(obj, dict):
        for k, v in obj.items():
            yield from _walk_numbers(v, f"{path}.{k}")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            yield from _walk_numbers(v, f"{path}[{i}]")
    elif isinstance(obj, float):
        yield path, obj


def check_bench_file(path: str, rel: str) -> List[Finding]:
    findings: List[Finding] = []

    def bad(msg: str):
        findings.append(Finding("PT401", rel, 1, msg))

    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        bad(f"unparseable bench artifact: {e}")
        return findings
    if not isinstance(data, dict):
        bad(f"bench artifact must be one JSON object, got "
            f"{type(data).__name__}")
        return findings
    # the artifact FAMILY comes from the filename, not sniffed content:
    # a BENCH file whose crashed writer dropped 'metric' but kept
    # 'platform' must fail as an unrecognized bench shape, not
    # quietly validate against the (looser) accuracy schema
    base = os.path.basename(rel)
    if base.startswith("MULTICHIP_"):
        # the dryrun_multichip capture
        if not isinstance(data.get("n_devices"), int) or isinstance(
                data.get("n_devices"), bool):
            bad("multichip artifact missing int 'n_devices'")
        if not isinstance(data.get("rc"), int) or isinstance(
                data.get("rc"), bool):
            bad("multichip artifact missing int 'rc'")
        for key in ("ok", "skipped"):
            if not isinstance(data.get(key), bool):
                bad(f"multichip artifact missing bool {key!r}")
        if not isinstance(data.get("tail"), str):
            bad("multichip artifact missing str 'tail' (the "
                "re-checkable dryrun evidence)")
    elif base.startswith("TRACE_"):
        spans = data.get("spans")
        if not (isinstance(spans, list) and spans):
            bad("trace artifact needs a non-empty 'spans' list")
        else:
            ids = {s.get("span_id") for s in spans
                   if isinstance(s, dict)}
            last_ts = None
            for i, s in enumerate(spans):
                if not isinstance(s, dict):
                    bad(f"span[{i}] must be an object")
                    continue
                for k in ("trace_id", "span_id", "name"):
                    if not (isinstance(s.get(k), str) and s.get(k)):
                        bad(f"span[{i}] missing non-empty str {k!r}")
                ts, dur = s.get("ts"), s.get("dur_ms")
                if not isinstance(ts, (int, float)) or isinstance(
                        ts, bool):
                    bad(f"span[{i}] missing numeric 'ts'")
                    ts = None
                if (not isinstance(dur, (int, float))
                        or isinstance(dur, bool) or dur < 0):
                    bad(f"span[{i}] needs numeric 'dur_ms' >= 0")
                if ts is not None:
                    if last_ts is not None and ts < last_ts:
                        bad(f"span[{i}] breaks monotone file order "
                            f"(ts {ts} < previous {last_ts}) — the "
                            "writer sorts by start time")
                    last_ts = ts
                parent = s.get("parent_id")
                if parent is not None and parent not in ids:
                    bad(f"span[{i}] parent_id {parent!r} resolves to "
                        "no span in this file — a dangling parent "
                        "reconstructs nothing")
    elif base.startswith("HEALTH_"):
        # a committed training-health timeline (obs/events.py records
        # bundled as one object; tools/healthview.py renders/diffs it)
        if not (isinstance(data.get("run"), str) and data.get("run")):
            bad("health artifact needs a non-empty str 'run'")
        period = data.get("period")
        if (not isinstance(period, int) or isinstance(period, bool)
                or period < 0):
            bad("health artifact needs int 'period' >= 0 (the stat "
                "cadence the timeline was recorded at)")
        events = data.get("events")
        if not (isinstance(events, list) and events):
            bad("health artifact needs a non-empty 'events' list "
                "(a timeline with no steps recorded nothing)")
        else:
            last_step = None
            for i, e in enumerate(events):
                if not isinstance(e, dict):
                    bad(f"events[{i}] must be an object")
                    continue
                step = e.get("step")
                if (not isinstance(step, int) or isinstance(step, bool)
                        or step < 0):
                    bad(f"events[{i}] missing int 'step' >= 0")
                    step = None
                if step is not None:
                    if last_step is not None and step < last_step:
                        bad(f"events[{i}] breaks monotone step order "
                            f"(step {step} < previous {last_step}) — "
                            "a shuffled timeline diffs nothing")
                    last_step = step
                loss = e.get("loss")
                if (not isinstance(loss, (int, float))
                        or isinstance(loss, bool)):
                    bad(f"events[{i}] missing numeric 'loss'")
                # non-finite losses are caught by the global
                # finite-number walk below, with their exact path
    elif base.startswith("WORKLOAD_"):
        # a committed request trace (serving/workload.py): replayable
        # by construction or a finding — the tuner's scores are only
        # evidence while the trace they came from still re-offers
        from paddle_tpu.serving.workload import (EVENT_KEYS,
                                                 WORKLOAD_VERSION)
        if not (isinstance(data.get("workload"), str)
                and data.get("workload")):
            bad("workload artifact needs a non-empty str 'workload'")
        if data.get("version") != WORKLOAD_VERSION:
            bad(f"workload artifact version {data.get('version')!r} != "
                f"{WORKLOAD_VERSION}")
        events = data.get("events")
        if not (isinstance(events, list) and events):
            bad("workload artifact needs a non-empty 'events' list "
                "(a trace with no offers replays nothing)")
        else:
            if data.get("n_events") != len(events):
                bad(f"workload artifact n_events {data.get('n_events')!r}"
                    f" != {len(events)} events present (truncated?)")
            last_t = None
            for i, e in enumerate(events):
                if not isinstance(e, dict):
                    bad(f"events[{i}] must be an object")
                    continue
                missing = [k for k in EVENT_KEYS if k not in e]
                if missing:
                    bad(f"events[{i}] missing replay key(s) {missing}")
                if e.get("kind") not in ("score", "generate"):
                    bad(f"events[{i}] unknown kind {e.get('kind')!r}")
                t = e.get("t")
                if (not isinstance(t, (int, float))
                        or isinstance(t, bool) or t < 0):
                    bad(f"events[{i}] needs numeric 't' >= 0 (the "
                        "recorded arrival offset)")
                elif last_t is not None and t < last_t:
                    bad(f"events[{i}] breaks monotone arrival order "
                        f"(t {t} < previous {last_t}) — the recorder "
                        "snapshot sorts by offset")
                else:
                    last_t = t
    elif base.startswith("MEM_"):
        # a pass-5 memory-manifest trend snapshot
        progs = data.get("programs")
        if not (isinstance(progs, dict) and progs):
            bad("mem artifact needs a non-empty 'programs' object "
                "(per-program per-device byte manifests)")
        else:
            for name, fields in progs.items():
                if not isinstance(fields, dict) or not fields:
                    bad(f"mem artifact program {name!r} must map to a "
                        "non-empty object of byte fields")
                    continue
                for k, v in fields.items():
                    if (not isinstance(v, int) or isinstance(v, bool)
                            or v < 0):
                        bad(f"mem artifact {name}.{k} must be a "
                            f"non-negative int byte count, got {v!r}")
    elif base.startswith("ACCURACY_"):
        # platform + named run sections
        if not (isinstance(data.get("platform"), str)
                and data.get("platform")):
            bad("accuracy artifact needs a non-empty str 'platform'")
        if not any(isinstance(v, dict) for v in data.values()):
            bad("accuracy artifact has no named run section "
                "(at least one config's results object)")
    elif "metric" in data:
        if not (isinstance(data["metric"], str) and data["metric"]):
            bad("'metric' must be a non-empty string")
        if not isinstance(data.get("platform"), str):
            bad("metric-style artifact missing 'platform'")
        if str(data.get("metric", "")).startswith("serving_fleet"):
            # the r13 fleet artifact (BENCH_r13): kill-and-respawn
            # evidence is only evidence with the cold-start A/B sides,
            # the fleet p99, and the failover/zero-drop counters present
            for k in ("cold_start_live_ms", "cold_start_cache_ms",
                      "fleet_p99_ms"):
                v = data.get(k)
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    bad(f"fleet artifact missing numeric {k!r}")
            for k in ("fleet_failovers_total", "fleet_failed_non_shed"):
                v = data.get(k)
                if not isinstance(v, int) or isinstance(v, bool):
                    bad(f"fleet artifact missing int {k!r} (the "
                        "failover / zero-drop evidence)")
        if str(data.get("metric", "")).startswith(
                "serving_fleet_autoscale"):
            # the r14 self-operating-fleet generation: an autoscale
            # claim is only evidence with the replica-count TRAJECTORY
            # (did the count actually follow load, inside the bounds?),
            # the p99 under the ramp, and the zero-failed counter
            # SUMMED across rounds (a failing round must not hide
            # behind a best-of)
            traj = data.get("autoscale_replica_trajectory")
            if (not isinstance(traj, list) or not traj
                    or not all(isinstance(n, int)
                               and not isinstance(n, bool)
                               for n in traj)):
                bad("autoscale artifact missing "
                    "'autoscale_replica_trajectory' (non-empty list of "
                    "replica counts — the follow-the-load evidence)")
            v = data.get("autoscale_p99_ms")
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                bad("autoscale artifact missing numeric "
                    "'autoscale_p99_ms' (the bounded-latency evidence)")
            v = data.get("fleet_failed_non_shed")
            if not isinstance(v, int) or isinstance(v, bool):
                bad("autoscale artifact missing int "
                    "'fleet_failed_non_shed' summed across rounds")
        if str(data.get("metric", "")).startswith("serving_quant"):
            # the r19 quantized-serving generation (BENCH_r19): a
            # quantization claim is only evidence with all THREE
            # precision sides, the gate deltas FINITE (the in-bench
            # accuracy gate actually replayed), and the gate verdict
            for k in ("quant_fp32_p50_ms", "quant_bf16_p50_ms",
                      "quant_int8_p50_ms", "quant_gate_delta_bf16",
                      "quant_gate_delta_int8"):
                v = data.get(k)
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    bad(f"quant artifact missing numeric {k!r} (the "
                        "three-sided A/B + gate-delta evidence)")
            if not isinstance(data.get("quant_gate_passed"), bool):
                bad("quant artifact missing bool 'quant_gate_passed' "
                    "(the in-bench warmup gate verdict)")
        if str(data.get("metric", "")).startswith("serving_autotune"):
            # the r21 self-tuning generation (BENCH_r21): a tune score
            # is only evidence joined to the trace it replayed — the
            # listed WORKLOAD_*.json files must exist beside the
            # artifact and each mix must carry both A/B score sides,
            # its determinism drift inside the declared bound, and the
            # zero-drop counter summed over every replay
            wls = data.get("autotune_workloads")
            if (not isinstance(wls, list) or not wls
                    or not all(isinstance(w, str)
                               and w.startswith("WORKLOAD_")
                               for w in wls)):
                bad("autotune artifact missing 'autotune_workloads' "
                    "(non-empty list of WORKLOAD_*.json filenames — "
                    "the trace/score join)")
            else:
                art_dir = os.path.dirname(os.path.abspath(path))
                for w in wls:
                    if not os.path.exists(os.path.join(art_dir, w)):
                        bad(f"autotune artifact cites trace {w!r} which "
                            "does not exist beside it — an unjoined "
                            "tune score is unreplayable evidence")
            bound = data.get("autotune_drift_bound")
            if not isinstance(bound, (int, float)) or isinstance(
                    bound, bool):
                bad("autotune artifact missing numeric "
                    "'autotune_drift_bound' (the declared score "
                    "tolerance its determinism claim cites)")
            mixes_ = data.get("autotune_mixes")
            if (not isinstance(mixes_, list) or not mixes_
                    or not all(isinstance(m, str) for m in mixes_)):
                bad("autotune artifact missing 'autotune_mixes' "
                    "(non-empty list of mix names)")
            else:
                for m in mixes_:
                    for k in (f"autotune_{m}_default_score",
                              f"autotune_{m}_tuned_score",
                              f"autotune_{m}_replay_drift"):
                        v = data.get(k)
                        if not isinstance(v, (int, float)) or isinstance(
                                v, bool):
                            bad(f"autotune artifact missing numeric "
                                f"{k!r} (the per-mix A/B + determinism "
                                "evidence)")
                    drift = data.get(f"autotune_{m}_replay_drift")
                    if (isinstance(drift, (int, float))
                            and not isinstance(drift, bool)
                            and isinstance(bound, (int, float))
                            and not isinstance(bound, bool)
                            and drift > bound):
                        bad(f"autotune mix {m!r} replay drift {drift} "
                            f"exceeds its own declared bound {bound} — "
                            "the determinism claim fails its artifact")
            v = data.get("fleet_failed_non_shed")
            if not isinstance(v, int) or isinstance(v, bool):
                bad("autotune artifact missing int "
                    "'fleet_failed_non_shed' summed over every replay")
        if str(data.get("metric", "")).startswith("serve_train"):
            # the r20 online-learning generation (BENCH_r20): an
            # online-loop claim is only evidence with the held-out
            # error TRAJECTORY (one point per published version — did
            # the stream actually teach the model?), the zero-drop
            # counter summed over every round, and the publish /
            # rollback ledger
            traj = data.get("serve_train_error_trajectory")
            if (not isinstance(traj, list) or not traj
                    or not all(isinstance(x, (int, float))
                               and not isinstance(x, bool)
                               for x in traj)):
                bad("serve_train artifact missing "
                    "'serve_train_error_trajectory' (non-empty list "
                    "of held-out error numbers, one per published "
                    "version — the learning evidence)")
            v = data.get("fleet_failed_non_shed")
            if not isinstance(v, int) or isinstance(v, bool):
                bad("serve_train artifact missing int "
                    "'fleet_failed_non_shed' summed over every round "
                    "(the fleet-stayed-up-through-the-swaps evidence)")
            for k in ("publishes_total", "rollbacks_total"):
                v = data.get(k)
                if not isinstance(v, int) or isinstance(v, bool):
                    bad(f"serve_train artifact missing int {k!r} (the "
                        "publish/rollback ledger)")
        if str(data.get("metric", "")).startswith("overlap"):
            # the r18 FSDP-overlap generation (BENCH_r18): the overlap
            # claim is only evidence with BOTH step-time sides AND the
            # exposed-collective split — the structural number a 1-core
            # CPU certifies even when its step-time ratio is
            # dispatch-bound
            for k in ("overlap_on_steps_per_sec",
                      "overlap_off_steps_per_sec",
                      "exposed_comm_frac_overlap_on",
                      "exposed_comm_frac_overlap_off"):
                v = data.get(k)
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    bad(f"overlap artifact missing numeric {k!r} "
                        "(the A/B sides + exposed-comm evidence)")
            for k in ("exposed_collectives_overlap_on",
                      "exposed_collectives_overlap_off"):
                v = data.get(k)
                if not isinstance(v, int) or isinstance(v, bool):
                    bad(f"overlap artifact missing int {k!r} (the "
                        "exposed-collective count per side)")
        for key, val in data.items():
            if "_vs_" not in key:
                continue
            if val is None:
                continue  # a skipped side is recorded as null
            if not isinstance(val, (int, float)) or isinstance(
                    val, bool):
                bad(f"ratio key {key!r} must be a number or null, got "
                    f"{type(val).__name__}")
                continue
            # per-metric best-of structure: an A/B ratio needs both
            # sides present so the best-of evidence is re-checkable
            stem, _, b_side = key.partition("_vs_")
            sides = [k for k in data
                     if k != key and isinstance(
                         data[k], (int, float))
                     and (k.startswith(stem.rsplit("_", 1)[0])
                          or b_side.split("_")[0] in k)]
            if len(sides) < 2:
                bad(f"A/B ratio {key!r} lacks its two sides in the "
                    "artifact (per-metric best-of structure)")
    elif "parsed" in data or "round" in data:
        if not isinstance(data.get("cmd"), (str, list)):
            bad("watcher-style artifact missing 'cmd'")
        if "parsed" in data and not isinstance(data["parsed"],
                                               (dict, type(None))):
            bad("'parsed' must be an object")
    elif "n" in data and "cmd" in data:
        if "rc" in data and not isinstance(data["rc"], int):
            bad("'rc' must be an int")
    else:
        bad("unrecognized bench artifact shape: expected metric-style "
            "('metric'+'platform'), watcher-style ('parsed'/'round'), "
            "or harness-style ('n'+'cmd') keys")
    for npath, val in _walk_numbers(data):
        if math.isnan(val) or math.isinf(val):
            bad(f"non-finite number at {npath} (a crashed averaging "
                "step wrote NaN/Infinity)")
    return findings


def run_schema_check(root: str,
                     patterns: Sequence[str] = ("BENCH_*.json",
                                                "MULTICHIP_*.json",
                                                "ACCURACY_*.json",
                                                "MEM_*.json",
                                                "TRACE_*.json",
                                                "HEALTH_*.json",
                                                "WORKLOAD_*.json")
                     ) -> List[Finding]:
    findings: List[Finding] = []
    for pattern in patterns:
        for path in sorted(glob.glob(os.path.join(root, pattern))):
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            findings.extend(check_bench_file(path, rel))
    return findings
