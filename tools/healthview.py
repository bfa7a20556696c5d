#!/usr/bin/env python
"""Render / diff training-health timelines.

    python tools/healthview.py RUN.jsonl              # render one run
    python tools/healthview.py HEALTH_r16.json        # or an artifact
    python tools/healthview.py A.jsonl --diff B.jsonl # compare runs
    python tools/healthview.py RUN.jsonl --json       # normalized dump

Accepts both timeline shapes the health plane produces:

- the live per-run JSONL an ``obs/events.py:EventLog`` appends
  (``--health_log`` / ``HealthConfig.log_path``): one record per line,
  torn tail lines tolerated;
- the committed ``HEALTH_*.json`` artifact family (PT401): one object
  ``{"run", "period", "events": [...]}``.

Rendering shows one line per step — loss, lr, max|grad|, the
data_wait/compute split — with ``!! divergence`` markers on sentry
trips (policy + postmortem pointer). ``--diff`` aligns two runs by
step and reports the first step whose loss differs (and the worst
absolute delta), which is how a telemetry-on vs telemetry-off pair or
a resumed-vs-uninterrupted pair is audited by eye.

Importable: ``load(path)`` -> (meta, step-events); ``diff(a, b)`` ->
{first_diverging_step, max_abs_delta, compared}.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Dict, List, Optional, Tuple


def load(path: str) -> Tuple[dict, List[dict]]:
    """(meta, events) from a JSONL timeline or a HEALTH_* artifact.
    Events keep file order; non-step records (divergence markers) ride
    along tagged by their ``event`` field."""
    base = os.path.basename(path)
    if base.endswith(".jsonl"):
        from paddle_tpu.obs.events import load_timeline
        events = load_timeline(path)
        meta = {"run": base, "format": "jsonl"}
        return meta, events
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict) or not isinstance(
            data.get("events"), list):
        raise SystemExit(
            f"{path}: not a health timeline (expected a JSONL event "
            "log or a HEALTH_* artifact with an 'events' list)")
    meta = {k: v for k, v in data.items() if k != "events"}
    meta.setdefault("run", base)
    meta["format"] = "artifact"
    return meta, list(data["events"])


def steps_of(events: List[dict]) -> Dict[int, dict]:
    """step -> record for the per-step rows (event == "step" or
    untagged rows that carry a step+loss)."""
    out: Dict[int, dict] = {}
    for e in events:
        if not isinstance(e, dict):
            continue
        if e.get("event", "step") != "step":
            continue
        step = e.get("step")
        if isinstance(step, int):
            out[step] = e
    return out


def _fmt(v, width=10, prec=5) -> str:
    if v is None:
        return " " * (width - 1) + "-"
    if isinstance(v, str):  # non-finite floats serialize as strings
        return f"{v:>{width}}"
    if isinstance(v, float) and not math.isfinite(v):
        return f"{v!r:>{width}}"
    return f"{v:>{width}.{prec}g}"


def _stat(v) -> str:
    """One per-layer stat value — non-finite floats arrive as strings
    ("nan"/"inf", the EventLog strict-JSON spelling); print those raw
    (a divergence timeline is exactly where this tool must not
    crash)."""
    return v if isinstance(v, str) else f"{v:.5g}"


def _loss_of(rec) -> Optional[float]:
    """The record's loss as a float — EventLog spells non-finite
    losses as strings ("nan"/"inf") to keep lines strict JSON."""
    v = rec.get("loss")
    if isinstance(v, str):
        try:
            return float(v)
        except ValueError:
            return None
    if isinstance(v, (int, float)):
        return float(v)
    return None


def format_run(meta: dict, events: List[dict],
               layers: bool = False) -> str:
    lines = [f"run={meta.get('run')} period={meta.get('period', '?')} "
             f"events={len(events)}"]
    lines.append(f"{'step':>6} {'pass':>4} {'batch':>5} {'loss':>10} "
                 f"{'lr':>10} {'max|grad|':>10} {'wait_ms':>8} "
                 f"{'comp_ms':>8}")
    for e in events:
        kind = e.get("event", "step")
        if kind == "divergence":
            lines.append(
                f"!! divergence at step {e.get('step')}: "
                f"loss={e.get('loss')!r} "
                f"max|grad|={e.get('grad_absmax')!r} "
                f"worst={e.get('worst_layer')} "
                f"policy={e.get('policy')} "
                f"postmortem={e.get('postmortem')}")
            continue
        if kind != "step":
            continue
        mark = " *skipped" if e.get("skipped") else ""
        lines.append(
            f"{e.get('step', -1):>6} {e.get('pass', 0):>4} "
            f"{e.get('batch', 0):>5} {_fmt(e.get('loss'))} "
            f"{_fmt(e.get('lr'))} {_fmt(e.get('grad_absmax'))} "
            f"{_fmt(e.get('data_wait_ms'), 8, 3)} "
            f"{_fmt(e.get('compute_ms'), 8, 3)}{mark}")
        if layers and e.get("param_stats"):
            for n, d in sorted(e["param_stats"].items()):
                detail = " ".join(f"{k}={_stat(v)}"
                                  for k, v in sorted(d.items()))
                lines.append(f"       param {n}: {detail}")
        if layers and e.get("act_stats"):
            for n, d in sorted(e["act_stats"].items()):
                detail = " ".join(f"{k}={_stat(v)}"
                                  for k, v in sorted(d.items()))
                lines.append(f"       layer {n}: {detail}")
    return "\n".join(lines)


def diff(a_events: List[dict], b_events: List[dict]) -> dict:
    """Align two runs by step; report where their losses part ways.
    NaN != NaN would flag every post-divergence step, so two NaNs
    count as equal — the FIRST diverging step is the signal."""
    a, b = steps_of(a_events), steps_of(b_events)
    common = sorted(set(a) & set(b))
    first = None
    worst = 0.0
    for s in common:
        la, lb = _loss_of(a[s]), _loss_of(b[s])
        if la is None or lb is None:
            continue
        if math.isnan(la) and math.isnan(lb):
            continue
        delta = abs(la - lb)
        if delta > 0 and first is None:
            first = s
        if math.isfinite(delta):
            worst = max(worst, delta)
        elif first is None:
            first = s
    return {"compared": len(common),
            "only_a": len(set(a) - set(b)),
            "only_b": len(set(b) - set(a)),
            "first_diverging_step": first,
            "max_abs_delta": worst}


def format_diff(meta_a: dict, meta_b: dict, d: dict) -> str:
    lines = [f"A: {meta_a.get('run')}  B: {meta_b.get('run')}",
             f"steps compared: {d['compared']} "
             f"(only-A: {d['only_a']}, only-B: {d['only_b']})"]
    if d["first_diverging_step"] is None:
        lines.append("losses identical on every common step")
    else:
        lines.append(
            f"first diverging step: {d['first_diverging_step']} "
            f"(max |delta-loss| = {d['max_abs_delta']:.6g})")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python tools/healthview.py")
    ap.add_argument("timeline", help="JSONL event log or HEALTH_*.json")
    ap.add_argument("--diff", default=None, metavar="OTHER",
                    help="second timeline to align by step")
    ap.add_argument("--layers", action="store_true",
                    help="expand per-layer stats on period steps")
    ap.add_argument("--json", action="store_true",
                    help="emit the normalized events (or diff) as JSON")
    args = ap.parse_args(argv)
    meta, events = load(args.timeline)
    if args.diff:
        meta_b, events_b = load(args.diff)
        d = diff(events, events_b)
        print(json.dumps(d, indent=1) if args.json
              else format_diff(meta, meta_b, d))
        return 0 if d["first_diverging_step"] is None else 1
    if args.json:
        print(json.dumps({"meta": meta, "events": events}, indent=1))
    else:
        print(format_run(meta, events, layers=args.layers))
    return 0 if events else 1


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
