"""What the idle chip was waiting for: the longest idle gaps of device 0
in a kept trace, each named by the program's own spans.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds 30 \\
        --trace 1 --keep-trace /tmp/cell.xplane.pb
    python3 -m benchmark.probes.gaps /tmp/cell.xplane.pb

The program writes ``train.*`` spans on the trainer's thread and
``prefetch.*`` spans on the prefetch thread, each with the ``step`` it
belongs to (``paddle_tpu/utils/profiler.py:StepBreakdown``), into the
host plane of the same trace as the device's operations, so a gap and a
span are intervals on one clock. For each of the ten longest gaps: the
``train.*`` span and the ``prefetch.*`` span that cover most of it with
their ``step``, and the share of the gap that every span name covers
(a gap between two steps' kernels begins in step n and ends in step n+1:
the later step's spans read ``train.dispatch+1``).
Then, over every gap of the trace longer than ``MIN_GAP_S``, the idle
milliseconds a step under each ``train.*`` span: they are the children of
one ``train.step`` on one thread, so they do not overlap and the rows
add up; ``train.step`` is what of a step no child covers. The
``prefetch.*`` rows run beside them and are no part of that sum. A trace
of a program without these spans names every gap ``no program span``.

The last line of standard output is the same as one JSON object.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, Iterable, List, Optional

from benchmark.trace_reduce import Interval, reduce_file, subtract, total, \
    union

PREFIXES = ("train.", "prefetch.")
WHOLE_STEP = "train.step"       # the parent: covers what no child does
NO_SPAN = "no program span"
MIN_GAP_S = 100e-6              # shorter gaps lie between two kernels


@dataclasses.dataclass
class Span:
    name: str
    step: Optional[int]
    start: float
    end: float
    line: int = 0       # which of the host's lines (threads) wrote it


def host_spans(path: str) -> List[Span]:
    """Every ``train.*`` and ``prefetch.*`` event of the host planes."""
    from jax.profiler import ProfileData
    spans, lines = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lines += 1
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    step = dict(ev.stats).get("step")
                    spans.append(Span(
                        ev.name, None if step is None else int(step),
                        ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9, lines))
    return spans


def idle_gaps(busy: Iterable[Interval]) -> List[Interval]:
    """The gaps between the device's operations, longest first."""
    busy = union(busy)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def covered(gap: Interval, spans: Iterable[Span]) -> float:
    """Seconds of the gap inside any of these spans."""
    inside = [(s.start, s.end) for s in spans
              if s.end > gap[0] and s.start < gap[1]]
    return (gap[1] - gap[0]) - total(subtract([gap], inside))


def most(gap: Interval, spans: List[Span]) -> Optional[Span]:
    """The one span that covers most of the gap."""
    best, most_s = None, 0.0
    for s in spans:
        c = covered(gap, [s])
        if c > most_s:
            best, most_s = s, c
    return best


def overlapping(gap: Interval, spans: List[Span]) -> List[Span]:
    return [s for s in spans if s.end > gap[0] and s.start < gap[1]]


def shares(gap: Interval, near: List[Span]) -> Dict[str, float]:
    """Share of the gap under each span name. A gap between two steps'
    kernels begins in step n and ends in step n+1: the trainer's spans
    of the later step read ``train.dispatch+1``. ``train.step`` keeps
    only what no other ``train.*`` span covers."""
    length = gap[1] - gap[0]
    first = min((s.step for s in near if s.name.startswith("train.")
                 and s.step is not None), default=0)
    by_name: Dict[str, List[Span]] = {}
    for s in near:
        name = s.name
        if name.startswith("train.") and s.step is not None \
                and s.step != first:
            name += f"+{s.step - first}"
        by_name.setdefault(name, []).append(s)
    out = {}
    for name, group in by_name.items():
        part = covered(gap, group)
        if name.startswith(WHOLE_STEP):
            part -= covered(gap, [c for c in children(near)
                                  if c.step == group[0].step])
        if part > 0:
            out[name] = part / length
    return out


def children(spans: List[Span]) -> List[Span]:
    return [s for s in spans
            if s.name.startswith("train.") and s.name != WHOLE_STEP]


def name_gap(gap: Interval, spans: List[Span]) -> dict:
    """A gap by the ``train.*`` bracket that covers most of it (by
    ``train.step`` where it fell between two brackets) and by the
    ``prefetch.*`` span that ran beside it."""
    near = overlapping(gap, spans)
    row = {"ms": 1e3 * (gap[1] - gap[0]), "train": NO_SPAN,
           "prefetch": NO_SPAN, "step": None, "prefetch_step": None,
           "shares": shares(gap, near)}
    best = most(gap, children(near)) or most(
        gap, [s for s in near if s.name == WHOLE_STEP])
    if best is not None:
        row["train"], row["step"] = best.name, best.step
    best = most(gap, [s for s in near if s.name.startswith("prefetch.")])
    if best is not None:
        row["prefetch"], row["prefetch_step"] = best.name, best.step
    return row


def report(busy: Iterable[Interval], spans: List[Span], n: int = 10) -> dict:
    gaps = idle_gaps(busy)
    longest = [name_gap(g, spans) for g in gaps[:n]]
    # whole steps: the iteration that finds the pass's end dispatches none
    steps = len({s.step for s in spans if s.name == "train.dispatch"})
    idle: Dict[str, float] = {}
    long_gaps = [g for g in gaps if g[1] - g[0] >= MIN_GAP_S]
    for g in long_gaps:
        part = shares(g, overlapping(g, spans))
        length = g[1] - g[0]
        for name, share in part.items():
            idle[name] = idle.get(name, 0.0) + share * length
        rest = 1.0 - sum(v for k, v in part.items()
                         if k.startswith("train."))
        if rest > 1e-9:
            idle[NO_SPAN] = idle.get(NO_SPAN, 0.0) + rest * length
    per = max(steps, 1)
    whole = {s.step: s.end - s.start for s in spans
             if s.name == WHOLE_STEP}
    in_children = sum(s.end - s.start for s in children(spans)
                      if s.step in whole)
    counts: Dict[str, Dict[str, int]] = {}
    for s in spans:
        c = counts.setdefault(s.name, {"spans": 0, "with_step": 0,
                                       "lines": set()})
        c["spans"] += 1
        c["with_step"] += s.step is not None
        c["lines"].add(s.line)
    return {
        "steps": steps,
        # the brackets' sum over the steps' own time, steps seen whole
        "coverage": in_children / sum(whole.values()) if whole else None,
        # every step of the window should show each name once, the
        # trainer's on one line and the prefetch thread's on another
        "spans": {k: dict(v, lines=sorted(v["lines"]))
                  for k, v in sorted(counts.items())},
        "idle_ms_a_step": 1e3 * sum(b - a for a, b in gaps) / per,
        "idle_ms_a_step_in_long_gaps":
            1e3 * sum(b - a for a, b in long_gaps) / per,
        "idle_ms_a_step_by_span":
            {k: 1e3 * v / per for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])},
        "longest": longest,
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit(__doc__)
    busy = reduce_file(argv[0], chips=1).devices[0].intervals()
    out = report(busy, host_spans(argv[0]))
    print(f"coverage {out['coverage']}")
    print(f"{out['steps']} steps; idle {out['idle_ms_a_step']:.3f} ms a "
          f"step, {out['idle_ms_a_step_in_long_gaps']:.3f} of it in gaps "
          f"of {1e6 * MIN_GAP_S:.0f} us or more, by span:")
    for name, ms in out["idle_ms_a_step_by_span"].items():
        print(f"  {name:<24} {ms:8.3f} ms a step")
    for name, c in out["spans"].items():
        print(f"  {name:<24} {c['spans']} spans, {c['with_step']} with a "
              f"step, on host line {c['lines']}")
    print("the longest gaps:")
    for row in out["longest"]:
        parts = " ".join(f"{k}={100 * v:.0f}%" for k, v in sorted(
            row["shares"].items(), key=lambda kv: -kv[1]))
        print(f"  {row['ms']:8.3f} ms  {row['train']} (step {row['step']})"
              f" | {row['prefetch']} (step {row['prefetch_step']})"
              f" | {parts}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
