"""One run of a cell with the program's ``Tracer`` armed, and which span
holds the excess of its slow steps.

    python3 -m benchmark.probes.slow_steps --workload <cell> --seed <n> \\
        --seconds 30 --trace 0

runs the cell as ``benchmark.run`` does (same arguments, same result
line last on standard output) with ``paddle_tpu.obs.trace`` recording
every step's spans in memory: that is the cost of tracing when it is on,
read off the line's ``samples_per_s``. With ``$PADDLE_TPU_TRACE_DIR`` set
the spans are also dumped there, as the trainer's CLI does at exit.

Each step is one trace: a ``train.step`` span and its children, per-step
values where the counters give means. After the run, on standard error
as one line ``[slow_steps] {...}``: the median step, the 95th percentile,
and for the steps beyond it the mean excess of every child span over
that span's own median: the part that holds the excess is the one to
open; and the share of a step that the trainer's brackets cover. Warm
steps and steps marked ``recompiled`` are left out.
"""

from __future__ import annotations

import json
import statistics
import sys
from typing import Dict, List

BUFFER = 1 << 15        # spans: a 30 s window of the LSTM cell holds 6,000


def by_step(spans: List[dict], skip: int) -> List[dict]:
    """``[{"step", "ms", "parts": {name: ms}}]`` of every whole step
    after the first ``skip``, in order."""
    kids: Dict[str, Dict[str, float]] = {}
    for s in spans:
        if s["parent_id"] is not None:
            parts = kids.setdefault(s["parent_id"], {})
            parts[s["name"]] = parts.get(s["name"], 0.0) + s["dur_ms"]
    steps = [s for s in spans if s["name"] == "train.step"
             and not s.get("attrs", {}).get("recompiled")]
    return [{"step": s["attrs"]["step"], "ms": s["dur_ms"],
             "parts": kids.get(s["span_id"], {})} for s in steps[skip:]]


def excess(steps: List[dict]) -> dict:
    """For the steps beyond the 95th percentile: how much longer than the
    median step they are, and the mean excess of each part over its own
    median."""
    if len(steps) < 20:
        return {"steps": len(steps)}
    times = [s["ms"] for s in steps]
    median = statistics.median(times)
    p95 = statistics.quantiles(times, n=20, method="inclusive")[18]
    slow = [s for s in steps if s["ms"] > p95]
    names = sorted({n for s in steps for n in s["parts"]})
    medians = {n: statistics.median(s["parts"].get(n, 0.0) for s in steps)
               for n in names}
    # the trainer's own brackets over the step they are parts of
    coverage = [sum(v for n, v in s["parts"].items()
                    if n.startswith("train.")) / s["ms"] for s in steps]
    out = {"steps": len(steps), "median_ms": median, "p95_ms": p95,
           "slow_steps": len(slow), "part_median_ms": medians,
           "coverage_median": statistics.median(coverage),
           "coverage_least": min(coverage)}
    if slow:
        out["slow_excess_ms"] = statistics.mean(
            s["ms"] for s in slow) - median
        out["part_excess_ms"] = {
            n: statistics.mean(s["parts"].get(n, 0.0) for s in slow)
            - medians[n] for n in names}
        holds: Dict[str, int] = {}
        for s in slow:
            # of the trainer's own parts: a prefetch span of batch n ran
            # beside earlier steps
            worst = max((n for n in names if n.startswith("train.")),
                        key=lambda n: s["parts"].get(n, 0.0) - medians[n])
            holds[worst] = holds.get(worst, 0) + 1
        out["holds_most_of_the_excess"] = holds
    return out


def main(argv=None) -> int:
    from benchmark import run
    from paddle_tpu.obs import trace
    tracer = trace.install(trace.Tracer("benchmark", buffer=BUFFER))
    rc = run.main(argv)
    tracer.dump_jsonl()     # to $PADDLE_TPU_TRACE_DIR, where it is set
    report = excess(by_step(tracer.spans(), skip=run.WARM_STEPS))
    report["dropped"] = tracer.dropped
    print("[slow_steps]", json.dumps(report), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
