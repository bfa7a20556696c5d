"""``benchmark/probes/slow_steps.py`` on hand-made spans: the part that
holds a slow step's excess is found, warm and recompiled steps are left
out."""

import pytest

from benchmark.probes import slow_steps


def _step(n, parts, **attrs):
    sid = f"s{n}"
    spans = [{"name": name, "parent_id": sid, "span_id": f"{sid}.{name}",
              "dur_ms": ms, "attrs": {"step": n}}
             for name, ms in parts.items()]
    spans.append({"name": "train.step", "parent_id": None, "span_id": sid,
                  "dur_ms": sum(v for k, v in parts.items()
                                if k.startswith("train.")) + 0.5,
                  "attrs": dict(attrs, step=n)})
    return spans


def test_the_part_that_holds_the_excess():
    usual = {"train.dispatch": 2.0, "train.device_wait": 47.0,
             "train.callback": 2.0, "prefetch.put_wait": 45.0}
    spans = _step(0, dict(usual, **{"train.device_wait": 5000.0}))
    spans += _step(1, usual)
    for n in range(2, 42):
        parts = dict(usual)
        if n in (10, 30):                       # two stalled callbacks
            parts["train.callback"] = 52.0
        if n == 20:                             # it compiled: left out
            parts["train.dispatch"] = 900.0
        spans += _step(n, parts, recompiled=(n == 20))
    steps = slow_steps.by_step(spans, skip=2)
    assert len(steps) == 39 and steps[0]["step"] == 2   # 0, 1, 20 left out
    out = slow_steps.excess(steps)
    assert out["median_ms"] == pytest.approx(51.5)
    assert out["slow_steps"] == 2
    assert out["slow_excess_ms"] == pytest.approx(50.0)
    assert out["part_excess_ms"]["train.callback"] == pytest.approx(50.0)
    assert out["part_excess_ms"]["train.device_wait"] == pytest.approx(0.0)
    assert out["holds_most_of_the_excess"] == {"train.callback": 2}
    assert out["coverage_median"] == pytest.approx(51.0 / 51.5)
    assert slow_steps.excess(steps[:5]) == {"steps": 5}
