"""Trace-replay harness: recorder tap, artifact round-trip, and the
determinism contract over the COMMITTED ``WORKLOAD_r21_*.json`` traces.

The determinism claim is structural, per ``serving/workload.py``: the
same trace against the same (generously provisioned) engine yields the
same outcome COUNTS exactly, every event accounted for once, and a
score within ``SCORE_DRIFT_BOUND`` (absolute latencies drift +-50% on
this shared host; counts do not). The engines the committed traces
were recorded on are rebuilt here via the ``serving/mixes.py`` builders
— if the model or knob defaults drift from what the traces were
recorded on, these tests fail.
"""

import os

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

from paddle_tpu.serving import (Overloaded, Workload,  # noqa: E402
                                WorkloadRecorder, replay, replay_score)
from paddle_tpu.serving import mixes  # noqa: E402
from paddle_tpu.serving.tuner import SLOTarget  # noqa: E402
from paddle_tpu.serving.workload import (EVENT_KEYS,  # noqa: E402
                                         SCORE_DRIFT_BOUND,
                                         engine_dispatch)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def classifier_eng():
    """One warmed classifier engine for the whole module (1-core host:
    never per-test warmup). Generously provisioned; tests that need
    pressure shrink knobs via apply_config and restore after."""
    eng = mixes.build_classifier_engine(batch_timeout_ms=1.0,
                                        queue_depth=64)
    yield eng
    eng.shutdown()


def test_recorder_taps_admitted_and_shed(classifier_eng):
    """The admission tap records the OFFERED stream — admitted and shed
    alike — with every event replayable by construction."""
    eng = classifier_eng
    rec = WorkloadRecorder()
    eng.workload_recorder = rec
    try:
        # narrow the queue so a synchronous burst sheds structurally
        eng.apply_config({"queue_depth": 2, "batch_timeout_ms": 20.0})
        sample = ([0.1] * mixes.CLASSIFIER_DIM, 1)
        reqs, shed = [], 0
        for _ in range(8):
            try:
                reqs.append(eng.submit(sample, deadline_ms=5000.0))
            except Overloaded:
                shed += 1
        eng.apply_config({"queue_depth": 64, "shed_watermark": 64,
                          "batch_timeout_ms": 1.0})
        for r in reqs:
            r.event.wait(30.0)
    finally:
        eng.workload_recorder = None
        eng.apply_config({"queue_depth": 64, "shed_watermark": 64,
                          "batch_timeout_ms": 1.0})
    assert shed > 0, "burst never shed: the tap's shed path is untested"
    assert len(rec) == 8  # every offer taped, shed included
    w = rec.snapshot("tap")
    outcomes = [e["outcome"] for e in w.events]
    assert outcomes.count("admitted") == len(reqs)
    assert outcomes.count("overloaded") == shed
    ts = [e["t"] for e in w.events]
    assert ts == sorted(ts) and ts[0] == 0.0
    for e in w.events:
        assert set(EVENT_KEYS) <= set(e)
        assert e["deadline_ms"] == 5000.0  # effective deadline taped


def test_workload_artifact_roundtrip(tmp_path):
    w = mixes.short_burst_workload()
    path = str(tmp_path / "WORKLOAD_rt.json")
    w.save(path)
    back = Workload.load(path)
    assert back.name == w.name
    assert len(back.events) == len(w.events)
    for a, b in zip(back.events, w.events):
        assert a["t"] == b["t"] and a["kind"] == b["kind"]
        assert a["outcome"] == b["outcome"]
        assert list(a["sample"][0]) == list(b["sample"][0])
    # a truncated artifact fails loudly, not as a short replay
    import json
    d = back.to_dict()
    d["n_events"] -= 1
    bad = tmp_path / "WORKLOAD_bad.json"
    bad.write_text(json.dumps(d))
    with pytest.raises(ValueError, match="n_events"):
        Workload.load(str(bad))
    d["version"] = 99
    bad.write_text(json.dumps(d))
    with pytest.raises(ValueError, match="version"):
        Workload.load(str(bad))


def test_replay_accounts_every_event_under_shed(classifier_eng):
    """ok + shed + deadline_miss + failed_non_shed == offered, and a
    shed-inducing config yields shed outcomes — not failures."""
    eng = classifier_eng
    eng.apply_config({"queue_depth": 3, "batch_timeout_ms": 30.0})
    try:
        s = replay(mixes.short_burst_workload(), engine_dispatch(eng))
    finally:
        eng.apply_config({"queue_depth": 64, "shed_watermark": 64,
                          "batch_timeout_ms": 1.0})
    assert (s["ok"] + s["shed"] + s["deadline_miss"]
            + s["failed_non_shed"]) == s["offered"] == 48
    assert s["shed"] > 0, "12-wide bursts into depth 3 must shed"
    assert s["failed_non_shed"] == 0, s["errors"]


def _assert_deterministic(eng, trace_path, slo):
    assert os.path.exists(trace_path), (
        f"missing committed trace {trace_path} (nothing regenerates "
        "it: ROADMAP D1b)")
    w = Workload.load(trace_path)
    disp = engine_dispatch(eng)
    a = replay_score(w, disp, slo, rounds=1)
    b = replay_score(w, disp, slo, rounds=1)
    for k in ("offered", "ok", "shed", "deadline_miss",
              "failed_non_shed"):
        assert a[k] == b[k], (k, a[k], b[k], a["errors"], b["errors"])
    assert a["failed_non_shed"] == 0, a["errors"]
    assert a["ok"] == a["offered"]  # generous knobs: nothing sheds
    assert abs(a["score"] - b["score"]) <= SCORE_DRIFT_BOUND
    assert 0.0 <= a["score"] <= 1.0


def test_committed_short_burst_trace_replays_deterministically(
        classifier_eng):
    _assert_deterministic(
        classifier_eng, mixes.committed_trace_path("short_burst", REPO),
        SLOTarget(p99_ms=100.0, max_shed_rate=0.0))


def test_committed_convoy_trace_replays_deterministically():
    eng = mixes.build_convoy_engine(batch_timeout_ms=1.0,
                                    queue_depth=64)
    try:
        _assert_deterministic(
            eng, mixes.committed_trace_path("convoy", REPO),
            SLOTarget(p99_ms=400.0, max_shed_rate=0.0))
    finally:
        eng.shutdown()
