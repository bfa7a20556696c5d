"""The train path's one span site (``utils/profiler.py:StepBreakdown``,
fed by ``SGD.train`` and the prefetch thread): every part of a step is a
counter, a span in a profiler session's trace and, with a ``Tracer``
armed, a span of that step's trace in ``obs.trace``'s buffer."""

import glob
import json
import sys
import threading
import time

import numpy as np
import pytest

import jax

from paddle_tpu.config import dsl
from paddle_tpu.data import DataFeeder, dense_vector, integer_value
from paddle_tpu.obs import trace
from paddle_tpu.optim import Momentum
from paddle_tpu.trainer import SGD, events
from paddle_tpu.utils.profiler import SITES, StepBreakdown

TRAIN = ["train.data_wait", "train.dispatch", "train.device_wait",
         "train.callback"]
PREFETCH = ["prefetch.read", "prefetch.decode", "prefetch.h2d",
            "prefetch.put_wait"]
STEPS = 5


def _trainer():
    dsl.reset()
    x = dsl.data("x", size=16)
    y = dsl.data("y", size=3)
    h = dsl.fc(input=dsl.fc(input=x, size=32, act="relu"), size=3,
               act="softmax")
    cost = dsl.classification_cost(input=h, label=y)
    return SGD(cost=cost, update_equation=Momentum(learning_rate=0.1),
               seed=0)


def _batches(sizes=(8,) * STEPS):
    rng = np.random.RandomState(3)
    return [[(rng.randn(16).astype(np.float32), int(rng.randint(3)))
             for _ in range(b)] for b in sizes]


FEEDER = DataFeeder({"x": dense_vector(16), "y": integer_value(3)})


def _train(t, batches, **kw):
    t.train(lambda: iter(batches), feeder=FEEDER, num_passes=1, **kw)
    return t.breakdown


def _ours(spans):
    """The train path's spans. A thread that another test file left
    behind in this worker (a master client's heartbeat) records into
    whatever Tracer is installed; which files share a worker changes
    with every file the suite gains."""
    return [s for s in spans
            if s["name"].startswith(("train", "prefetch."))]


@pytest.fixture
def tracer():
    t = trace.install(trace.Tracer("test"))
    try:
        yield t
    finally:
        trace.install(None)


def _profiled(tmp_path, async_load_data):
    """A short ``SGD.train`` under a profiler session: the host spans of
    its trace as ``[(name, line, start_ns, end_ns, stats)]``."""
    from jax.profiler import ProfileData
    t = _trainer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _train(t, _batches(), async_load_data=async_load_data)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert len(files) == 1
    spans = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name == "train" or \
                        ev.name.startswith(("train.", "prefetch.")):
                    spans.append((ev.name, i, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return spans


def _by_step(spans, name):
    out = {}
    for s in spans:
        if s[0] == name:
            assert s[4]["step"] not in out, f"two {name} for one step"
            out[s[4]["step"]] = s
    return out


@pytest.mark.parametrize("async_load_data", [True, False])
def test_profiler_session_holds_every_step_with_its_children(
        tmp_path, async_load_data):
    spans = _profiled(tmp_path, async_load_data)
    steps = _by_step(spans, "train.step")
    # one more than the batches: the iteration that found the pass's end
    assert sorted(steps) == list(range(STEPS + 1))
    whole = {s[4]["step_num"]: s for s in spans if s[0] == "train"}
    assert sorted(whole) == sorted(steps)
    names = TRAIN + ([] if async_load_data else ["train.h2d"])
    for n in range(STEPS):
        _, line, start, end, _ = steps[n]
        assert whole[n][2] <= start and end <= whole[n][3]
        for name in names:
            child = _by_step(spans, name)[n]
            assert child[1] == line                 # the trainer's thread
            assert start <= child[2] and child[3] <= end
    assert ("train.h2d" in {s[0] for s in spans}) == (not async_load_data)
    last = [s for s in spans if s[4].get("step") == STEPS
            and s[0].startswith("train.")]
    assert sorted(s[0] for s in last) == ["train.data_wait", "train.step"]


def test_prefetch_spans_carry_the_step_of_the_batch(tmp_path):
    spans = _profiled(tmp_path, True)
    steps = _by_step(spans, "train.step")
    lines = set()
    for name in PREFETCH:
        found = _by_step(spans, name)
        # read runs once more, to find the reader's end
        assert sorted(found)[:STEPS] == list(range(STEPS))
        for n in range(STEPS):
            lines.add(found[n][1])
            # the worker had batch n ready before step n could take it
            if name != "prefetch.put_wait":
                assert found[n][3] <= \
                    _by_step(spans, "train.dispatch")[n][2]
    assert len(lines) == 1 and lines != {steps[0][1]}   # its own thread


@pytest.mark.parametrize("async_load_data", [True, False])
def test_counters_nest_and_cover_the_step(async_load_data):
    bd = _train(_trainer(), _batches(), async_load_data=async_load_data)
    t = bd.totals
    assert bd.steps == STEPS
    assert 0 < t["device_wait"] <= t["compute"] \
        <= t["dispatch"] + t["device_wait"]
    covered = sum(t[p] for p in ("data_wait", "h2d", "dispatch",
                                 "device_wait", "callback"))
    # the last data_wait (it found the end of the pass) is no step's
    covered -= bd.last["data_wait"]
    assert 0.9 * bd.wall <= covered <= bd.wall
    for part in ("prefetch_read", "prefetch_decode", "prefetch_h2d",
                 "prefetch_put_wait"):
        assert (t[part] > 0) == async_load_data
    assert (t["h2d"] > 0) == (not async_load_data)


@pytest.mark.parametrize("async_load_data", [True, False])
def test_h2d_brackets_the_call_that_places_the_feeders_host_arrays(
        tracer, monkeypatch, async_load_data):
    """The feeder hands over numpy; ``train.h2d`` (synchronous) or
    ``prefetch.h2d`` (the worker) holds the ``device_put`` of exactly
    those arrays; the step is called with device arrays."""
    from paddle_tpu.utils import profiler
    t = _trainer()
    fed, placed, stepped = [], [], []
    leaves = jax.tree_util.tree_leaves

    def feeder(rows):
        feed = FEEDER(rows)
        fed.append({type(leaf) for leaf in leaves(feed)})
        return feed

    put = jax.device_put

    def spying_put(tree, *a, **kw):
        t0 = time.perf_counter()
        out = put(tree, *a, **kw)
        if isinstance(tree, dict):      # a feed, not the step's scalars
            placed.append(({type(leaf) for leaf in leaves(tree)},
                           threading.current_thread().name,
                           profiler._EPOCH + t0,
                           profiler._EPOCH + time.perf_counter()))
        return out

    step = t._train_step

    def spying_step(params, opt_state, feed, *rest):
        stepped.append({isinstance(leaf, jax.Array)
                        for leaf in leaves(feed)})
        return step(params, opt_state, feed, *rest)

    monkeypatch.setattr(jax, "device_put", spying_put)
    t._train_step = spying_step
    t.train(lambda: iter(_batches()), feeder=feeder, num_passes=1,
            async_load_data=async_load_data)
    assert fed == [{np.ndarray}] * STEPS
    assert stepped == [{True}] * STEPS
    assert [p[0] for p in placed] == [{np.ndarray}] * STEPS
    name = "prefetch.h2d" if async_load_data else "train.h2d"
    spans = {s["attrs"]["step"]: s for s in _ours(tracer.spans())
             if s["name"] == name}
    assert sorted(spans) == list(range(STEPS))
    for n, (_types, thread, t0, t1) in enumerate(placed):
        assert (thread == "prefetch-worker") == async_load_data
        span = spans[n]
        assert span["ts"] <= t0 + 1e-6
        assert t1 <= span["ts"] + span["dur_ms"] / 1e3 + 1e-6


def test_new_keys_are_zero_from_reset_and_outside_total():
    bd = StepBreakdown()
    assert set(bd.totals) == set(SITES)
    assert all(v == 0.0 for v in bd.totals.values())
    for part in bd.totals:
        bd.add(part, 1.0)
    assert bd.total == 4.0          # data_wait, h2d, compute, callback
    bd.reset()
    assert all(v == 0.0 for v in bd.totals.values())
    s = bd.summary()
    assert "dispatch_ms_per_step" in s and "prefetch_put_wait_frac" in s


def test_stats_keep_the_names_the_log_period_dump_prints():
    from paddle_tpu.utils.stat import StatRegistry
    reg = StatRegistry("t")
    bd = StepBreakdown(reg)
    with bd.measure("h2d"), bd.measure("data_wait"):
        pass
    bd.add("compute", 0.5)
    with bd.measure("prefetch_decode", 0):
        pass
    counted = {n for n, s in reg.stats().items() if s.count}
    assert counted == {"prepareBatchData", "step/data_wait", "trainBatch",
                       "prefetch/decode"}


def _check_pt401(tracer, tmp_path):
    path = tracer.dump_jsonl(str(tmp_path / "trace.jsonl"))
    with open(path, encoding="utf-8") as f:
        dumped = [json.loads(line) for line in f]
    artifact = tmp_path / "TRACE_train.json"
    artifact.write_text(json.dumps({"spans": dumped}))
    from paddle_tpu.analysis.bench_schema import check_bench_file
    findings = check_bench_file(str(artifact), "TRACE_train.json")
    assert findings == [], [f.message for f in findings]
    return _ours(dumped)


@pytest.mark.parametrize("async_load_data", [True, False])
def test_armed_tracer_gets_one_trace_a_step(tracer, tmp_path,
                                            async_load_data):
    _train(_trainer(), _batches(), async_load_data=async_load_data)
    spans = _check_pt401(tracer, tmp_path)
    steps = [s for s in spans if s["name"] == "train.step"]
    assert [s["attrs"]["step"] for s in steps] == list(range(STEPS))
    assert len({s["trace_id"] for s in steps}) == STEPS
    names = TRAIN + (PREFETCH if async_load_data else ["train.h2d"])
    for step in steps:
        assert step["parent_id"] is None
        kids = [s for s in spans if s["parent_id"] == step["span_id"]]
        assert sorted(k["name"] for k in kids) == sorted(names)
        for k in kids:
            assert k["trace_id"] == step["trace_id"]
            assert k["attrs"] == {"step": step["attrs"]["step"]}
            if k["name"].startswith("train."):
                assert step["ts"] <= k["ts"] + 1e-6
                assert k["dur_ms"] <= step["dur_ms"] + 1e-3
    # nothing but whole steps: the end-of-pass iteration left no span
    assert len(spans) == STEPS * (1 + len(names))


def test_unarmed_step_makes_no_id_and_reaches_no_tracer(monkeypatch):
    def never(*a, **kw):
        raise AssertionError("the Tracer's sink ran with no Tracer armed")

    assert trace._TRACER is None
    for name in ("new_trace_id", "new_span_id", "child"):
        monkeypatch.setattr(trace, name, never)
    for name in ("record", "record_span"):
        monkeypatch.setattr(trace.Tracer, name, never)
    monkeypatch.setattr(StepBreakdown, "_span", never)
    monkeypatch.setattr(StepBreakdown, "_flush", never)
    bd = _train(_trainer(), _batches(), async_load_data=True)
    assert bd.steps == STEPS and bd._pending == {}


def test_a_shape_change_marks_exactly_that_step_recompiled(tracer):
    sizes = (8, 8, 8, 4, 4, 8)
    _train(_trainer(), _batches(sizes), async_load_data=True)
    steps = [s for s in _ours(tracer.spans()) if s["name"] == "train.step"]
    assert [s["attrs"].get("recompiled", False) for s in steps] == \
        [False, False, False, True, False, False]


def test_recompiled_is_on_the_profiler_span_too(tmp_path):
    from jax.profiler import ProfileData
    t = _trainer()
    _train(t, _batches((8, 8)))             # compiled before the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        _train(t, _batches((8, 4, 4)), async_load_data=True)
    finally:
        jax.profiler.stop_trace()
    file, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    marked = {}
    for plane in ProfileData.from_file(file).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "train.step":
                    stats = dict(ev.stats)
                    marked[stats["step"]] = bool(stats.get("recompiled"))
    assert marked == {0: False, 1: True, 2: False, 3: False}


def test_a_step_that_raised_leaves_no_span(tracer, tmp_path):
    class Stop(Exception):
        pass

    def handler(e):
        if isinstance(e, events.EndIteration) and e.batch_id == 2:
            raise Stop

    t = _trainer()
    with pytest.raises(Stop):
        _train(t, _batches(), async_load_data=True, event_handler=handler)
    spans = _check_pt401(tracer, tmp_path)      # no dangling parent
    assert sorted({s["attrs"]["step"] for s in spans}) == [0, 1]
    assert t.breakdown._step is None            # the open step was closed
    # the next run starts clean: what the worker had in flight is gone
    tracer.clear()
    _train(t, _batches((8, 8)), async_load_data=True)
    assert [s["attrs"]["step"] for s in _ours(tracer.spans())
            if s["name"] == "train.step"] == [0, 1]


def test_a_span_that_ends_after_its_step_goes_under_it(tracer):
    bd = StepBreakdown()
    bd.step_begin(0)
    site = bd.measure("prefetch_put_wait", 0)   # still open at step's end
    site.__enter__()
    with bd.measure("dispatch"):
        pass
    bd.step_done()
    site.__exit__(None, None, None)
    with bd.measure("prefetch_read", 1):        # the next step's: waits
        pass
    by_name = {s["name"]: s for s in _ours(tracer.spans())}
    assert set(by_name) == {"train.step", "train.dispatch",
                            "prefetch.put_wait"}
    step = by_name["train.step"]
    assert by_name["prefetch.put_wait"]["parent_id"] == step["span_id"]
    assert by_name["prefetch.put_wait"]["trace_id"] == step["trace_id"]
    assert list(bd._pending) == [1]


def test_spans_nobody_finishes_are_bounded(tracer):
    bd = StepBreakdown()
    for n in range(3 * bd.PENDING_STEPS):
        with bd.measure("prefetch_read", n):
            pass
    assert len(bd._pending) == bd.PENDING_STEPS
    assert _ours(tracer.spans()) == []


def test_worker_and_trainer_share_the_sink_without_losing_a_span():
    """More threads than this needs and a short switch interval: every
    batch's four worker spans reach its step, whichever thread the
    interpreter lets run."""
    from paddle_tpu.data import PrefetchPipeline
    tracer = trace.install(trace.Tracer("test", buffer=100000))
    n_batches = 400
    bd = StepBreakdown()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    noise_on = threading.Event()

    def noise():
        while not noise_on.is_set():
            sum(range(50))

    noisy = [threading.Thread(target=noise, daemon=True) for _ in range(4)]
    for th in noisy:
        th.start()
    try:
        pipe = PrefetchPipeline(lambda: iter(range(n_batches)),
                                feeder=lambda b: b, place=True, depth=2,
                                breakdown=bd)
        deadline = time.monotonic() + 120
        n = -1
        while time.monotonic() < deadline:
            n += 1
            bd.step_begin(n)
            try:
                with bd.measure("data_wait"):
                    pipe.get()
            except StopIteration:
                bd.step_abandon()
                break
            bd.step_done()
        pipe.close()
    finally:
        trace.install(None)
        noise_on.set()
        sys.setswitchinterval(interval)
        for th in noisy:
            th.join(timeout=10)
    assert not any(th.is_alive() for th in noisy)
    assert n == n_batches
    spans = _ours(tracer.spans())
    steps = {s["span_id"]: s for s in spans if s["name"] == "train.step"}
    assert len(steps) == n_batches
    for name in PREFETCH + ["train.data_wait"]:
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) == n_batches, name
        for s in mine:
            assert steps[s["parent_id"]]["attrs"]["step"] == \
                s["attrs"]["step"]
