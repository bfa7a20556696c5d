"""Async input pipeline (`data/prefetch.py`): ordering, bounded depth /
backpressure, worker-exception propagation, clean shutdown; bucketing
exactness (padded rows contribute ZERO loss and grad via the row mask);
and the recompile-guard — a ragged corpus compiles at most bucket-count
step variants, counted by the jit-cache probe."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.config import dsl
from paddle_tpu.data import (DataFeeder, LengthBuckets, PrefetchPipeline,
                             ROW_MASK_KEY, dense_vector, integer_value,
                             integer_value_sequence, prefetch_reader)
from paddle_tpu.data.prefetch import RecompileGuard, jit_cache_size
from paddle_tpu.optim import Momentum
from paddle_tpu.trainer import SGD
from paddle_tpu.utils.stat import StatRegistry


# ------------------------------------------------------------- pipeline
def test_prefetch_preserves_order():
    pipe = PrefetchPipeline(lambda: iter(range(20)), place=False)
    assert list(pipe) == list(range(20))


def test_prefetch_bounded_depth_backpressure():
    produced = []

    def reader():
        for i in range(100):
            produced.append(i)
            yield i

    pipe = PrefetchPipeline(reader, depth=2, place=False)
    deadline = time.time() + 5.0
    # the worker runs ahead only up to the queue bound (+1 in-prepare)
    while len(produced) < 3 and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.3)  # would overrun here if the queue were unbounded
    assert len(produced) <= 2 + 1, produced
    assert pipe.get() == 0  # consuming frees a slot
    deadline = time.time() + 5.0
    while len(produced) < 4 and time.time() < deadline:
        time.sleep(0.01)
    assert 4 <= len(produced) <= 4 + 1
    pipe.close()


def test_prefetch_propagates_worker_exception_after_good_items():
    def reader():
        yield 1
        yield 2
        raise ValueError("decode exploded")

    pipe = PrefetchPipeline(reader, place=False)
    assert pipe.get() == 1
    assert pipe.get() == 2
    with pytest.raises(ValueError, match="decode exploded"):
        pipe.get()
    # after the failure the stream is closed, not wedged
    with pytest.raises(StopIteration):
        pipe.get()


def test_prefetch_feeder_exception_propagates():
    def bad_feeder(b):
        raise KeyError("bad batch")

    pipe = PrefetchPipeline(lambda: iter([[1]]), feeder=bad_feeder,
                            place=False)
    with pytest.raises(KeyError):
        pipe.get()


def test_prefetch_close_is_clean_and_idempotent():
    release = threading.Event()

    def reader():
        for i in range(1000):
            yield i
            release.wait(0.001)

    pipe = PrefetchPipeline(reader, depth=2, place=False)
    assert pipe.get() == 0
    pipe.close()
    pipe.close()  # idempotent
    assert not pipe._thread.is_alive()
    with pytest.raises(StopIteration):
        pipe.get()


def test_prefetch_records_wait_and_decode_stats():
    reg = StatRegistry("t")
    pipe = PrefetchPipeline(lambda: iter([[1], [2]]),
                            feeder=lambda b: b, place=False, registry=reg)
    assert list(pipe) == [[1], [2]]
    assert reg.get("prefetch/decode").count == 2
    assert reg.get("prefetch/wait").count >= 2
    assert pipe.data_wait >= 0.0


def test_prefetch_times_its_four_parts_into_a_handed_breakdown():
    from paddle_tpu.utils.profiler import StepBreakdown
    reg = StatRegistry("t")
    bd = StepBreakdown(reg)
    def reader():
        for i in range(4):
            time.sleep(0.002)
            yield [i]

    def feeder(b):
        time.sleep(0.002)
        return b

    pipe = PrefetchPipeline(reader, feeder=feeder, place=False, depth=1,
                            breakdown=bd)
    time.sleep(0.1)     # the worker fills the queue and blocks on it
    assert list(pipe) == [[0], [1], [2], [3]]
    assert bd.totals["prefetch_read"] >= 4 * 0.002
    assert bd.totals["prefetch_decode"] >= 4 * 0.002
    assert bd.totals["prefetch_put_wait"] > 0.05    # it waited for us
    assert bd.totals["prefetch_h2d"] == 0.0         # place=False
    # the worker adds to its own keys alone
    assert all(bd.totals[p] == 0.0 for p in
               ("data_wait", "h2d", "compute", "callback", "dispatch",
                "device_wait"))
    assert reg.get("prefetch/read").count == 5      # the last finds the end
    assert reg.get("prefetch/decode").count == 4
    assert reg.get("prefetch/put_wait").count == 4
    assert reg.get("prefetch/h2d").count == 0


def test_prefetch_built_elsewhere_keeps_a_private_breakdown():
    reg = StatRegistry("t")
    a = PrefetchPipeline(lambda: iter([[1]]), feeder=lambda b: b,
                         place=False, registry=reg)
    b = PrefetchPipeline(lambda: iter([[2]]), feeder=lambda b: b,
                         place=False, registry=reg)
    assert list(a) == [[1]] and list(b) == [[2]]
    assert a._bd is not b._bd and a._bd.registry is reg
    assert a._bd.totals["prefetch_decode"] > 0
    assert reg.get("prefetch/decode").count == 2


def test_prefetch_spans_carry_the_batchs_sequence_number():
    from paddle_tpu.obs import trace
    from paddle_tpu.utils.profiler import StepBreakdown
    bd = StepBreakdown(StatRegistry("t"))
    trace.install(trace.Tracer("test"))
    try:
        pipe = PrefetchPipeline(lambda: iter("abc"), feeder=lambda b: b,
                                place=False, breakdown=bd)
        assert list(pipe) == ["a", "b", "c"]
        pipe._thread.join(timeout=5.0)
        assert not pipe._thread.is_alive()
    finally:
        trace.install(None)
    # no step finished: the spans wait, keyed by the batch's number
    waiting = {n: sorted(name for name, _t0, _s in spans)
               for n, spans in bd._pending.items()}
    batch = ["prefetch.decode", "prefetch.put_wait", "prefetch.read"]
    assert waiting == {0: batch, 1: batch, 2: batch, 3: ["prefetch.read"]}


# ------------------------------------- the worker as built: one stage,
# read -> decode (host arrays) -> h2d (the one placement) -> put
def _pull(pipe, timeout=10.0):
    """``pipe.get()`` that cannot hang the suite."""
    box = []

    def run():
        try:
            box.append((True, pipe.get()))
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box.append((False, e))

    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(timeout)
    assert box, f"pipe.get() still blocked after {timeout} s"
    ok, value = box[0]
    if not ok:
        raise value
    return value


def _workers():
    return [t for t in threading.enumerate()
            if t.name == "prefetch-worker" and t.is_alive()]


def _host_feed(i):
    return {"x": np.full((2, 3), i, np.float32),
            "n": np.asarray([i], np.int32)}


def test_order_holds_over_50_placed_batches_with_a_feeder_of_random_delay():
    rng = np.random.RandomState(0)
    delays = rng.uniform(0.0, 0.004, size=50)

    def feeder(i):
        time.sleep(delays[i])
        return _host_feed(i)

    pipe = PrefetchPipeline(lambda: iter(range(50)), feeder=feeder,
                            depth=3)
    got = []
    for i in range(50):
        if i % 7 == 0:
            time.sleep(0.003)       # a consumer that is late at times
        got.append(_pull(pipe))
    with pytest.raises(StopIteration):
        _pull(pipe)
    assert [int(f["n"][0]) for f in got] == list(range(50))
    for i, f in enumerate(got):
        assert isinstance(f["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(f["x"]),
                                      _host_feed(i)["x"])
    pipe._thread.join(timeout=5.0)
    assert not pipe._thread.is_alive()


@pytest.mark.parametrize("where", ["feeder", "placement"])
def test_a_failure_re_raises_at_its_queue_position(where):
    def feeder(i):
        if i == 3:
            if where == "feeder":
                raise KeyError("batch 3 is bad")
            return {"x": object()}      # nothing device_put can place
        return _host_feed(i)

    pipe = PrefetchPipeline(lambda: iter(range(6)), feeder=feeder, depth=2)
    time.sleep(0.2)         # the worker runs into the failure first
    for i in range(3):      # the batches before it still drain, in order
        assert int(_pull(pipe)["n"][0]) == i
    with pytest.raises(KeyError if where == "feeder" else TypeError):
        _pull(pipe)
    with pytest.raises(StopIteration):
        _pull(pipe)
    pipe._thread.join(timeout=5.0)
    assert not _workers()


@pytest.mark.parametrize("stuck_in", ["put_wait", "feeder", "reader"])
def test_close_mid_stream_returns_in_time_with_every_thread_gone(stuck_in):
    entered = threading.Event()

    def reader():
        for i in range(1000):
            if stuck_in == "reader" and i == 4:
                entered.set()
                time.sleep(0.5)
            yield i

    def feeder(i):
        if stuck_in == "feeder" and i == 4:
            entered.set()
            time.sleep(0.5)
        return _host_feed(i)

    assert not _workers()
    pipe = PrefetchPipeline(reader, feeder=feeder, depth=2)
    assert int(_pull(pipe)["n"][0]) == 0
    if stuck_in == "put_wait":
        time.sleep(0.2)     # the queue fills, the worker blocks on it
    else:
        assert _pull(pipe) is not None and _pull(pipe) is not None
        assert entered.wait(timeout=5.0)
    t0 = time.perf_counter()
    pipe.close()
    assert time.perf_counter() - t0 < 5.0       # close()'s join timeout
    assert not pipe._thread.is_alive() and not _workers()
    with pytest.raises(StopIteration):
        _pull(pipe)


def test_batches_in_flight_never_exceed_depth_plus_the_one_stage():
    depth = 2
    made, taken, worst = [], [], []

    def feeder(i):
        made.append(i)
        worst.append(len(made) - len(taken))
        return _host_feed(i)

    pipe = PrefetchPipeline(lambda: iter(range(40)), feeder=feeder,
                            depth=depth)
    for i in range(40):
        if i % 5 == 0:
            time.sleep(0.02)    # let the worker run as far ahead as it may
        _pull(pipe)
        taken.append(i)
    # the queue's `depth`, one in the worker's hands, and the one the
    # consumer has pulled but not yet counted
    assert max(worst) <= depth + 1 + 1
    assert max(worst) >= depth      # it did run ahead
    pipe.close()
    assert not _workers()


def test_spans_of_batch_n_carry_step_n_in_every_stage():
    from paddle_tpu.obs import trace
    from paddle_tpu.utils.profiler import StepBreakdown
    bd = StepBreakdown(StatRegistry("t"))
    trace.install(trace.Tracer("test"))
    try:
        pipe = PrefetchPipeline(lambda: iter(range(5)), feeder=_host_feed,
                                breakdown=bd)
        got = [int(_pull(pipe)["n"][0]) for _ in range(5)]
        with pytest.raises(StopIteration):
            _pull(pipe)
        pipe._thread.join(timeout=5.0)
        assert not pipe._thread.is_alive()
    finally:
        trace.install(None)
    assert got == list(range(5))
    waiting = {n: sorted(name for name, _t0, _s in spans)
               for n, spans in bd._pending.items()}
    stages = ["prefetch.decode", "prefetch.h2d", "prefetch.put_wait",
              "prefetch.read"]
    assert waiting == {**{n: stages for n in range(5)},
                       5: ["prefetch.read"]}
    # and in its own order within a batch: read, decode, h2d, put_wait
    for n in range(5):
        by_start = sorted(bd._pending[n], key=lambda s: s[1])
        assert [name for name, _t0, _s in by_start] == [
            "prefetch.read", "prefetch.decode", "prefetch.h2d",
            "prefetch.put_wait"]


def test_the_worker_places_what_a_real_feeder_built_on_the_host():
    feeder = DataFeeder({"x": dense_vector(4), "y": integer_value(3)})
    rng = np.random.RandomState(2)
    batches = [[(rng.randn(4).astype(np.float32), int(rng.randint(3)))
                for _ in range(4)] for _ in range(12)]
    built = []

    def spy(rows):
        feed = feeder(rows)
        built.append({k: (type(a.value), a.value.copy())
                      for k, a in feed.items()})
        return feed

    pipe = PrefetchPipeline(lambda: iter(batches), feeder=spy, depth=2)
    got = [_pull(pipe) for _ in batches]
    for feed, host in zip(got, built):
        for k, (kind, value) in host.items():
            assert kind is np.ndarray
            assert isinstance(feed[k].value, jax.Array)
            np.testing.assert_array_equal(np.asarray(feed[k].value), value)
    pipe.close()
    # twelve batches went through the memory of a few
    assert feeder._staging.allocated <= 2 + 1 + 1


def test_recompile_guard_says_when_the_cache_grew():
    f = jax.jit(lambda x: x + 1)
    guard = RecompileGuard(f)
    f(jnp.zeros(2))
    guard.check()
    assert guard.grew is False      # the first check takes the baseline
    f(jnp.zeros(2))
    guard.check()
    assert guard.grew is False
    f(jnp.zeros(3))
    guard.check()
    assert guard.grew is True
    guard.check()
    assert guard.grew is False


def test_prefetch_reader_wrapper_marks_and_streams():
    r = prefetch_reader(lambda: iter([1, 2, 3]), place=False)
    assert r.is_prefetched
    assert list(r()) == [1, 2, 3]
    # a second call re-streams (fresh pipeline per pass)
    assert list(r()) == [1, 2, 3]


def test_prefetched_reader_trains_and_rejects_stray_feeder():
    rng = np.random.RandomState(6)
    data = [(rng.randn(4).astype(np.float32), int(rng.randint(3)))
            for _ in range(8)]
    feeder = DataFeeder({"x": dense_vector(4), "y": integer_value(3)})
    reader = prefetch_reader(lambda: iter([data[:4], data[4:]]),
                             feeder=feeder)
    t = _fc_trainer()
    # passing ANOTHER feeder alongside a prefetched reader is a
    # misconfiguration the trainer must reject loudly, not ignore
    with pytest.raises(ValueError, match="prefetched"):
        t.train(reader, feeder=feeder, num_passes=1)
    t.train(reader, num_passes=2)  # the wrapped form trains
    assert t.step_breakdown()["steps"] == 4
    assert not any(th.name == "prefetch-worker" and th.is_alive()
                   for th in threading.enumerate())


# ------------------------------------------------------------- buckets
def test_length_buckets_pad_len():
    b = LengthBuckets([16, 32, 64])
    assert b.pad_len(1) == 16
    assert b.pad_len(16) == 16
    assert b.pad_len(17) == 32
    assert b.pad_len(64) == 64
    # beyond the last edge: multiples of it, still a bounded menu
    assert b.pad_len(65) == 128
    assert b.pad_len(129) == 192
    with pytest.raises(ValueError):
        LengthBuckets([])
    with pytest.raises(ValueError):
        LengthBuckets([4, 4])


def test_feeder_length_buckets_shape_menu():
    feeder = DataFeeder({"w": integer_value_sequence(50)},
                        length_buckets=[8, 16])
    feed = feeder([([1, 2, 3],), ([4] * 10,)])
    assert feed["w"].value.shape == (2, 16)
    feed = feeder([([1, 2],)])
    assert feed["w"].value.shape == (1, 8)
    # masks mark exactly the real tokens
    assert float(jnp.sum(feed["w"].mask)) == 2.0


def test_feeder_batch_buckets_pads_rows_with_row_mask():
    feeder = DataFeeder({"x": dense_vector(3), "y": integer_value(2)},
                        batch_buckets=[4])
    batch = [(np.ones(3, np.float32), 1), (np.zeros(3, np.float32), 0)]
    feed = feeder(batch)
    assert feed["x"].value.shape == (4, 3)
    assert feed["y"].value.shape == (4,)
    np.testing.assert_array_equal(np.asarray(feed[ROW_MASK_KEY].value),
                                  [1.0, 1.0, 0.0, 0.0])
    # a full batch keeps the SAME pytree structure (no recompile flip)
    full = feeder([(np.ones(3, np.float32), 1)] * 4)
    assert ROW_MASK_KEY in full
    np.testing.assert_array_equal(np.asarray(full[ROW_MASK_KEY].value),
                                  [1.0] * 4)


def _fc_trainer(seed=0):
    dsl.reset()
    x = dsl.data("x", size=4)
    y = dsl.data("y", size=3)
    h = dsl.fc(input=x, size=3, act="softmax")
    cost = dsl.classification_cost(input=h, label=y)
    return SGD(cost=cost, update_equation=Momentum(learning_rate=0.1),
               seed=seed)


def test_padded_rows_contribute_zero_loss_and_grad():
    """The acceptance shape: stepping on [5 real rows] and on [5 real +
    3 dead rows, row-masked] yields the SAME cost, classification error,
    and updated parameters — padding is exactly ignored, including the
    batch-mean denominator."""
    rng = np.random.RandomState(0)
    batch = [(rng.randn(4).astype(np.float32), int(rng.randint(3)))
             for _ in range(5)]
    plain = DataFeeder({"x": dense_vector(4), "y": integer_value(3)})
    padded = DataFeeder({"x": dense_vector(4), "y": integer_value(3)},
                        batch_buckets=[8])

    t1, t2 = _fc_trainer(), _fc_trainer()
    key = jax.random.PRNGKey(7)
    p1, _, m1 = t1._train_step(t1.params, t1.opt_state, plain(batch),
                               key, jnp.int32(0))
    p2, _, m2 = t2._train_step(t2.params, t2.opt_state, padded(batch),
                               key, jnp.int32(0))
    assert float(m1["cost"]) == pytest.approx(float(m2["cost"]), rel=1e-6)
    e1, c1 = (float(v) for v in m1["classification_error"])
    e2, c2 = (float(v) for v in m2["classification_error"])
    assert (e1, c1) == (e2, c2)
    assert c2 == 5.0  # dead rows not in the count
    for name in p1:
        np.testing.assert_allclose(np.asarray(p1[name]),
                                   np.asarray(p2[name]), rtol=1e-6,
                                   atol=1e-7)


def test_row_mask_stays_f32_under_bf16_compute():
    """Masks are f32 count data (CLAUDE.md): _cast_compute must exempt
    the ROW_MASK_KEY entry by key, not rely on callers re-reading the
    uncast feed — and a bf16 step on a padded batch must still train."""
    import jax.numpy as jnp
    dsl.reset()
    x = dsl.data("x", size=4)
    y = dsl.data("y", size=3)
    h = dsl.fc(input=x, size=3, act="softmax")
    cost = dsl.classification_cost(input=h, label=y)
    t = SGD(cost=cost, update_equation=Momentum(learning_rate=0.1),
            compute_dtype="bfloat16")
    feeder = DataFeeder({"x": dense_vector(4), "y": integer_value(3)},
                        batch_buckets=[8])
    feed = feeder([(np.ones(4, np.float32), 1)] * 5)
    cast = t._cast_compute(feed)
    assert cast[ROW_MASK_KEY].value.dtype == jnp.float32
    assert cast["x"].value.dtype == jnp.bfloat16
    _, _, m = t._train_step(t.params, t.opt_state, feed,
                            jax.random.PRNGKey(0), jnp.int32(0))
    assert np.isfinite(float(m["cost"]))
    assert float(m["classification_error"][1]) == 5.0


def test_batch_bucket_overflow_raises():
    """Batch sizes are a closed menu: a batch beyond the largest bucket
    is a reader/config mismatch, not something to silently pad around."""
    feeder = DataFeeder({"x": dense_vector(3)}, batch_buckets=[4])
    with pytest.raises(ValueError, match="largest batch bucket"):
        feeder([(np.ones(3, np.float32),)] * 5)


def test_padded_sequence_rows_have_dead_masks():
    """A dead row on a sequence input is an all-zero token mask — the
    existing mask-as-count semantics every layer already honors."""
    feeder = DataFeeder({"w": integer_value_sequence(20)},
                        length_buckets=[8], batch_buckets=[4])
    feed = feeder([([1, 2, 3],), ([4, 5],)])
    assert feed["w"].value.shape == (4, 8)
    mask = np.asarray(feed["w"].mask)
    assert mask[:2].sum() == 5.0
    assert mask[2:].sum() == 0.0  # padded rows: fully masked


# ------------------------------------------------------- recompile guard
def _seq_trainer(vocab=30, recompile_warn=8):
    dsl.reset()
    w = dsl.data("w", size=vocab)
    y = dsl.data("y", size=2)
    e = dsl.embedding(input=w, size=8, vocab_size=vocab)
    p = dsl.pooling(input=e, pooling_type="avg")
    h = dsl.fc(input=p, size=2, act="softmax")
    cost = dsl.classification_cost(input=h, label=y)
    return SGD(cost=cost, update_equation=Momentum(learning_rate=0.1),
               recompile_warn=recompile_warn)


def _ragged_reader(vocab=30, n_batches=8, bsz=2):
    rng = np.random.RandomState(3)
    lengths = rng.randint(1, 60, size=n_batches * bsz)

    def reader():
        it = iter(lengths)
        for _ in range(n_batches):
            yield [(list(rng.randint(0, vocab, size=next(it))),
                    int(rng.randint(2))) for _ in range(bsz)]
    return reader


def test_ragged_corpus_bucketing_bounds_recompiles():
    vocab = 30
    buckets = [16, 32, 64]
    feeder = DataFeeder({"w": integer_value_sequence(vocab),
                         "y": integer_value(2)}, length_buckets=buckets)
    t = _seq_trainer(vocab)
    t.train(_ragged_reader(vocab), feeder=feeder, num_passes=1)
    n = t.recompile_guard.count
    assert n is not None and n <= len(buckets), n
    assert not t.recompile_guard.warned


def test_unbucketed_ragged_corpus_thrashes_and_guard_warns(caplog):
    vocab = 30
    # pad_multiple=1: every distinct raw max-length is its own shape
    feeder = DataFeeder({"w": integer_value_sequence(vocab),
                         "y": integer_value(2)}, pad_multiple=1)
    t = _seq_trainer(vocab, recompile_warn=3)
    import logging
    plogger = logging.getLogger("paddle_tpu")
    plogger.addHandler(caplog.handler)
    try:
        t.train(_ragged_reader(vocab), feeder=feeder, num_passes=1)
    finally:
        plogger.removeHandler(caplog.handler)
    n = t.recompile_guard.count
    assert n is not None and n > 3, n
    assert t.recompile_guard.warned
    assert "compile cache" in caplog.text


def test_jit_cache_probe_counts_variants():
    f = jax.jit(lambda x: x * 2)
    assert jit_cache_size(f) == 0  # the probe exists on this jax
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))
    assert jit_cache_size(f) == 2
    g = RecompileGuard(f, warn_after=1, name="probe")
    assert g.check() == 2
    assert g.warned
    assert g.harden() == 2
    # a callable without the probe leaves the advisory check quiet, but
    # it cannot be HARDENED: that guard would never trip
    blind = RecompileGuard(object(), name="blind")
    assert jit_cache_size(object()) is None and blind.check() is None
    with pytest.raises(RuntimeError, match="no jit-cache probe"):
        blind.harden()


# ----------------------------------------------------- trainer integration
def test_async_training_matches_sync_training():
    """Same data, same seeds: the async pipeline must be a pure overlap
    optimization — costs identical batch for batch."""
    rng = np.random.RandomState(1)
    data = [(rng.randn(4).astype(np.float32), int(rng.randint(3)))
            for _ in range(12)]
    feeder = DataFeeder({"x": dense_vector(4), "y": integer_value(3)})

    def reader():
        for i in range(0, len(data), 4):
            yield data[i:i + 4]

    costs = {}
    for mode in ("sync", "async"):
        t = _fc_trainer(seed=5)
        got = []
        t.train(reader, feeder=feeder, num_passes=2,
                async_load_data=(mode == "async"),
                event_handler=lambda e: got.append(e.cost)
                if hasattr(e, "cost") else None)
        costs[mode] = got
    assert costs["sync"] == pytest.approx(costs["async"], rel=1e-6)
    assert len(costs["sync"]) == 6


def test_step_breakdown_accumulates_all_parts():
    rng = np.random.RandomState(2)
    data = [(rng.randn(4).astype(np.float32), int(rng.randint(3)))
            for _ in range(8)]
    feeder = DataFeeder({"x": dense_vector(4), "y": integer_value(3)})
    t = _fc_trainer()
    t.train(lambda: iter([data[:4], data[4:]]), feeder=feeder, num_passes=1)
    s = t.step_breakdown()
    assert s["steps"] == 2
    assert s["steps_per_sec"] > 0
    assert s["compute_frac"] > 0
    # denominator is TRUE wall time: the four parts cover most-but-not-
    # all of it (BeginIteration handlers / rng splits are outside), so
    # the sum must be close to 1 from BELOW, never above
    fracs = sum(s[f"{p}_frac"] for p in ("data_wait", "h2d", "compute",
                                         "callback"))
    assert 0.5 < fracs <= 1.0 + 1e-9


def test_async_pipeline_closed_when_loop_raises():
    """A raising event handler (the v2 early-stop idiom) must not leak
    the prefetch worker thread — train() closes the pipe in a finally."""
    rng = np.random.RandomState(0)
    data = [(rng.randn(4).astype(np.float32), int(rng.randint(3)))
            for _ in range(8)]
    feeder = DataFeeder({"x": dense_vector(4), "y": integer_value(3)})
    t = _fc_trainer()

    class Stop(Exception):
        pass

    def handler(e):
        if e.__class__.__name__ == "EndIteration":
            raise Stop

    with pytest.raises(Stop):
        t.train(lambda: iter([data[:4], data[4:]] * 50), feeder=feeder,
                num_passes=1, async_load_data=True, event_handler=handler)
    assert not any(th.name == "prefetch-worker" and th.is_alive()
                   for th in threading.enumerate())


def test_host_evaluators_never_see_padded_rows():
    """Config-declared (host-side) evaluators on NON-sequence layers get
    the live-row prefix only — batch-bucket padding is exactly ignored
    on this path too, not just in the cost."""
    def build(batch_buckets):
        dsl.reset()
        x = dsl.data("x", size=4)
        y = dsl.data("y", size=3)
        h = dsl.fc(input=x, size=3, act="softmax")
        cost = dsl.classification_cost(input=h, label=y)
        dsl.evaluator("classification_error", input=h, label=y,
                      name="host_err")
        t = SGD(cost=cost, update_equation=Momentum(learning_rate=0.1))
        f = DataFeeder({"x": dense_vector(4), "y": integer_value(3)},
                       batch_buckets=batch_buckets)
        return t, f

    rng = np.random.RandomState(4)
    batch = [(rng.randn(4).astype(np.float32), int(rng.randint(3)))
             for _ in range(5)]
    vals = {}
    for tag, buckets in (("plain", None), ("padded", [8])):
        t, f = build(buckets)
        t.train(lambda: iter([batch]), feeder=f, num_passes=1)
        vals[tag] = t.host_eval_values()["host_err"]
    assert vals["padded"] == pytest.approx(vals["plain"], rel=1e-6)
