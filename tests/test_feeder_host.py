"""The feeder's contract (`data/feeder.py`): every leaf of a feed is a
host array with the values, dtypes and masks the placing feeder made;
the memory of a dense batch is used again only once nothing can read
the batch; a batch placed on the device never changes afterwards."""

import gc
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.data import DataFeeder, ROW_MASK_KEY
from paddle_tpu.data import types as T
from paddle_tpu.data.feeder import _Staging

DIM = 6
PAD = 4


def _ceil(n, m):
    return ((max(n, 1) + m - 1) // m) * m


def _one(kind, rng):
    """One timestep's (or one no-sequence sample's) raw value."""
    if kind == T.DENSE:
        return rng.standard_normal(DIM).astype(np.float32)
    if kind == T.INDEX:
        return int(rng.integers(0, DIM))
    idxs = sorted(rng.choice(DIM, size=2, replace=False).tolist())
    if kind == T.SPARSE_BINARY:
        return idxs
    return [(j, float(rng.standard_normal())) for j in idxs]


def _dense_of(kind, x):
    """The [DIM] (or scalar id) array one raw value stands for."""
    if kind == T.DENSE:
        return np.asarray(x, np.float32)
    if kind == T.INDEX:
        return np.int32(x)
    out = np.zeros(DIM, np.float32)
    for e in x:
        j, v = (e, 1.0) if kind == T.SPARSE_BINARY else e
        out[j] = v
    return out


def _samples(kind, seq, rng, n=5):
    if seq == T.NO_SEQUENCE:
        return [_one(kind, rng) for _ in range(n)]
    if seq == T.SEQUENCE:
        return [[_one(kind, rng) for _ in range(int(rng.integers(1, 7)))]
                for _ in range(n)]
    return [[[_one(kind, rng) for _ in range(int(rng.integers(1, 7)))]
             for _ in range(int(rng.integers(1, 4)))] for _ in range(n)]


def _oracle(kind, seq, col):
    """What the feeder made before it stopped placing, written plainly:
    (value, mask). The dense no-sequence expression is the old code's."""
    dt = np.int32 if kind == T.INDEX else np.float32
    feat = () if kind == T.INDEX else (DIM,)
    if seq == T.NO_SEQUENCE:
        if kind == T.DENSE:
            return np.asarray(col, dtype=np.float32), None
        return np.stack([_dense_of(kind, x) for x in col]).astype(dt), None
    if seq == T.SEQUENCE:
        t = _ceil(max(len(s) for s in col), PAD)
        value = np.zeros((len(col), t) + feat, dt)
        mask = np.zeros((len(col), t), np.float32)
        for i, s in enumerate(col):
            for k, x in enumerate(s):
                value[i, k] = _dense_of(kind, x)
                mask[i, k] = 1.0
        return value, mask
    n_sub = max(len(s) for s in col)
    t = _ceil(max(len(ss) for s in col for ss in s), PAD)
    value = np.zeros((len(col), n_sub, t) + feat, dt)
    mask = np.zeros((len(col), n_sub, t), np.float32)
    for i, s in enumerate(col):
        for j, ss in enumerate(s):
            for k, x in enumerate(ss):
                value[i, j, k] = _dense_of(kind, x)
                mask[i, j, k] = 1.0
    return value, mask


def _same(leaf, want):
    assert type(leaf) is np.ndarray
    assert leaf.dtype == want.dtype and leaf.shape == want.shape
    np.testing.assert_array_equal(leaf, want)
    # what the old feeder handed out was jnp.asarray of this very array
    placed = jnp.asarray(leaf)
    assert placed.dtype == leaf.dtype
    np.testing.assert_array_equal(np.asarray(placed), want)


CASES = [(kind, seq) for kind in (T.DENSE, T.INDEX, T.SPARSE_BINARY,
                                  T.SPARSE_FLOAT)
         for seq in (T.NO_SEQUENCE, T.SEQUENCE, T.SUB_SEQUENCE)]


@pytest.mark.parametrize(
    "kind,seq", CASES,
    ids=[f"{k}-{('plain', 'seq', 'subseq')[s]}" for k, s in CASES])
def test_leaves_are_host_arrays_equal_to_the_old_feeders(kind, seq):
    rng = np.random.default_rng(7)
    col = _samples(kind, seq, rng)
    labels = [int(rng.integers(0, 3)) for _ in col]
    feeder = DataFeeder({"x": T.InputType(DIM, seq, kind),
                         "y": T.integer_value(3)}, pad_multiple=PAD)
    feed = feeder(list(zip(col, labels)))
    value, mask = _oracle(kind, seq, col)
    _same(feed["x"].value, value)
    if mask is None:
        assert feed["x"].mask is None
    else:
        _same(feed["x"].mask, mask)
    _same(feed["y"].value, np.asarray(labels, np.int32))
    assert all(type(leaf) is np.ndarray
               for leaf in jax.tree_util.tree_leaves(feed))


def test_batch_buckets_pad_rows_and_emit_a_host_row_mask():
    rng = np.random.default_rng(3)
    rows = [(rng.standard_normal(DIM).astype(np.float32),
             [int(rng.integers(0, 9)) for _ in range(3)], i % 3)
            for i in range(5)]
    feeder = DataFeeder({"x": T.dense_vector(DIM),
                         "w": T.integer_value_sequence(9),
                         "y": T.integer_value(3)},
                        pad_multiple=PAD, batch_buckets=[4, 8])
    feed = feeder(rows)
    x = np.zeros((8, DIM), np.float32)
    x[:5] = np.asarray([r[0] for r in rows], dtype=np.float32)
    _same(feed["x"].value, x)
    w = np.zeros((8, PAD), np.int32)
    w[:5, :3] = [r[1] for r in rows]
    wm = np.zeros((8, PAD), np.float32)
    wm[:5, :3] = 1.0
    _same(feed["w"].value, w)
    _same(feed["w"].mask, wm)
    _same(feed["y"].value, np.asarray([0, 1, 2, 0, 1, 0, 0, 0], np.int32))
    _same(feed[ROW_MASK_KEY].value,
          np.asarray([1, 1, 1, 1, 1, 0, 0, 0], np.float32))


@pytest.mark.parametrize("rows", [
    [[1.0, 2.0, 3.0], [4, 5, 6]],                       # lists, mixed
    [np.arange(3, dtype=np.float64), np.ones(3, np.float64)],
    [np.arange(3, dtype=np.uint8), np.ones(3, np.uint8)],
    [np.ones((2, 2), np.float32), np.zeros((2, 2), np.float32)],
    [1.5, 2.5],                                         # dim 1, scalars
], ids=["lists", "float64", "uint8", "shaped", "scalars"])
def test_dense_rows_of_any_kind_stack_like_asarray(rows):
    feeder = DataFeeder({"x": T.dense_vector(3)})
    _same(feeder([(r,) for r in rows])["x"].value,
          np.asarray(rows, dtype=np.float32))


def test_dense_rows_of_two_shapes_raise_and_do_not_broadcast():
    feeder = DataFeeder({"x": T.dense_vector(3)})
    with pytest.raises(ValueError, match="row 1 has shape"):
        feeder([(np.ones(3, np.float32),), (np.ones(1, np.float32),)])


# ------------------------------------------------------------ the staging
def _dense_batch(k, n=4, dim=1024):
    return [(np.full(dim, k, np.float32),) for _ in range(n)]


def test_a_dropped_batchs_memory_serves_the_next_one():
    feeder = DataFeeder({"x": T.dense_vector(1024)})
    seen = set()
    for k in range(20):
        value = feeder(_dense_batch(k))["x"].value
        assert (value == k).all()
        seen.add(value.ctypes.data)
        del value
    assert feeder._staging.allocated == 1 and len(seen) == 1


def test_a_batch_or_any_view_of_it_keeps_its_memory():
    feeder = DataFeeder({"x": T.dense_vector(1024)})
    held = [feeder(_dense_batch(k))["x"].value for k in range(3)]
    row = feeder(_dense_batch(3))["x"].value[1, 5:9]    # a view alone
    flat = feeder(_dense_batch(4))["x"].value.reshape(-1).view(np.int32)
    gc.collect()
    for k in range(5, 5 + 3 * _Staging.KEEP):
        feeder(_dense_batch(k))
    for k, value in enumerate(held):
        assert (value == k).all()
    assert (row == 3).all()
    assert (flat.view(np.float32) == 4).all()
    # five batches are held, the stream behind them turned over one block
    assert feeder._staging.allocated == 6


def test_free_blocks_are_bounded_and_shapes_do_not_mix():
    feeder = DataFeeder({"x": T.dense_vector(1024)})
    held = [feeder(_dense_batch(k))["x"].value
            for k in range(_Staging.KEEP + 3)]
    del held
    gc.collect()
    assert len(feeder._staging._free) == _Staging.KEEP
    small = feeder(_dense_batch(1, n=2))["x"].value
    assert small.shape == (2, 1024) and (small == 1).all()
    assert feeder._staging.allocated == _Staging.KEEP + 4


def test_concurrent_callers_never_share_a_block():
    """The serving batcher's threads convert through one feeder."""
    import sys
    feeder = DataFeeder({"x": T.dense_vector(4096)})
    bad, done = [], []

    def work(tid):
        for k in range(200):
            tag = tid * 1000 + k
            value = feeder(_dense_batch(tag, n=3, dim=4096))["x"].value
            if not (value == tag).all():
                bad.append(tag)
        done.append(tid)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,), daemon=True)
                   for t in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
    finally:
        sys.setswitchinterval(old)
    assert sorted(done) == list(range(12)) and not bad


def test_a_placed_batch_never_changes_on_the_cpu_backend():
    """Place batch 0, build and place batches 1..K with K past every
    block the feeder keeps: batch 0's device values still equal its
    source. (On the CPU a device array may alias the host array it was
    made from; the alias then holds the block.)"""
    dim = 64 * 1024     # 1 MiB of float32 a batch of 4: a buffer of size
    feeder = DataFeeder({"x": T.dense_vector(dim)})
    rng = np.random.default_rng(0)
    sources = [rng.standard_normal((4, dim)).astype(np.float32)
               for _ in range(4 + 3 * _Staging.KEEP)]

    def place(src):
        return jax.device_put(feeder([(r,) for r in src]))["x"].value

    first = place(sources[0])
    first.block_until_ready()
    later = [place(src) for src in sources[1:]]
    jax.block_until_ready(later)
    gc.collect()
    np.testing.assert_array_equal(np.asarray(first), sources[0])
    for got, src in zip(later, sources[1:]):
        np.testing.assert_array_equal(np.asarray(got), src)
