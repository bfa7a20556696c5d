"""Pallas kernel parity tests (run in interpreter mode on the CPU mesh).

Mirrors the reference's CPU-vs-GPU equivalence strategy
(`paddle/math/tests/test_matrixCompare.cpp`, `TensorCheck.h`): every fused
kernel is compared — values AND gradients — against the pure-JAX reference
implementation it replaces.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import common
from paddle_tpu.ops.attention import (blockwise_attention, flash_attention,
                                      mha_reference)
from paddle_tpu.ops.gru import gru_sequence, gru_sequence_ref
from paddle_tpu.ops.lstm import lstm_sequence, lstm_sequence_ref


def _ragged_mask(T, B, rng):
    lens = rng.integers(1, T + 1, size=B)
    lens[0] = T
    return (np.arange(T)[:, None] < lens[None, :]).astype(np.float32)


def test_lstm_kernel_matches_reference():
    rng = np.random.default_rng(0)
    T, B, H = 7, 4, 8
    xs = jnp.asarray(rng.normal(size=(T, B, 4 * H)), jnp.float32)
    mask = jnp.asarray(_ragged_mask(T, B, rng))
    w = jnp.asarray(rng.normal(size=(H, 4 * H)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(4 * H,)) * 0.1, jnp.float32)
    pI, pF, pO = (jnp.asarray(rng.normal(size=(H,)) * 0.1, jnp.float32)
                  for _ in range(3))
    h0 = jnp.asarray(rng.normal(size=(B, H)) * 0.1, jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(B, H)) * 0.1, jnp.float32)

    def loss(fn, xs, w, b, pI, pF, pO, h0, c0):
        ys, hT, cT = fn(xs, mask, w, b, pI, pF, pO, h0, c0)
        return (jnp.sum(ys * jnp.cos(ys * 0 + 1.3))
                + jnp.sum(hT * 0.7) + jnp.sum(cT * 0.3))

    args = (xs, w, b, pI, pF, pO, h0, c0)
    ref_val, ref_g = jax.value_and_grad(
        lambda *a: loss(lstm_sequence_ref, *a), argnums=tuple(range(8)))(*args)
    with common.force_mode("interpret"):
        ys, hT, cT = lstm_sequence(xs, mask, *args[1:])
        ys_r, hT_r, cT_r = lstm_sequence_ref(xs, mask, *args[1:])
        np.testing.assert_allclose(ys, ys_r, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(hT, hT_r, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(cT, cT_r, rtol=1e-5, atol=1e-5)
        val, grads = jax.value_and_grad(
            lambda *a: loss(lstm_sequence, *a), argnums=tuple(range(8)))(*args)
    np.testing.assert_allclose(val, ref_val, rtol=1e-5)
    for g, rg in zip(grads, ref_g):
        np.testing.assert_allclose(g, rg, rtol=1e-4, atol=1e-5)


def test_gru_kernel_matches_reference():
    rng = np.random.default_rng(1)
    T, B, H = 6, 3, 8
    xs = jnp.asarray(rng.normal(size=(T, B, 3 * H)), jnp.float32)
    mask = jnp.asarray(_ragged_mask(T, B, rng))
    wg = jnp.asarray(rng.normal(size=(H, 2 * H)) * 0.2, jnp.float32)
    ws = jnp.asarray(rng.normal(size=(H, H)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.normal(size=(3 * H,)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, H)) * 0.1, jnp.float32)

    def loss(fn, xs, wg, ws, b, h0):
        ys, hT = fn(xs, mask, wg, ws, b, h0)
        return jnp.sum(ys * jnp.sin(ys * 0 + 0.9)) + jnp.sum(hT * 0.5)

    args = (xs, wg, ws, b, h0)
    ref_val, ref_g = jax.value_and_grad(
        lambda *a: loss(gru_sequence_ref, *a), argnums=tuple(range(5)))(*args)
    with common.force_mode("interpret"):
        ys, hT = gru_sequence(xs, mask, *args[1:])
        ys_r, hT_r = gru_sequence_ref(xs, mask, *args[1:])
        np.testing.assert_allclose(ys, ys_r, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(hT, hT_r, rtol=1e-5, atol=1e-5)
        val, grads = jax.value_and_grad(
            lambda *a: loss(gru_sequence, *a), argnums=tuple(range(5)))(*args)
    np.testing.assert_allclose(val, ref_val, rtol=1e-5)
    for g, rg in zip(grads, ref_g):
        np.testing.assert_allclose(g, rg, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_reference(causal):
    rng = np.random.default_rng(2)
    B, N, T, D = 2, 2, 33, 8
    q = jnp.asarray(rng.normal(size=(B, N, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, N, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, N, T, D)), jnp.float32)
    kv_mask = jnp.asarray(_ragged_mask(T, B, rng).T)  # [B, T]

    out_ref = mha_reference(q, k, v, kv_mask, causal=causal)
    out_blk = blockwise_attention(q, k, v, kv_mask, causal=causal, block_k=8)
    np.testing.assert_allclose(out_blk, out_ref, rtol=1e-5, atol=1e-5)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, kv_mask, causal=causal) ** 2)

    g_ref = jax.grad(lambda *a: loss(mha_reference, *a), (0, 1, 2))(q, k, v)
    g_blk = jax.grad(
        lambda *a: loss(lambda q_, k_, v_, m, causal: blockwise_attention(
            q_, k_, v_, m, causal=causal, block_k=8), *a), (0, 1, 2))(q, k, v)
    for g, rg in zip(g_blk, g_ref):
        np.testing.assert_allclose(g, rg, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_kernel(causal):
    rng = np.random.default_rng(3)
    B, N, T, D = 2, 2, 40, 8
    q = jnp.asarray(rng.normal(size=(B, N, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, N, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, N, T, D)), jnp.float32)
    kv_mask = jnp.asarray(_ragged_mask(T, B, rng).T)

    out_ref = mha_reference(q, k, v, kv_mask, causal=causal)
    with common.force_mode("interpret"):
        out = flash_attention(q, k, v, kv_mask, causal=causal,
                              block_q=16, block_k=16)
        np.testing.assert_allclose(out, out_ref, rtol=1e-5, atol=1e-5)
        # grads come from the two backward kernels
        g = jax.grad(lambda q_: jnp.sum(flash_attention(
            q_, k, v, kv_mask, causal=causal, block_q=16, block_k=16) ** 2)
        )(q)
    g_ref = jax.grad(lambda q_: jnp.sum(
        mha_reference(q_, k, v, kv_mask, causal=causal) ** 2))(q)
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [
    # B, N, Tq, Tk, Dqk, Dv, block_q, block_k, ragged mask
    (2, 2, 64, 64, 24, 16, 16, 16, False),    # latent attention's 3:2
    (2, 3, 40, 72, 24, 16, 16, 16, True),     # Tq != Tk, padded, masked
    (1, 2, 64, 64, 12, 8, 32, 16, False),     # block_q != block_k
    (1, 2, 64, 64, 12, 8, 16, 32, False),
])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_qk_and_v_widths_differ(shape, causal):
    """q, k of one head size and v of another, forward and the backward
    kernels (dQ; dK, dV) against ``mha_reference``; ``blockwise_attention``
    takes the same shapes. Causal blocks above the diagonal are skipped:
    the result must not notice."""
    B, N, Tq, Tk, Dqk, Dv, bq, bk, ragged = shape
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, N, Tq, Dqk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, N, Tk, Dqk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, N, Tk, Dv)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, N, Tq, Dv)), jnp.float32)
    kv_mask = jnp.asarray(_ragged_mask(Tk, B, rng).T) if ragged else None

    def run(fn):
        return jax.value_and_grad(
            lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) * w), (0, 1, 2))(q, k, v)

    want = run(lambda *a: mha_reference(*a, kv_mask, causal=causal))
    with common.force_mode("interpret"), \
            common.record_dispatch() as tally:
        got = run(lambda *a: flash_attention(
            *a, kv_mask, causal=causal, block_q=bq, block_k=bk))
    assert tally["flash_attention"] == {"interpret": 1}
    blk = run(lambda *a: blockwise_attention(*a, kv_mask, causal=causal,
                                             block_k=bk))
    for have in (got, blk):
        assert float(have[0]) == pytest.approx(float(want[0]), rel=1e-5)
        for g, rg in zip(have[1], want[1]):
            np.testing.assert_allclose(g, rg, rtol=1e-4, atol=2e-5)


def _keys(kind, B, Tk, rng):
    """Key masks for the walk's cases: none, all ones, one invalid key
    in a block that is otherwise below the diagonal, a padded tail."""
    if kind == "none":
        return None
    m = np.ones((B, Tk), np.float32)
    if kind == "one_invalid":
        m[0, 5] = 0.0
    elif kind == "padded_tail":
        m[:, Tk - 7:] = 0.0
        m[1, Tk - 20:] = 0.0
    return jnp.asarray(m)


@pytest.mark.parametrize("widths", [(24, 16), (16, 16)])
@pytest.mark.parametrize("keys", ["none", "ones", "one_invalid",
                                  "padded_tail"])
@pytest.mark.parametrize("Tq,Tk", [(64, 64), (40, 72)])
def test_flash_attention_walks_only_the_tiles_a_query_sees(Tq, Tk, keys,
                                                           widths):
    """Causal, blocks of 16: the kernels' grids hold one step a (q block,
    kv block) pair in which a query sees a key and none for the pairs
    above the diagonal (T_k = T_q, and T_k > T_q where every query sees
    T_k - T_q keys more; the second pads both lengths). Output and all
    three gradients against ``mha_reference``, whatever the key mask."""
    from paddle_tpu.ops.attention import _Tiles
    B, N, (Dqk, Dv) = 2, 2, widths
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(B, N, Tq, Dqk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, N, Tk, Dqk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, N, Tk, Dv)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, N, Tq, Dv)), jnp.float32)
    kv_mask = _keys(keys, B, Tk, rng)

    def run(fn):
        return jax.value_and_grad(
            lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) * w), (0, 1, 2))(q, k, v)

    want = run(lambda *a: mha_reference(*a, kv_mask, causal=True))
    with common.force_mode("interpret"):
        got = run(lambda *a: flash_attention(*a, kv_mask, causal=True,
                                             block_q=16, block_k=16))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, rg in zip(got[1], want[1]):
        np.testing.assert_allclose(g, rg, rtol=1e-4, atol=2e-5)
    # the grids: fewer steps than pairs, the same tiles in both orders
    nq, nk = -(-Tq // 16), -(-Tk // 16)
    walks = [_Tiles(N, Tk - Tq, True, 16, 16, nq, nk, kv_major)
             for kv_major in (False, True)]
    assert walks[0].steps == walks[1].steps < nq * nk
    assert walks[0].whole is None


def _walk(tiles):
    qb, kb, ends = np.asarray(tiles.walk).reshape(3, tiles.steps)
    return list(zip(qb.tolist(), kb.tolist())), ends.tolist()


def test_the_walk_of_a_causal_grid_and_of_a_whole_one():
    from paddle_tpu.ops.attention import _FIRST, _LAST, _Tiles
    both = _FIRST | _LAST
    # three q blocks of 16 over three kv blocks of 16, T_k = T_q
    pairs, ends = _walk(_Tiles(1, 0, True, 16, 16, 3, 3, kv_major=False))
    assert pairs == [(0, 0), (1, 0), (1, 1), (2, 0), (2, 1), (2, 2)]
    assert ends == [both, _FIRST, _LAST, _FIRST, 0, _LAST]
    pairs, ends = _walk(_Tiles(1, 0, True, 16, 16, 3, 3, kv_major=True))
    assert pairs == [(0, 0), (1, 0), (2, 0), (1, 1), (2, 1), (2, 2)]
    assert ends == [_FIRST, 0, _LAST, _FIRST, _LAST, both]
    # q blocks of 32 over kv blocks of 16: the diagonal crosses two
    pairs, _ = _walk(_Tiles(1, 0, True, 32, 16, 2, 4, kv_major=False))
    assert pairs == [(0, 0), (0, 1), (1, 0), (1, 1), (1, 2), (1, 3)]
    # 16 more keys than queries: every query sees one kv block more
    pairs, _ = _walk(_Tiles(1, 16, True, 16, 16, 2, 3, kv_major=False))
    assert pairs == [(0, 0), (0, 1), (1, 0), (1, 1), (1, 2)]
    # every pair a step: no table, the grid's own two indices
    assert _Tiles(1, 0, False, 16, 16, 3, 2, kv_major=False).whole == (3, 2)
    assert _Tiles(1, 64, True, 16, 16, 3, 2, kv_major=True).whole == (2, 3)


def test_a_sweep_that_sees_nothing_still_writes_its_block():
    """32 queries more than keys, causal: the first two q blocks see no
    key and the walk keeps one masked tile for each (their rows are
    whatever a fully masked row is, but finite, and no gradient leaves
    them into a kv block that nothing sees); the rows that do see keys
    are the reference's."""
    from paddle_tpu.ops.attention import _Tiles
    B, N, Tq, Tk, D = 1, 2, 64, 32, 8
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(B, N, Tq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, N, Tk, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, N, Tk, D)), jnp.float32)
    live = jnp.asarray((np.arange(Tq) >= Tq - Tk)[None, None, :, None],
                       jnp.float32)

    def run(fn):
        return jax.value_and_grad(
            lambda q_, k_, v_: jnp.sum((fn(q_, k_, v_) * live) ** 2),
            (0, 1, 2))(q, k, v)

    want = run(lambda *a: mha_reference(*a, causal=True))
    with common.force_mode("interpret"):
        got = run(lambda *a: flash_attention(*a, causal=True, block_q=16,
                                             block_k=16))
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    assert np.isfinite(np.asarray(out)).all()
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, rg in zip(got[1], want[1]):
        np.testing.assert_allclose(g, rg, rtol=1e-4, atol=2e-5)
    pairs, _ = _walk(_Tiles(N, Tk - Tq, True, 16, 16, 4, 2, kv_major=False))
    assert pairs == [(0, 0), (1, 0), (2, 0), (3, 0), (3, 1)]


def test_a_walk_too_long_for_its_table_takes_the_reference_path():
    """The causal walk's table of steps lives in SMEM; a call whose grid
    has more pairs than the table's budget holds stands down, and says
    so in the tally. A grid in which every pair is a step has no table."""
    T = 8 * 210          # 210 x 210 blocks of 8: 44,100 pairs
    x = jax.ShapeDtypeStruct((1, 1, T, 8), jnp.float32)
    for causal, path in ((True, "ref"), (False, "interpret")):
        with common.force_mode("interpret"), \
                common.record_dispatch() as tally:
            jax.eval_shape(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block_q=8, block_k=8), x, x, x)
        assert tally["flash_attention"] == {path: 1}


# window (a causal band) and grouped queries ---------------------------

def _band_case(B, N, Nkv, Tq, Tk, D, seed, masked=True):
    """q, k, v, the loss's weights and a ragged key mask (or None). A
    query whose own position is masked is padding: what it reads is
    whatever a row without a key is, so the loss gives it no weight."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, N, Tq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Nkv, Tk, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Nkv, Tk, D)), jnp.float32)
    w = rng.normal(size=(B, N, Tq, D)).astype(np.float32)
    if not masked:
        return q, k, v, jnp.asarray(w), None
    kv_mask = _ragged_mask(Tk, B, rng).T
    w = w * kv_mask[:, None, Tk - Tq:, None]
    return q, k, v, jnp.asarray(w), jnp.asarray(kv_mask)


def _value_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) * w), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("window", [5, 16, 24, 40, 200])
@pytest.mark.parametrize("Tq,Tk,masked", [(64, 64, False), (40, 40, True),
                                          (40, 72, True)])
def test_flash_attention_window(Tq, Tk, masked, window):
    """A window below, at, above and not a multiple of the block of 16,
    and wider than the sequence; lengths a multiple of the block or not,
    more keys than queries, with and without a key mask: the forward and
    all three gradients against ``mha_reference`` (which masks a plain
    score matrix), and ``blockwise_attention`` the same."""
    q, k, v, w, kv_mask = _band_case(2, 2, 2, Tq, Tk, 8, 17, masked)
    want = _value_and_grads(lambda *a: mha_reference(
        *a, kv_mask, causal=True, window=window), q, k, v, w)
    with common.force_mode("interpret"), \
            common.record_dispatch() as tally:
        got = _value_and_grads(lambda *a: flash_attention(
            *a, kv_mask, causal=True, block_q=16, block_k=16,
            window=window), q, k, v, w)
    assert tally["flash_attention"] == {"interpret": 1}
    blk = _value_and_grads(lambda *a: blockwise_attention(
        *a, kv_mask, causal=True, block_k=16, window=window), q, k, v, w)
    for have in (got, blk):
        assert float(have[0]) == pytest.approx(float(want[0]), rel=1e-5,
                                                abs=2e-5)
        for g, rg in zip(have[1], want[1]):
            np.testing.assert_allclose(g, rg, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 24)])
@pytest.mark.parametrize("N,Nkv", [(2, 2), (6, 1), (12, 2), (8, 1),
                                   (16, 2)])
def test_flash_attention_grouped_queries(N, Nkv, causal, window):
    """Groups of 1, 6 and 8 query heads a key-value head (one and two
    key-value heads), with a ragged key mask and a length (40) that is
    no multiple of the block: K and V keep their own heads into the
    kernels, dK and dV come back at those heads, summed over the group
    inside the dK/dV kernel. A whole grid (no ``causal``), the causal
    walk and the band."""
    q, k, v, w, kv_mask = _band_case(2, N, Nkv, 40, 40, 8, 19)
    want = _value_and_grads(lambda *a: mha_reference(
        *a, kv_mask, causal=causal, window=window), q, k, v, w)
    with common.force_mode("interpret"):
        got = _value_and_grads(lambda *a: flash_attention(
            *a, kv_mask, causal=causal, block_q=16, block_k=16,
            window=window), q, k, v, w)
    blk = _value_and_grads(lambda *a: blockwise_attention(
        *a, kv_mask, causal=causal, block_k=16, window=window), q, k, v, w)
    assert got[1][1].shape == k.shape and got[1][2].shape == v.shape
    for have in (got, blk):
        assert float(have[0]) == pytest.approx(float(want[0]), rel=1e-5,
                                                abs=2e-5)
        for g, rg in zip(have[1], want[1]):
            np.testing.assert_allclose(g, rg, rtol=1e-4, atol=3e-5)


# the backward as one kernel -------------------------------------------

def _backward_operands(B, N, Nkv, Tq, Tk, Dqk, Dv, bq, bk, causal, window,
                       masked, dtype):
    """``cfg`` and what the backward kernels take, the forward kernel's
    output and log-sum-exp among them, as `_flash_backward` hands them
    on."""
    from paddle_tpu.ops import attention as A
    keys = jax.random.split(jax.random.PRNGKey(29), 4)
    q, k, v, do = (
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key, shape in zip(keys, [(B * N, Tq, Dqk), (B * Nkv, Tk, Dqk),
                                     (B * Nkv, Tk, Dv), (B * N, Tq, Dv)]))
    mask = jnp.ones((B, 1, Tk), jnp.float32)
    if masked:
        mask = mask.at[:, :, Tk - 5:].set(0).at[:, :, 3].set(0)
    cfg = (N, Tk - Tq, Dqk ** -0.5, causal, bq, bk, window, N // Nkv)
    out, lse = A._flash_forward(cfg, q, k, v, mask)
    return cfg, (q, k, v, mask, out, lse, do), (
        q, k, v, mask, do, A._backward_stats(out, lse, do))


bf16, f32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("case", [
    # B, N, Nkv, Tq, Tk, Dqk, Dv, bq, bk, causal, window, masked, dtype
    pytest.param((2, 2, 2, 128, 128, 192, 128, 32, 32, True, None, False,
                  bf16), id="latent_192_128_group1"),
    pytest.param((1, 2, 2, 128, 128, 128, 128, 32, 32, True, None, False,
                  bf16), id="looped_128_group1"),
    pytest.param((1, 6, 1, 128, 128, 128, 128, 32, 32, True, None, False,
                  bf16), id="group6"),
    pytest.param((1, 6, 1, 128, 128, 128, 128, 32, 32, True, 32, False,
                  bf16), id="group6_window"),
    pytest.param((1, 8, 1, 128, 128, 128, 128, 32, 32, True, None, False,
                  bf16), id="group8"),
    pytest.param((1, 8, 1, 128, 128, 128, 128, 32, 32, True, 32, False,
                  bf16), id="group8_window_a_block"),
    pytest.param((1, 16, 2, 128, 128, 128, 128, 32, 32, True, 48, True,
                  bf16), id="group8_window_off_the_blocks_kv_mask"),
    pytest.param((2, 2, 2, 128, 128, 128, 128, 32, 32, True, None, True,
                  bf16), id="kv_mask"),
    pytest.param((1, 2, 2, 64, 128, 128, 128, 32, 32, True, None, False,
                  bf16), id="more_keys_than_queries"),
    pytest.param((1, 2, 2, 64, 256, 128, 128, 32, 32, True, 32, False,
                  bf16), id="kv_blocks_no_query_sees"),
    pytest.param((1, 2, 2, 128, 128, 128, 128, 32, 32, False, None, True,
                  bf16), id="not_causal_kv_mask"),
    pytest.param((1, 4, 2, 96, 128, 64, 32, 32, 64, False, None, False,
                  bf16), id="not_causal_group2_blocks_differ"),
    pytest.param((1, 2, 2, 64, 64, 24, 16, 16, 16, True, None, True, f32),
                 id="float32"),
])
def test_fused_backward_equals_the_two_kernels(case):
    """dq, dk and dv of the one backward kernel against dK/dV's and dQ's,
    on the same operands: every sum adds the same terms in the same
    order, so they are the same bits, at the three cells' head sizes and
    groups (cut in length), under a window, a key mask, more keys than
    queries and no ``causal``. The float32 case holds both to
    ``blockwise_attention``'s own gradients."""
    from paddle_tpu.ops import attention as A
    B, N, Nkv, Tq, Tk, Dqk, Dv = case[:7]
    with common.force_mode("interpret"), \
            common.record_dispatch() as tally:
        cfg, residuals, operands = _backward_operands(*case)
        fused = A._flash_backward(cfg, *residuals)
        split = A._backward_split(cfg, *operands)
    assert tally["flash_backward"] == {"fused": 1}
    for name, a, b in zip(("dq", "dk", "dv"), fused, split):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), name)
    if case[-1] == f32:
        q, k, v, mask, _, _, do = residuals
        causal, window = case[9], case[10]
        _, back = jax.vjp(
            lambda q, k, v: blockwise_attention(
                q, k, v, mask[:, 0], causal=causal, window=window,
                scale=cfg[2], block_k=cfg[5]),
            q.reshape(B, N, Tq, Dqk), k.reshape(B, Nkv, Tk, Dqk),
            v.reshape(B, Nkv, Tk, Dv))
        for g, rg in zip(fused, back(do.reshape(B, N, Tq, Dv))):
            np.testing.assert_allclose(g.reshape(rg.shape), rg, rtol=1e-4,
                                       atol=2e-5)


def test_fused_backward_keeps_the_tile_of_a_q_block_that_sees_nothing():
    """32 queries more than keys, causal: the first q blocks see no key.
    The one kernel writes their dq from the all-masked tile that the
    forward's and dQ's walks keep for them (dK/dV's own walk has no such
    tile), so dq is dQ's bits, and dK, dV differ from dK/dV's only in kv
    block 0, by those rows' share."""
    from paddle_tpu.ops import attention as A
    case = (1, 2, 2, 64, 32, 128, 128, 16, 16, True, None, False, bf16)
    with common.force_mode("interpret"):
        cfg, residuals, operands = _backward_operands(*case)
        fused = A._flash_backward(cfg, *residuals)
        split = A._backward_split(cfg, *operands)
    np.testing.assert_array_equal(np.asarray(fused[0], np.float32),
                                  np.asarray(split[0], np.float32))
    for a, b in zip(fused[1:], split[1:]):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a[:, 16:], np.float32),
                                      np.asarray(b[:, 16:], np.float32))


@pytest.mark.parametrize("shape,window,path", [
    ((2, 32, 32, 4096, 192, 128), None, "fused"),       # the JoyAI cell's
    ((1, 16, 16, 4096, 128, 128), None, "fused"),       # the Ouro cell's
    ((1, 64, 8, 8192, 128, 128), 512, "fused"),         # Laguna, sliding
    ((1, 48, 8, 8192, 128, 128), None, "split"),        # Laguna, full
])
def test_the_backward_is_one_kernel_where_dq_fits_beside_it(shape, window,
                                                            path):
    """Which backward runs follows from the reckoned bytes against
    ``VMEM_BUDGET_BYTES``, and the tally says it: at 512 x 512 tiles the
    cells' cores of one query head a key-value head hold dQ for the
    whole of a row (4.2 and 2.1 MB), a band of 512 keeps two q blocks
    open for each of a group's 8 heads (4.2 MB), and 6 heads over the
    whole of 8,192 positions would take 25 MB: the two kernels."""
    B, N, Nkv, T, Dqk, Dv = shape

    def sd(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16)

    def back(q, k, v, g):
        return jax.vjp(lambda *a: flash_attention(
            *a, None, causal=True, block_q=512, block_k=512,
            window=window), q, k, v)[1](g)

    with common.force_mode("interpret"), \
            common.record_dispatch() as tally:
        jax.eval_shape(back, sd(B, N, T, Dqk), sd(B, Nkv, T, Dqk),
                       sd(B, Nkv, T, Dv), sd(B, N, T, Dv))
    assert tally == {"flash_attention": {"interpret": 1},
                     "flash_backward": {path: 1}}


@pytest.mark.parametrize("args", [
    # off, causal, nq, nk, window, group (blocks of 16)
    (0, True, 4, 4, None, 1), (0, True, 4, 4, None, 3),
    (0, True, 8, 8, 16, 2), (0, True, 8, 8, 24, 1), (0, True, 8, 8, 5, 2),
    (32, True, 4, 6, None, 1), (64, True, 4, 8, 16, 2),
    (-32, True, 4, 2, None, 1),
])
def test_the_fused_walk_opens_and_closes_every_q_block_once(args):
    """The table the one backward kernel walks: every (query head, q
    block) is opened at its first step and closed at its last, the slot
    it takes of dQ's ``ring`` is no other open block's meanwhile, and
    dQ's output block is, at every step, the block that closes next: a
    run of consecutive steps that ends where it is written. A band
    keeps fewer slots than q blocks."""
    from paddle_tpu.ops.attention import _CLOSES, _OPENS, _Tiles
    off, causal, nq, nk, window, group = args
    tiles = _Tiles(group, off, causal, 16, 16, nq, nk, kv_major=True,
                   window=window, group=group, dq_too=True)
    cols = np.asarray(tiles.walk).reshape(-1, tiles.steps)
    qb, ends, out = cols[0], cols[2], cols[-1]
    head = cols[3] if group > 1 else np.zeros_like(qb)
    open_in, closed, runs = {}, set(), []
    for t in range(tiles.steps):
        ident, slot = head[t] * nq + qb[t], (head[t], qb[t] % tiles.ring)
        if ends[t] & _OPENS:
            assert slot not in open_in and ident not in closed
            open_in[slot] = ident
        assert open_in[slot] == ident
        if not runs or runs[-1] != out[t]:
            runs.append(out[t])
        if ends[t] & _CLOSES:
            assert out[t] == ident
            closed.add(open_in.pop(slot))
    assert not open_in and closed == set(range(group * nq))
    assert sorted(runs) == list(range(group * nq))     # one run a block
    if window is not None and off <= window:
        assert tiles.ring == -(-window // 16) + (window % 16 != 1) < nq


def test_grouped_queries_need_a_divisor_and_a_window_needs_causal():
    x = jnp.zeros((1, 6, 16, 8))
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(x, x[:, :4], x[:, :4])
    with pytest.raises(ValueError, match="causal band"):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="causal band"):
        mha_reference(x, x, x, causal=True, window=0)


@pytest.mark.parametrize("bq,bk", [(16, 16), (32, 16), (16, 32), (8, 24)])
@pytest.mark.parametrize("window", [None, 1, 7, 16, 17, 40, 1000])
@pytest.mark.parametrize("Tq,Tk", [(96, 96), (48, 96), (96, 48)])
def test_the_walk_of_a_band_holds_every_visible_pair_once(Tq, Tk, window,
                                                          bq, bk):
    """A property of ``_Tiles`` under ``causal`` with and without a
    window, in both orders and under a group: every pair the mask lets
    see lies in exactly one walked tile (of every query head of the
    group, in dK/dV's order), and no walked tile is without a visible
    pair but the one that a sweep which sees nothing keeps; a kv-major
    sweep holds its kv block's tiles of the group's heads in turn, first
    and last marked once a sweep."""
    from paddle_tpu.ops.attention import _FIRST, _LAST, _Tiles, walked_pairs
    off = Tk - Tq
    nq, nk = -(-Tq // bq), -(-Tk // bk)
    qi = np.arange(nq * bq)[:, None] + off
    kj = np.arange(nk * bk)[None, :]
    sees = kj <= qi
    if window is not None:
        sees &= qi - kj < window
    # by tile: how many visible pairs it holds
    held = sees.reshape(nq, bq, nk, bk).sum(axis=(1, 3))
    for kv_major, group in ((False, 1), (True, 1), (True, 3)):
        t = _Tiles(group, off, True, bq, bk, nq, nk, kv_major,
                   window=window, group=group)
        if t.whole:
            assert (held > 0).all()
            assert t.whole == ((nk, group * nq) if kv_major else (nq, nk))
            continue
        cols = np.asarray(t.walk).reshape(-1, t.steps)
        qb, kb, ends = cols[:3]
        head = cols[3] if group > 1 else np.zeros_like(qb)
        walked = np.zeros((group, nq, nk), int)
        np.add.at(walked, (head, qb, kb), 1)
        assert walked.max() == 1                     # no tile twice
        for g in range(group):
            assert (walked[g][held > 0] == 1).all()  # every visible pair
            blind = (walked[g] == 1) & (held == 0)
            # only where a whole sweep sees nothing, one tile of it
            if kv_major:
                assert (blind.sum(axis=0) <= (held.sum(axis=0) == 0)).all()
            else:
                assert (blind.sum(axis=1) <= (held.sum(axis=1) == 0)).all()
        sweep = kb if kv_major else qb
        assert (np.diff(sweep) >= 0).all()           # sweeps in order
        starts = np.r_[True, np.diff(sweep) != 0]
        stops = np.r_[np.diff(sweep) != 0, True]
        assert ((ends & _FIRST) != 0).tolist() == starts.tolist()
        assert ((ends & _LAST) != 0).tolist() == stops.tolist()
        if kv_major and group > 1:      # within a sweep, head by head
            for b in np.unique(kb):
                assert (np.diff(head[kb == b]) >= 0).all()
    if Tq * Tk == nq * bq * nk * bk:        # nothing padded
        visited, visible = walked_pairs(Tq, Tk, True, window, bq, bk)
        t = _Tiles(1, off, True, bq, bk, nq, nk, False, window=window)
        steps = nq * nk if t.whole else t.steps
        assert visited == steps * bq * bk and visible == sees.sum()


def test_walked_pairs_of_the_cells_sliding_layer():
    """8,192 positions, a window of 512 in 512 x 512 tiles: 31 tiles a
    head (the causal walk has 136, the whole grid 256), twice the pairs
    the mask lets see."""
    from paddle_tpu.ops.attention import _Tiles, walked_pairs
    assert walked_pairs(8192, 8192, True, 512, 512, 512) == (
        31 * 512 * 512, 4_063_488)
    assert _Tiles(64, 0, True, 512, 512, 16, 16, False, window=512,
                  group=8).steps == 31
    assert _Tiles(64, 0, True, 512, 512, 16, 16, True, window=512,
                  group=8).steps == 8 * 31
    assert _Tiles(48, 0, True, 512, 512, 16, 16, False, group=6).steps == 136


def test_lstm_layer_uses_fused_path():
    """lstmemory layer output must be identical with kernels forced to the
    reference tier vs the fused tier (the layer auto-dispatches)."""
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.core.network import Network

    rng = np.random.default_rng(4)
    dsl.reset()
    inp = dsl.data("x", size=32, is_sequence=True)
    lstm = dsl.lstmemory(input=dsl.fc(input=inp, size=32, act="linear",
                                      bias_attr=False))
    net = Network(dsl.current_graph(), outputs=[lstm.name])
    params = net.init_params(jax.random.PRNGKey(0))
    x = rng.normal(size=(2, 5, 32)).astype(np.float32)
    mask = _ragged_mask(5, 2, rng).T
    feed = {"x": Argument(value=jnp.asarray(x), mask=jnp.asarray(mask))}
    with common.force_mode("ref"):
        out_ref = net.apply(params, feed, train=False)[lstm.name].value
    with common.force_mode("interpret"):
        out_pal = net.apply(params, feed, train=False)[lstm.name].value
    np.testing.assert_allclose(out_pal, out_ref, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------- CRF

def _crf_inputs(B=4, T=7, C=9, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, T, C).astype(np.float32))
    lengths = rng.randint(2, T + 1, size=B)
    mask = jnp.asarray((np.arange(T)[None, :] < lengths[:, None])
                       .astype(np.float32))
    trans = jnp.asarray(rng.randn(C, C).astype(np.float32))
    a = jnp.asarray(rng.randn(C).astype(np.float32))
    b = jnp.asarray(rng.randn(C).astype(np.float32))
    return x, mask, trans, a, b


def test_crf_ref_matches_plain_logsumexp_scan():
    """The max-shifted exp-space-matmul reference equals the direct
    logsumexp formulation used by layers/chain.py historically."""
    from paddle_tpu.ops.crf import crf_log_z_ref

    def _logsumexp(x, axis=-1):
        m = jnp.max(x, axis=axis, keepdims=True)
        return jnp.squeeze(m, axis) + jnp.log(
            jnp.sum(jnp.exp(x - m), axis=axis))
    x, mask, trans, a, b = _crf_inputs()
    alpha = a[None, :] + x[:, 0]
    for t in range(1, x.shape[1]):
        nxt = _logsumexp(alpha[:, :, None] + trans[None], axis=1) + x[:, t]
        alpha = jnp.where(mask[:, t][:, None] > 0, nxt, alpha)
    want = _logsumexp(alpha + b[None, :], axis=1)
    got = crf_log_z_ref(x, mask, trans, a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_crf_pallas_kernel_matches_reference():
    """Interpret-mode kernel parity (values + all grads) with the class
    axis padded 9 -> 128 inside the dispatcher."""
    from paddle_tpu.ops.crf import crf_log_z, crf_log_z_ref
    x, mask, trans, a, b = _crf_inputs()

    def loss(fn):
        return lambda x_, tr_, a_, b_: jnp.sum(fn(x_, mask, tr_, a_, b_)
                                               * jnp.arange(1., 5.))

    with common.force_mode("interpret"):
        got = crf_log_z(x, mask, trans, a, b)
        g_got = jax.grad(loss(crf_log_z), argnums=(0, 1, 2, 3))(
            x, trans, a, b)
    with common.force_mode("ref"):
        want = crf_log_z_ref(x, mask, trans, a, b)
        g_want = jax.grad(loss(crf_log_z_ref), argnums=(0, 1, 2, 3))(
            x, trans, a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for gg, gw in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                                   rtol=1e-4, atol=1e-4)


def test_crf_layer_end_to_end_with_kernel_dispatch():
    """crf_log_likelihood (gold score - log Z) is identical through the
    kernel path and the scan path, full-mask and ragged."""
    from paddle_tpu.layers.chain import crf_log_likelihood
    x, mask, trans, a, b = _crf_inputs(B=3, T=5, C=6, seed=1)
    w = jnp.concatenate([a[None], b[None], trans], axis=0)
    rng = np.random.RandomState(2)
    labels = jnp.asarray(rng.randint(0, 6, size=(3, 5)).astype(np.int32))
    with common.force_mode("interpret"):
        got = crf_log_likelihood(x, labels, mask, w)
    with common.force_mode("ref"):
        want = crf_log_likelihood(x, labels, mask, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # likelihoods are proper: exp(ll) in (0, 1]
    assert np.all(np.asarray(want) <= 1e-5)


def test_crf_grad_finite_with_forbidden_transitions():
    """Strongly forbidden transitions (trans ~ -1e4, the constraint trick)
    must give finite gradients — the pairwise marginal is accumulated in
    probability space, never through an overflowing factorization."""
    from paddle_tpu.ops.crf import crf_log_z
    x, mask, trans, a, b = _crf_inputs(B=3, T=6, C=5, seed=3)
    trans = trans.at[0, 1].set(-1e4).at[2, 3].set(-1e4)
    with common.force_mode("interpret"):
        g = jax.grad(lambda t_: jnp.sum(crf_log_z(x, mask, t_, a, b)))(trans)
    assert np.all(np.isfinite(np.asarray(g)))
    assert abs(float(g[0, 1])) < 1e-6 and abs(float(g[2, 3])) < 1e-6


# ------------------------------------------------------------------- CTC

def _ctc_inputs(B=4, T=12, C=6, L=4, seed=0):
    rng = np.random.RandomState(seed)
    log_probs = jax.nn.log_softmax(
        jnp.asarray(rng.randn(B, T, C).astype(np.float32)), axis=-1)
    labels = jnp.asarray(rng.randint(0, C - 1, size=(B, L)).astype(np.int32))
    lab_lens = rng.randint(1, L + 1, size=B)
    label_mask = jnp.asarray((np.arange(L)[None, :] < lab_lens[:, None])
                             .astype(np.float32))
    in_lens = rng.randint(2 * L + 1, T + 1, size=B)
    in_mask = jnp.asarray((np.arange(T)[None, :] < in_lens[:, None])
                          .astype(np.float32))
    return log_probs, labels, in_mask, label_mask


def test_ctc_pallas_kernel_matches_reference():
    """Interpret-mode CTC kernel parity (loss + d loss / d log_probs) with
    the extended axis padded 2L+1 -> 128 in the dispatcher."""
    from paddle_tpu.layers.chain import ctc_loss
    log_probs, labels, in_mask, label_mask = _ctc_inputs()

    def loss(fn_mode, lp):
        with common.force_mode(fn_mode):
            return jnp.sum(ctc_loss(lp, labels, in_mask, label_mask,
                                    blank=5) * jnp.arange(1., 5.))

    got = loss("interpret", log_probs)
    want = loss("ref", log_probs)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    g_got = jax.grad(lambda lp: loss("interpret", lp))(log_probs)
    g_want = jax.grad(lambda lp: loss("ref", lp))(log_probs)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=2e-4, atol=2e-5)


def test_ctc_ref_analytic_grad_matches_autodiff():
    """The hand-written beta-recursion VJP (used by the kernel path) must
    equal autodiff through the scan reference."""
    from paddle_tpu.ops.ctc import _ctc_core, ctc_ll_ref
    from paddle_tpu.layers.chain import ctc_loss
    log_probs, labels, in_mask, label_mask = _ctc_inputs(seed=2)
    B, T, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    ext = jnp.full((B, S), 5, jnp.int32).at[:, 1::2].set(labels)
    lab_lens = jnp.sum(label_mask, axis=1).astype(jnp.int32)
    ext_lens = 2 * lab_lens + 1
    s_idx = jnp.arange(S)[None, :]
    valid_s = (s_idx < ext_lens[:, None]).astype(jnp.float32)
    ext_m2 = jnp.concatenate(
        [jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
    can_skip = ((ext != 5) & (ext != ext_m2)).astype(jnp.float32)
    emit = jnp.take_along_axis(
        log_probs, jnp.broadcast_to(ext[:, None, :], (B, T, S)), axis=2)

    def ll_core(e):
        return jnp.sum(_ctc_core(e, in_mask, valid_s, can_skip, ext_lens))

    def ll_ref(e):
        return jnp.sum(ctc_ll_ref(e, in_mask, valid_s, can_skip, ext_lens))

    with common.force_mode("interpret"):
        v_core = float(ll_core(emit))
        g_core = jax.grad(ll_core)(emit)
    v_ref = float(ll_ref(emit))
    g_ref = jax.grad(ll_ref)(emit)
    np.testing.assert_allclose(v_core, v_ref, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_core), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-5)


# ------------------------------------------- kernels under a mesh
def test_kernels_run_per_device_or_stand_down_under_a_mesh():
    """XLA cannot partition a Mosaic kernel (jax 0.9.0 refuses to lower
    it inside a multi-device step). Traced into a step declared
    partitioned over a mesh (``common.step_mesh``) whose batch axes
    divide the batch, the kernel runs per device through ``batch_local``
    — dispatch sees the per-device batch, values and gradients match the
    reference; under one that cannot split it the reference runs. A
    ``shard_map`` body is per-device code: nothing is partitioned
    there."""
    from paddle_tpu.ops import common
    from paddle_tpu.ops.lstm import lstm_sequence
    from paddle_tpu.parallel import create_mesh
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.mesh import shard_map_compat
    mesh = create_mesh(n_data=4, devices=jax.devices()[:4])
    assert not common.partitioned() and common.batch_split(6) == 1
    with common.step_mesh(mesh):
        assert common.partitioned()
        assert common.batch_split(16) == 4
        assert common.batch_split(6) == 0   # must take the reference
        with common.step_mesh(None):        # declares nothing: inherits
            assert common.current_mesh() is mesh
        inside = []
        shard_map_compat(lambda x: inside.append(common.partitioned()) or x,
                         mesh, in_specs=P("data"), out_specs=P("data"))(
                             jnp.zeros((4,)))
        assert inside == [False]
    assert common.current_mesh() is None
    rng = np.random.RandomState(0)
    T, B, H = 3, 16, 128
    xs = jnp.asarray(rng.randn(T, B, 4 * H).astype(np.float32) * 0.3)
    mask = jnp.ones((T, B), jnp.float32).at[2:, 5].set(0.0)
    w = jnp.asarray(rng.randn(H, 4 * H).astype(np.float32) * 0.1)
    zb, zc = jnp.zeros((4 * H,)), jnp.zeros((H,))
    h0 = jnp.zeros((B, H))

    def loss(x, w_):
        return jnp.sum(lstm_sequence(x, mask, w_, zb, zc, zc, zc, h0,
                                     h0)[0] ** 2)

    with common.force_mode("ref"):
        want = jax.grad(loss, argnums=(0, 1))(xs, w)
    with common.force_mode("interpret"), common.step_mesh(mesh), \
            common.record_dispatch() as tally:
        got = jax.jit(jax.grad(loss, argnums=(0, 1)))(xs, w)
        # a batch the four-way axis cannot split: the reference runs
        lstm_sequence(xs[:, :6], mask[:, :6], w, zb, zc, zc, zc,
                      h0[:6], h0[:6])
    assert tally == {"lstm": {"resident": 1, "ref": 1}}
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-5)


# ------------------------------------------------- tiled-H LSTM (big H)
def test_lstm_dispatch_pins_bench_shapes():
    """The benchmark shapes must take their intended kernel path
    (VERDICT r3 weak #5: the h=1280 BASELINE row silently lost the fused
    kernel). h=256 (headline bench) -> resident; h=1280 -> tiled, NOT
    the scan fallback — except where the chip refused: the working set
    is counted as Mosaic allocates it (double-buffered blocks) against
    the 16 MiB scoped-VMEM limit of a v5e, and the two widest batches
    do not fit (PERF.md, "State of the chip path")."""
    from paddle_tpu.ops import common
    from paddle_tpu.ops.lstm import lstm_dispatch
    with common.force_mode("pallas"):
        # EVERY BASELINE.md rnn-table shape (benchmark/README.md:108-161)
        assert lstm_dispatch(64, 256) == "resident"
        assert lstm_dispatch(64, 512) == "resident"
        assert lstm_dispatch(64, 1280) == "tiled"
        assert lstm_dispatch(128, 256) == "resident"
        assert lstm_dispatch(128, 1280) == "tiled"
        assert lstm_dispatch(256, 256) == "resident"
        # narrowed by the chip run: h0/c0 blocks + state scratch alone
        # are ~9 MiB at B=256, H=1280; with the smallest weight block
        # the kernel needs ~19 MiB
        assert lstm_dispatch(256, 1280) == "ref"
        assert lstm_dispatch(512, 512) == "ref"  # 4-GPU table row, ~16 MiB
        # the largest resident shape the chip has compiled; one more
        # lane tile of hidden width streams
        assert lstm_dispatch(64, 640) == "resident"
        assert lstm_dispatch(64, 768) == "tiled"
        # H % 128 != 0 compiles on the chip (TPU_EVIDENCE.json): no
        # alignment gate on the resident path
        assert lstm_dispatch(8, 16) == "resident"
        assert lstm_dispatch(8, 200) == "resident"
    with common.force_mode("ref"):
        assert lstm_dispatch(64, 256) == "ref"


def test_dispatch_table_matches_pins():
    """``kernel_dispatch_table()`` says in one place what the LSTM takes
    at every BASELINE.md shape (VERDICT r04 item #8); the table must
    agree with the pins above — narrowing included."""
    from paddle_tpu.ops import common
    from paddle_tpu.ops.lstm import kernel_dispatch_table
    with common.force_mode("pallas"):
        table = kernel_dispatch_table()
    assert table == {
        "lstm_bs64_h256": "resident", "lstm_bs64_h512": "resident",
        "lstm_bs64_h1280": "tiled", "lstm_bs128_h256": "resident",
        "lstm_bs128_h1280": "tiled", "lstm_bs256_h256": "resident",
        "lstm_bs256_h1280": "ref", "lstm_bs512_h512": "ref"}


def test_record_dispatch_shows_the_silent_gates():
    """Shape and budget gates stay silent dispatch; ``record_dispatch``
    is how a caller sees which path each kernel entry took."""
    from paddle_tpu.ops import common
    from paddle_tpu.ops.lstm import lstm_sequence
    T, B, H = 2, 8, 128
    xs = jnp.zeros((T, B, 4 * H), jnp.float32)
    mask = jnp.ones((T, B), jnp.float32)
    w = jnp.zeros((H, 4 * H), jnp.float32)
    zb, zc = jnp.zeros((4 * H,)), jnp.zeros((H,))
    h0 = jnp.zeros((B, H))
    with common.record_dispatch() as outer:
        with common.force_mode("interpret"), \
                common.record_dispatch() as inner:
            lstm_sequence(xs, mask, w, zb, zc, zc, zc, h0, h0)
        with common.force_mode("ref"):
            lstm_sequence(xs, mask, w, zb, zc, zc, zc, h0, h0)
    assert inner == {"lstm": {"resident": 1}}
    assert outer == {"lstm": {"resident": 1, "ref": 1}}
    assert common.forced() is None


def test_lstm_tiled_matches_ref_fwd_bwd():
    """The tiled kernel (weight streamed in gate-column blocks) matches
    the scan reference bitwise-close on forward and grads, at a shape
    that genuinely exceeds the resident VMEM budget (H=1280)."""
    from paddle_tpu.ops import common
    from paddle_tpu.ops.lstm import (_pick_hblock, lstm_sequence,
                                     lstm_sequence_ref)
    rng = np.random.RandomState(0)
    T, B, H = 3, 8, 1280
    assert _pick_hblock(H, B, 4) == 128  # streams 10 column blocks
    xs = jnp.asarray(rng.randn(T, B, 4 * H).astype(np.float32) * 0.1)
    mask = np.ones((T, B), np.float32)
    mask[1:, -2:] = 0.0  # ragged tail
    mask = jnp.asarray(mask)
    w = jnp.asarray(rng.randn(H, 4 * H).astype(np.float32) * 0.05)
    zb = jnp.zeros((4 * H,), jnp.float32)
    pI = jnp.asarray(rng.randn(H).astype(np.float32) * 0.1)
    pF = jnp.asarray(rng.randn(H).astype(np.float32) * 0.1)
    pO = jnp.asarray(rng.randn(H).astype(np.float32) * 0.1)
    h0 = c0 = jnp.zeros((B, H), jnp.float32)

    want_ys, want_h, want_c = lstm_sequence_ref(xs, mask, w, zb, pI, pF,
                                                pO, h0, c0)
    with common.force_mode("interpret"):
        from paddle_tpu.ops.lstm import lstm_dispatch
        assert lstm_dispatch(B, H) == "tiled"
        got_ys, got_h, got_c = lstm_sequence(xs, mask, w, zb, pI, pF, pO,
                                             h0, c0)
    np.testing.assert_allclose(np.asarray(got_ys), np.asarray(want_ys),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                               rtol=2e-5, atol=2e-5)

    def loss_tiled(xs_, w_):
        with common.force_mode("interpret"):
            ys, hT, cT = lstm_sequence(xs_, mask, w_, zb, pI, pF, pO,
                                       h0, c0)
        return jnp.sum(ys ** 2) + jnp.sum(hT) + jnp.sum(cT)

    def loss_ref(xs_, w_):
        ys, hT, cT = lstm_sequence_ref(xs_, mask, w_, zb, pI, pF, pO,
                                       h0, c0)
        return jnp.sum(ys ** 2) + jnp.sum(hT) + jnp.sum(cT)

    gx_t, gw_t = jax.grad(loss_tiled, argnums=(0, 1))(xs, w)
    gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(xs, w)
    np.testing.assert_allclose(np.asarray(gx_t), np.asarray(gx_r),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(gw_t), np.asarray(gw_r),
                               rtol=3e-4, atol=3e-3)


# ------------------------------- the scan path's analytic backward (PR 34)
def _scan_case(mask_kind, state, peep, dtype=jnp.float32, bias=0.1,
               T=9, B=6, H=16):
    rng = np.random.default_rng(5)

    def arr(*shape, scale):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    mask = (_ragged_mask(T, B, rng) if mask_kind == "ragged"
            else np.ones((T, B), np.float32))
    args = (arr(T, B, 4 * H, scale=1.0), arr(H, 4 * H, scale=0.1),
            arr(4 * H, scale=bias), arr(H, scale=peep), arr(H, scale=peep),
            arr(H, scale=peep), arr(B, H, scale=state),
            arr(B, H, scale=state))
    weights = (arr(T, B, H, scale=1.0), arr(B, H, scale=1.0),
               arr(B, H, scale=1.0))
    return jnp.asarray(mask), args, weights


def _scan_grads(fn, mask, args, weights):
    """Gradients of a weighted sum of all three outputs with respect to
    all eight differentiable arguments."""
    def loss(xs, *rest):
        out = fn(xs, mask, *rest)
        return sum(jnp.sum(o.astype(jnp.float32) * r.astype(jnp.float32))
                   for o, r in zip(out, weights))
    return jax.jit(jax.grad(loss, argnums=tuple(range(8))))(*args)


def _reversed_ref(xs, mask, *rest):
    ys, hT, cT = lstm_sequence_ref(jnp.flip(xs, 0), jnp.flip(mask, 0), *rest)
    return jnp.flip(ys, 0), hT, cT


@pytest.mark.parametrize("peep", [0.0, 0.1], ids=["peep0", "peep"])
@pytest.mark.parametrize("state", [0.0, 0.3], ids=["state0", "state"])
@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("mask_kind", ["full", "ragged"])
def test_lstm_scan_path_grads_match_autodiff(mask_kind, reverse, state,
                                             peep):
    """The "ref" dispatch is a ``custom_vjp`` with the kernels' analytic
    backward; JAX's own differentiation of ``lstm_sequence_ref`` is the
    gold, for all eight arguments."""
    mask, args, weights = _scan_case(mask_kind, state, peep)
    with common.force_mode("ref"), common.record_dispatch() as tally:
        got = _scan_grads(
            lambda *a: lstm_sequence(*a, reverse=reverse), mask, args,
            weights)
    assert tally == {"lstm": {"ref": 1}}
    want = _scan_grads(_reversed_ref if reverse else lstm_sequence_ref,
                       mask, args, weights)
    for g, r in zip(got, want):
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(g), r, rtol=1e-5,
                                   atol=1e-5 * np.abs(r).max())


def test_lstm_scan_path_grads_bf16():
    """Under a bfloat16 compute dtype the rule's sums are float32 and its
    outputs the arguments' dtype, within the tolerance the tiled kernel's
    bfloat16 case is held to (``tools/tpu_evidence.py:BF16_TOLS``)."""
    mask, args, weights = _scan_case("ragged", 0.3, 0.1, jnp.bfloat16)
    with common.force_mode("ref"):
        got = _scan_grads(lstm_sequence, mask, args, weights)
    want = _scan_grads(lstm_sequence_ref, mask, args, weights)
    for g, r, a in zip(got, want, args):
        assert g.dtype == a.dtype == jnp.bfloat16
        g, r = (np.asarray(x, np.float32) for x in (g, r))
        assert np.abs(g - r).max() <= 1e-1 * np.abs(r).max()


@pytest.mark.parametrize("peep", [0.0, 0.1], ids=["peep0", "peep"])
@pytest.mark.parametrize("mask_kind", ["full", "ragged"])
def test_lstm_scan_path_dgates_equal_autodiff_bit_for_bit(mask_kind, peep):
    """The rule rounds as JAX's own rules do, so with no gate bias to
    fold the gradients of xs, h0 and c0 are the autodiff'd scan's bits.
    The benchmark's check rests on it: on the chip a product rounds its
    operands to bfloat16 and a last-bit difference in ``dgates`` grows
    to 1.5e-3 of a bias leaf's gradient over 100 steps (PERF.md PR 34)."""
    mask, args, weights = _scan_case(mask_kind, 0.3, peep, bias=0.0, T=12)
    with common.force_mode("ref"):
        got = _scan_grads(lstm_sequence, mask, args, weights)
    want = _scan_grads(lstm_sequence_ref, mask, args, weights)
    for k in (0, 6, 7):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_lstm_scan_backward_carries_no_weight_gradient():
    """The static counter of PR 34: in the gradient of a two-layer stack
    no ``scan`` carries an array of the weight's shape, and each layer
    has exactly one ``dot_general`` that contracts T*B rows (``dW``)."""
    T, B, H = 5, 4, 8
    mask, args, _ = _scan_case("full", 0.0, 0.1, T=T, B=B, H=H)

    def loss(w0, w1, xs, *rest):
        with common.force_mode("ref"):
            ys, _, _ = lstm_sequence(xs, mask, w0, *rest)
            ys, _, _ = lstm_sequence(jnp.tile(ys, 4), mask, w1, *rest)
        return jnp.sum(ys)

    w = args[1]
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        w, w, args[0], *args[2:]).jaxpr
    scans = over_rows = 0
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "scan":
            scans += 1
            n, k = eqn.params["num_consts"], eqn.params["num_carry"]
            carry = [v.aval.shape for v in eqn.invars[n:n + k]]
            assert carry == [(B, H), (B, H)], carry
        if eqn.primitive.name == "dot_general":
            (lhs, _), _ = eqn.params["dimension_numbers"]
            rows = math.prod(eqn.invars[0].aval.shape[d] for d in lhs)
            if rows == T * B:
                over_rows += 1
                assert eqn.outvars[0].aval.shape == w.shape
    assert scans == 4 and over_rows == 2
