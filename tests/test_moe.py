"""The expert layer (``parallel/moe.py``, ``layers/moe.py``): sigmoid
top-k routing with a selection bias, SwiGLU experts, a shared expert, a
layer told which experts it holds. Compared with the plain reference
(``benchmark/reference/joyai_llm_flash_ep32.py:_experts``, a dense
one-hot combine with no sort), with itself over a CPU mesh, and with
itself on the kernel's path (megablox in interpret mode)."""

import importlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmark.reference import plain
from paddle_tpu.ops import common
from paddle_tpu.ops import rows as row_ops
from paddle_tpu.parallel import create_mesh
from paddle_tpu.parallel import moe as moe_lib
from paddle_tpu.parallel.moe import (init_moe_params, make_moe, moe_ffn,
                                     shard_moe_params)

ref = importlib.import_module("benchmark.reference.joyai_llm_flash_ep32")

D, H, E, K, T = 16, 32, 8, 2, 64
SCALE = 2.5


@pytest.fixture()
def setup():
    params = init_moe_params(jax.random.PRNGKey(0), D, H, E)
    params["br"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
    return params, x


def reference(params, x, held=None, offset=0, shared=True):
    """The plain reference's expert layer on the same leaves."""
    e = params["wr"].shape[-1]
    m = {"n_routed_experts": e, "experts_held": held or e,
         "expert_offset": offset, "num_experts_per_tok": K,
         "routed_scaling_factor": SCALE}
    p = {f"_l_moe.{k}": v for k, v in params.items()
         if shared or k not in ("sg", "su", "sd")}
    with jax.default_matmul_precision("highest"):
        return ref._experts(p, "l", x, m, plain.Arith())


def share(params, lo, hi):
    """The leaves a device holding experts lo..hi has."""
    return {k: v[lo:hi] if k in ("wg", "wu", "wd") else v
            for k, v in params.items()}


def test_layer_matches_plain_reference_and_trains(setup):
    """Output and every leaf's gradient equal the reference's (router
    and selection bias included: chosen by s + b, weighted by s), and a
    gradient step lowers the loss."""
    params, x = setup
    target = jax.random.normal(jax.random.PRNGKey(2), (T, D))

    def loss(f):
        return lambda p, x_: jnp.mean((f(p, x_) - target) ** 2)

    ours = loss(lambda p, x_: moe_ffn(p, x_, top_k=K, scale=SCALE)[0])
    theirs = loss(reference)
    (l0, g0), (l1, g1) = (jax.value_and_grad(f, argnums=(0, 1))(params, x)
                          for f in (ours, theirs))
    assert float(l0) == pytest.approx(float(l1), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)
    assert float(jnp.abs(g0[0]["wr"]).sum()) > 0      # the router learns
    assert not np.any(np.asarray(g0[0]["br"]))        # its bias does not
    stepped = {k: v - 0.1 * g0[0][k] for k, v in params.items()}
    assert float(ours(stepped, x)) < float(l0)


def test_selection_bias_chooses_but_does_not_weigh(setup):
    params, x = setup
    ids0, w0, _ = moe_lib.route(x, params["wr"], jnp.zeros((E,)), K,
                                SCALE)
    ids1, w1, _ = moe_lib.route(x, params["wr"], params["br"], K, SCALE)
    assert np.any(np.asarray(ids0) != np.asarray(ids1))
    s = jax.nn.sigmoid(jnp.matmul(x, params["wr"], precision="highest"))
    chosen = jnp.take_along_axis(s, ids1, axis=-1)
    np.testing.assert_allclose(
        np.asarray(w1), np.asarray(chosen / chosen.sum(-1, keepdims=True)
                                   * SCALE), rtol=1e-6)
    # top-k of s + b, not of s
    np.testing.assert_array_equal(
        np.sort(np.asarray(ids1), axis=-1),
        np.sort(np.asarray(jax.lax.top_k(s + params["br"], K)[1]), axis=-1))


def _collapsed(params):
    """Every token chooses experts 2 and 3: the worst case T * k rows for
    a layer that holds them."""
    return dict(params, br=jnp.zeros((E,)).at[jnp.array([2, 3])].set(50.))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_no_token_is_dropped_at_any_skew(setup, mode):
    """Every token chooses the same two experts, both held: the worst
    case T * k rows, two buffers' worth. Exact, on both paths."""
    params, x = setup
    held = share(_collapsed(params), 2, 4)
    with common.force_mode(mode), common.record_dispatch() as tally:
        y, rows, turns, _ = moe_ffn(held, x, top_k=K, scale=SCALE,
                                    offset=2)
    assert list(np.asarray(rows)) == [T, T]
    assert mode in tally["moe_grouped_matmul"]
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(reference(held, x, held=2, offset=2)),
        rtol=2e-5, atol=2e-6)
    # the buffer holds twice a uniform router's rows: this took 2 turns
    assert moe_lib._chunk_rows(T, K, E, 2) * 2 == T * K
    assert int(turns) == 2


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("turns,first,spill", [
    (0, None, None), (2, T * K // 2, None), (4, T * K // 4, None),
    (5, T * K // 2, T * K // 8), (4, T * K // 2, 24),
    (1, T * K, None), (3, T * K // 2, T * K // 4)])
def test_gradients_over_any_number_of_chunks(setup, mode, turns, first,
                                             spill, monkeypatch):
    """The collapsed router's gradients, every leaf's and the input's,
    equal the plain reference's where the rows take 2 chunks and, with a
    smaller buffer, 4 (the loop after the first chunk turns once and
    three times), and where no row is held here (0 chunks: the first
    chunk's gradients, computed outside any loop, are zero). With chunks
    after the first smaller than it (``spill`` rows), 4 and 5 chunks:
    one of 64 rows and 64 more in chunks of 16, or of 24, which leave
    the last chunk reaching past the pairs into their padding; and 1
    chunk that holds every row, and 3 (64 rows, then two of 32). In
    ``interpret`` the row kernel sums the rows (``ops/rows.py``)."""
    params, x = setup
    lo = 4 if turns == 0 else 2
    held = share(_collapsed(params), lo, lo + 2)
    if first:
        monkeypatch.setattr(moe_lib, "_chunk_rows", lambda *a: first)
    if spill:
        monkeypatch.setattr(moe_lib, "_SPILL_ROWS", spill)
    target = jax.random.normal(jax.random.PRNGKey(2), (T, D))

    def loss(f):
        return lambda p, x_: jnp.mean((f(p, x_) - target) ** 2)

    with common.force_mode(mode):
        (l0, g0), (l1, g1) = (
            jax.value_and_grad(f, argnums=(0, 1))(held, x)
            for f in (loss(lambda p, x_: moe_ffn(p, x_, top_k=K, scale=SCALE,
                                                 offset=lo)[0]),
                      loss(lambda p, x_: reference(p, x_, held=2,
                                                   offset=lo))))
        got = moe_ffn(held, x, top_k=K, scale=SCALE, offset=lo)
    assert int(got[2]) == turns
    assert int(np.asarray(got[1]).sum()) == (T * K if turns else 0)
    assert float(l0) == pytest.approx(float(l1), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)
    if not turns:
        assert not any(np.any(np.asarray(g0[0][k])) for k in
                       ("wg", "wu", "wd"))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("turns", [1, 2])
def test_bfloat16_gradients_are_the_float32_sum_over_chunks_rounded_once(
        setup, mode, turns):
    """In bfloat16, at one chunk and at two, the rule's gradients are bit
    for bit the formula it replaced: each chunk's gradients (the
    transposed ``_chunk``, in the leaves' own types) added in float32
    from zeros, the sum cast back once."""
    params, x = setup
    held = share(_collapsed(params), 2, 4)
    bf = {k: v.astype(jnp.bfloat16) for k, v in held.items()}
    xb = x.astype(jnp.bfloat16)
    ids, gates, _ = moe_lib.route(xb, held["wr"], held["br"], K, SCALE)
    key, counts = moe_lib._plan(ids, 2, 2, None)
    R = T * K // turns
    dy = jax.random.normal(jax.random.PRNGKey(4), (T, D))
    floats = (xb, bf["wg"], bf["wu"], bf["wd"], gates)

    @jax.jit
    def ours(dy, key, counts, *floats):
        return jax.vjp(lambda *f: moe_lib._routed(R, *f, key, counts),
                       *floats)[1](dy)[:5]

    @jax.jit
    def formula(dy, key, counts, *floats):
        order, slot = moe_lib._sorted_pairs(key, R)
        zero = jnp.zeros(x.shape, jnp.float32)
        sums = [jnp.zeros(f.shape, jnp.float32) for f in floats]
        for c in range(turns):
            _, vjp = jax.vjp(lambda *f: moe_lib._chunk(
                *f, order[c * R:(c + 1) * R], counts, c * R, zero, slot),
                *floats)
            sums = [s + d.astype(jnp.float32)
                    for s, d in zip(sums, vjp(dy))]
        return [s.astype(f.dtype) for s, f in zip(sums, floats)]

    assert int(moe_lib._turns(R, counts)) == turns
    with common.force_mode(mode):
        got, want = (f(dy, key, counts, *floats) for f in (ours, formula))
    for a, b, f in zip(got, want, floats):
        assert a.dtype == b.dtype == f.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def test_shares_add_up():
    """32 experts over 4 shares of 8: the four partial sums, with the
    shared expert counted once, equal the uncut layer of the uncut
    reference; each share equals the reference given the same share."""
    params = init_moe_params(jax.random.PRNGKey(3), D, H, 32)
    params["br"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (32,))
    x = jax.random.normal(jax.random.PRNGKey(5), (T, D))
    whole = reference(params, x)
    shared = moe_lib.swiglu(x, params["sg"], params["su"], params["sd"])
    total = shared
    for i in range(4):
        mine = share(params, 8 * i, 8 * i + 8)
        part, rows, _, _ = moe_ffn(mine, x, top_k=K, scale=SCALE,
                                   offset=8 * i, shared=False)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(reference(
                mine, x, held=8, offset=8 * i, shared=False)),
            rtol=2e-5, atol=2e-6)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=3e-6)


def test_sharded_matches_one_device_layer(setup):
    """``make_moe`` over the model axis of a CPU mesh: partial sums
    added, shared expert once; with and without padding."""
    params, x = setup
    mesh = create_mesh(n_data=2, n_model=4)
    fn = make_moe(mesh, "model", top_k=K, scale=SCALE)
    sharded = shard_moe_params(params, mesh, "model")
    np.testing.assert_allclose(
        np.asarray(fn(sharded, x)),
        np.asarray(moe_ffn(params, x, top_k=K, scale=SCALE)[0]),
        rtol=2e-5, atol=2e-6)
    live = (jnp.arange(T) % 3 != 0).astype(x.dtype)
    np.testing.assert_allclose(
        np.asarray(fn(sharded, x, live)),
        np.asarray(moe_ffn(params, x, top_k=K, scale=SCALE, live=live)[0]),
        rtol=2e-5, atol=2e-6)


def test_sharded_program_has_collective(setup):
    params, x = setup
    mesh = create_mesh(n_data=2, n_model=4)
    fn = make_moe(mesh, "model", top_k=K, scale=SCALE)
    sp = shard_moe_params(params, mesh, "model")
    hlo = jax.jit(lambda p, x_: fn(p, x_)).lower(sp, x).compile().as_text()
    assert "all-reduce" in hlo


def test_masked_tokens_take_no_rows(setup):
    """Padding is routed nowhere: it takes no expert's rows, gets the
    shared expert only, and leaves the live tokens' outputs alone."""
    params, x = setup
    y_ref, rows_ref, _, _ = moe_ffn(params, x[:8], top_k=K, scale=SCALE)
    pad = jax.random.normal(jax.random.PRNGKey(3), (24, D))
    live = jnp.concatenate([jnp.zeros(24), jnp.ones(8)])
    y_pad, rows, _, _ = moe_ffn(params, jnp.concatenate([pad, x[:8]]),
                                top_k=K, scale=SCALE, live=live)
    np.testing.assert_allclose(np.asarray(y_pad[24:]), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_ref))
    assert int(rows.sum()) == 8 * K


def _layer(name="mx", **kw):
    from paddle_tpu.config import dsl
    from paddle_tpu.core.registry import get_layer_impl
    dsl.reset()
    x = dsl.data(name="x", size=D, is_sequence=True)
    dsl.moe(input=x, expert_hidden=H, num_experts=E, top_k=K,
            shared_hidden=H, routed_scaling_factor=SCALE, name=name, **kw)
    cfg = dsl.current_graph().layers[name]
    impl = get_layer_impl("moe")
    infos = [type("I", (), {"size": D, "is_sequence": True})()]
    return cfg, impl, impl.params(cfg, infos)


def test_moe_layer_respects_sequence_mask():
    """The registered ``moe`` layer threads Argument.mask into dispatch:
    growing the pad length leaves live positions' outputs and the rows
    it reports unchanged."""
    from paddle_tpu.core.argument import Argument
    cfg, impl, specs = _layer(experts_held=4, expert_offset=2)
    assert specs["wg"].shape == (4, D, H) and specs["wr"].shape == (D, E)
    assert specs["wr"].compute_f32 and specs["br"].is_static
    key = jax.random.PRNGKey(0)
    params = {k: jax.random.normal(jax.random.fold_in(key, i), s.shape) * 0.1
              for i, (k, s) in enumerate(specs.items())}
    v = jax.random.normal(jax.random.PRNGKey(1), (2, 4, D))
    mask = jnp.asarray([[1, 1, 1, 0], [1, 1, 0, 0]], jnp.float32)
    out_short = impl.apply(cfg, params, [Argument(value=v, mask=mask)], None)
    v_long = jnp.concatenate(
        [v, jax.random.normal(jax.random.PRNGKey(2), (2, 5, D))], axis=1)
    mask_long = jnp.concatenate([mask, jnp.zeros((2, 5))], axis=1)
    out_long = impl.apply(cfg, params, [Argument(value=v_long,
                                                 mask=mask_long)], None)
    np.testing.assert_allclose(np.asarray(out_long.value[:, :4]),
                               np.asarray(out_short.value),
                               rtol=1e-5, atol=1e-5)
    short, long = out_short.state["counters"], out_long.state["counters"]
    assert set(short) == {"moe_rows_max", "moe_rows_mean",
                          "moe_experts_active", "moe_turns"}
    for name in short:
        assert float(long[name]) == float(short[name]), name
    # 5 live tokens choose K of E experts each; 4 of the E are held
    assert 0 < float(short["moe_rows_mean"]) * 4 <= 5 * K
    assert 1 <= float(short["moe_experts_active"]) <= 4


def test_moe_layer_trains_and_shards():
    """``dsl.moe``: the registered layer type trains through SGD, its
    expert weights shard over the model axis via shard_rules, and the
    step hands back the rows of each held expert with the cost."""
    from paddle_tpu.config import dsl
    from paddle_tpu.data import DataFeeder, dense_vector, integer_value
    from paddle_tpu.optim import Momentum
    from paddle_tpu.trainer import SGD

    dsl.reset()
    x = dsl.data(name="x", size=D)
    lab = dsl.data(name="label", size=4)
    m = dsl.moe(input=x, expert_hidden=H, num_experts=E, top_k=K,
                shared_hidden=H, name="mx")
    out = dsl.fc(input=m, size=4, act="softmax", name="out")
    cost = dsl.classification_cost(input=out, label=lab)
    rng = np.random.RandomState(0)
    X = rng.randn(64, D).astype(np.float32)
    Y = rng.randint(0, 4, 64)
    feeder = DataFeeder({"x": dense_vector(D), "label": integer_value(4)})
    mesh = create_mesh(n_data=2, n_model=4)
    tr = SGD(cost=cost, update_equation=Momentum(learning_rate=0.1),
             mesh=mesh, shard_rules={"_mx.wg": P("model"),
                                     "_mx.wu": P("model"),
                                     "_mx.wd": P("model")})
    assert tr.params["_mx.wg"].sharding.spec == P("model")
    before = np.asarray(tr.params["_mx.wg"]).copy()
    tr.train(lambda: iter([[(X[i], int(Y[i])) for i in range(64)]]),
             feeder=feeder, num_passes=3)
    after = np.asarray(tr.params["_mx.wg"])
    assert np.isfinite(after.sum()) and np.any(after != before)
    assert not np.any(np.asarray(tr.params["_mx.br"]))   # static
    assert tr.params["_mx.wg"].sharding.spec == P("model")
    totals = tr.breakdown.totals
    assert totals["moe_rows_mean"] == pytest.approx(3 * 64 * K / E)
    assert totals["moe_rows_max"] >= totals["moe_rows_mean"]
    assert 3 <= totals["moe_experts_active"] <= 3 * E


def test_layer_counters_reach_totals_and_the_armed_steps_span():
    """What a layer puts in ``state["counters"]`` comes back with the
    cost: summed into ``StepBreakdown.totals`` under its own name and,
    with a Tracer armed, attributes of each step's span. The channel
    knows no layer kind: a name nobody registered goes the same way."""
    from paddle_tpu.config import dsl
    from paddle_tpu.data import DataFeeder, dense_vector, integer_value
    from paddle_tpu.obs import trace
    from paddle_tpu.optim import Momentum
    from paddle_tpu.trainer import SGD
    from paddle_tpu.utils.profiler import StepBreakdown

    dsl.reset()
    x = dsl.data(name="x", size=D)
    lab = dsl.data(name="label", size=4)
    m = dsl.moe(input=x, expert_hidden=H, num_experts=E, top_k=K, name="mx")
    out = dsl.fc(input=m, size=4, act="softmax", name="out")
    cost = dsl.classification_cost(input=out, label=lab)
    rng = np.random.RandomState(1)
    X = rng.randn(16, D).astype(np.float32)
    Y = rng.randint(0, 4, 16)
    feeder = DataFeeder({"x": dense_vector(D), "label": integer_value(4)})
    tr = SGD(cost=cost, update_equation=Momentum(learning_rate=0.1))
    tracer = trace.install(trace.Tracer("test"))
    try:
        tr.train(lambda: iter([[(X[i], int(Y[i])) for i in range(16)]] * 2),
                 feeder=feeder, num_passes=1)
    finally:
        trace.install(None)
    steps = [s for s in tracer.spans() if s["name"] == "train.step"]
    assert len(steps) == 2
    for name in ("moe_rows_max", "moe_rows_mean", "moe_experts_active",
                 "moe_turns"):
        assert tr.breakdown.totals[name] == pytest.approx(
            sum(s["attrs"][name] for s in steps))
    assert steps[0]["attrs"]["moe_rows_mean"] == pytest.approx(16 * K / E)
    # 16 tokens' 32 pairs fill one buffer of 32 rows: one turn a step
    assert moe_lib._chunk_rows(16, K, E, E) == 16 * K
    assert tr.breakdown.totals["moe_turns"] == 2.0
    bd = StepBreakdown()
    bd.add_counters({"anything": np.float32(2.5)})
    bd.add_counters({"anything": 1})
    assert bd.totals["anything"] == 3.5


def test_unwritten_rows_of_the_kernel_poison_nothing(setup, monkeypatch):
    """The grouped-product kernel leaves the buffer's rows past the last
    expert's unwritten, forward and in its left operand's gradient, and
    on the chip what lies there may be no number (it was, PR 27: a zero
    cotangent times such a row made every gradient upstream NaN on some
    seeds). With NaN planted there by megablox's two kernels as
    ``grouped_matmul``'s rule calls them (``gmm`` forward and transposed
    for the left operand's gradient, ``tgmm`` for the right's), output
    and gradients are those of the other path."""
    params, x = setup

    def written(rows, sizes):
        return jnp.arange(rows)[:, None] < jnp.sum(sizes)

    def gmm(lhs, rhs, sizes, dtype, tiles, transpose_rhs=False,
            interpret=False):
        out = jax.lax.ragged_dot(
            lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs, sizes)
        return jnp.where(written(lhs.shape[0], sizes), out, jnp.nan)

    def tgmm(lhs_t, rhs, sizes, dtype, tiles, interpret=False):
        ends = jnp.cumsum(sizes)
        row = jnp.arange(rhs.shape[0])[:, None]
        mine = (row >= ends - sizes) & (row < ends)          # [m, G]
        return jnp.stack([
            jnp.where(mine[:, g:g + 1], lhs_t.T, 0).T
            @ jnp.where(mine[:, g:g + 1], rhs, 0)
            for g in range(sizes.shape[0])]).astype(dtype)

    monkeypatch.setattr(moe_lib, "_megablox", lambda: types.SimpleNamespace(
        gmm=gmm, tgmm=tgmm))
    held = share(params, 2, 6)
    target = jax.random.normal(jax.random.PRNGKey(2), (T, D))

    def loss(p, x_):
        y = moe_ffn(p, x_, top_k=K, scale=SCALE, offset=2)[0]
        return jnp.mean((y - target) ** 2)

    want = jax.value_and_grad(loss, argnums=(0, 1))(held, x)
    with common.force_mode("interpret"):
        got = jax.value_and_grad(loss, argnums=(0, 1))(held, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("k,n,sizes", [
    (384, 640, (40, 0, 50, 0)),     # 384 x 640 tiles where 128 x 128 were
    (512, 384, (0, 128, 0, 0)),     # dlhs contracted 384 over a tile of 512
    (256, 1152, (20, 30, 0, 78)),   # every row held, n in tiles of 1152
])
def test_each_call_takes_its_own_tiles_and_the_products_gradients(k, n,
                                                                   sizes):
    """megablox interpreted with each of its three calls at the tiles its
    own shape gives (a width of 128s in the widest tile that divides it,
    where the forward's tiling by (1024, 512, 256, 128) handed its
    transposes a tile past the contraction's end): the product and both
    operands' gradients equal ``lax.ragged_dot``'s, with empty groups and
    with rows past the sum, which come out zero forward and in the left
    operand's gradient."""
    R = 128
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    lhs = jax.random.normal(keys[0], (R, k))
    rhs = jax.random.normal(keys[1], (len(sizes), k, n))
    dout = jax.random.normal(keys[2], (R, n))
    group_sizes = jnp.asarray(sizes, jnp.int32)

    def run(a, b):
        out, vjp = jax.vjp(
            lambda a, b: moe_lib.grouped_matmul(a, b, group_sizes), a, b)
        return (out, *vjp(dout))

    with common.force_mode("interpret"), common.record_dispatch() as tally:
        got = run(lhs, rhs)
    want = run(lhs, rhs)            # off the TPU: lax.ragged_dot
    assert tally["moe_grouped_matmul"] == {"interpret": 1}
    assert set(tally["moe_gmm_tiles"]) == {
        f"fwd {R}x{k}x{n}", f"dlhs {R}x{n}x{k}", f"drhs {R}x{k}x{n}"}
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)
    past = sum(sizes)
    assert not np.any(np.asarray(got[0])[past:])
    assert not np.any(np.asarray(got[1])[past:])


def _rows_grads(ids, kernels, monkeypatch, gmm=None):
    """Output and every gradient of the routed sum of 4 held experts
    (2..5 of E) over float32 inputs, kernels interpreted; the rows moved
    by the row kernels (``kernels``) or by XLA's gather and
    scatter-add."""
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(keys[0], (T, D))
    w = [jax.random.normal(keys[i], shape) * 0.3 for i, shape in
         ((1, (4, D, H)), (2, (4, D, H)), (3, (4, H, D)))]
    gates = jax.random.uniform(keys[4], (T, K))
    target = jax.random.normal(keys[5], (T, D))

    def loss(x, wg, wu, wd, g):
        y = moe_lib.routed_experts(x, wg, wu, wd, ids, g, n_experts=E,
                                   offset=2)[0]
        return jnp.mean((y - target) ** 2)

    with monkeypatch.context() as m:
        m.setattr(moe_lib.row_ops, "kernels", lambda *a: kernels)
        if gmm is not None:
            m.setattr(moe_lib, "grouped_matmul", gmm)
        with common.force_mode("interpret"), \
                common.record_dispatch() as tally:
            out = jax.value_and_grad(loss, argnums=range(5))(x, *w, gates)
    path = "interpret" if kernels else "ref"
    assert tally["moe_gather_rows"] == {path: tally["moe_gather_rows"][
        path]} and set(tally["moe_sum_rows"]) == {path}, tally
    return out


def _ids(held_rows):
    """[T, K] choices: the first ``held_rows`` (token, choice) pairs in
    token order name held experts (2, 4, 5 in turn: expert 3 gets none,
    and a token's two choices are two held experts), the rest experts
    this device does not hold."""
    held = np.array([2, 4, 5])[np.arange(T * K) % 3]
    away = np.array([0, 1, 6, 7])[np.arange(T * K) % 4]
    return jnp.asarray(np.where(np.arange(T * K) < held_rows, held, away)
                       .reshape(T, K), jnp.int32)


@pytest.mark.parametrize("held_rows,first", [
    (T * K, T * K),     # every pair held, one chunk
    (32, None),         # n on a block's end (blocks of 16 buffer rows)
    (37, None),         # n inside a block
    (0, None),          # nothing held: no chunk at all
    (100, 48),          # 48 rows, then chunks of 48: n = 4 in the last
])
def test_row_kernels_equal_xla_gather_and_scatter_add(held_rows, first,
                                                      monkeypatch):
    """``sum_rows`` as the combine and as the gather's transpose
    (interpreted, 16 buffer rows and 16 tokens a grid step, so that
    ``n`` lands on and off a block's end) give the routed sum and every gradient that XLA's gather and
    scatter-add give: an empty held expert, tokens whose two choices are
    both held, one chunk, several, none."""
    monkeypatch.setattr(row_ops, "_WORD_ROWS", 16)
    monkeypatch.setattr(row_ops, "_SUM_SLOTS", 16 * K)
    if first:
        monkeypatch.setattr(moe_lib, "_chunk_rows", lambda *a: first)
    ids = _ids(held_rows)
    (l0, g0), (l1, g1) = (_rows_grads(ids, k, monkeypatch)
                          for k in (True, False))
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    if not held_rows:
        assert not np.any(np.asarray(g0[1]))


def test_rows_past_the_held_ones_poison_nothing_under_the_row_kernels(
        monkeypatch):
    """With NaN in every row of the buffer past the held ones, forward
    and in the left operand's gradient (what an unwritten row of the
    grouped products may hold on the chip), the row kernels read none of
    them: the routed sum and every gradient are finite and those of the
    XLA path with the products as they are."""
    def nan_gmm(lhs, rhs, sizes):
        rows = jnp.arange(lhs.shape[0])[:, None]

        @jax.custom_vjp
        def f(lhs, rhs):
            return jnp.where(rows < jnp.sum(sizes),
                             jax.lax.ragged_dot(lhs, rhs, sizes), jnp.nan)

        def f_bwd(saved, g):
            lhs, rhs = saved
            g = jnp.where(rows < jnp.sum(sizes), g, 0)
            dlhs = jax.lax.ragged_dot(g, rhs.swapaxes(1, 2), sizes)
            _, vjp = jax.vjp(lambda r: jax.lax.ragged_dot(lhs, r, sizes),
                             rhs)
            return (jnp.where(rows < jnp.sum(sizes), dlhs, jnp.nan),
                    vjp(jnp.where(rows < jnp.sum(sizes), g, 0))[0])

        f.defvjp(lambda lhs, rhs: (f(lhs, rhs), (jnp.where(
            rows < jnp.sum(sizes), lhs, 0), rhs)), f_bwd)
        return f(lhs, rhs)

    monkeypatch.setattr(row_ops, "_WORD_ROWS", 16)
    ids = _ids(37)
    l0, g0 = _rows_grads(ids, True, monkeypatch, gmm=nan_gmm)
    l1, g1 = _rows_grads(ids, False, monkeypatch)
    assert np.isfinite(float(l0))
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(g0, g1):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_experts,path", [(64, "interpret"),
                                            (256, "ref")])
def test_the_row_kernel_takes_a_layer_by_its_slots_a_buffer_row(n_experts,
                                                                 path):
    """The row kernel moves a layer's rows where its first buffer has a
    row for at most ``_SLOTS_A_ROW`` (token, choice) slots: 4 slots a row
    at 8 held experts of 64 (as the Mellum2 and LFM2 cells), where it is
    the faster on the chip; 16 at 8 of 256 (as JoyAI and Laguna), where
    XLA's scatter-add is."""
    tokens, k, d, h = 64, 8, 128, 32
    R = moe_lib._chunk_rows(tokens, k, n_experts, 8)
    assert (tokens * k <= row_ops._SLOTS_A_ROW * R) == (path != "ref")
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    w = [jax.random.normal(keys[i], shape) for i, shape in
         ((1, (8, d, h)), (2, (8, d, h)), (3, (8, h, d)))]
    ids = jax.random.randint(keys[4], (tokens, k), 0, n_experts)
    gates = jnp.full((tokens, k), 1.0 / k)

    def loss(x, *w):
        return jnp.sum(moe_lib.routed_experts(
            x, *w, ids, gates, n_experts=n_experts, offset=8)[0])

    with common.force_mode("interpret"), \
            common.record_dispatch() as tally:
        jax.eval_shape(jax.grad(loss, argnums=range(4)), x, *w)
    assert set(tally["moe_gather_rows"]) == {path}, tally
    assert set(tally["moe_sum_rows"]) == {path}, tally
