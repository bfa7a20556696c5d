"""``models.ouro`` (one stack of layers run several times a step over
one copy of its weights, four sandwich norms a layer, an exit gate and a
loss weighted over the passes) against the plain reference
(``benchmark/reference/ouro_2_6b_pp6.py``) at a small size on the CPU,
seeded weights; and the mechanism under it, a layer that uses another
layer's parameters (``LayerDef.params_of``)."""

import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import plain
from paddle_tpu import models
from paddle_tpu.config import dsl
from paddle_tpu.core.argument import Argument
from paddle_tpu.core.network import Network
from paddle_tpu.trainer.trainer import Topology

ref = importlib.import_module("benchmark.reference.ouro_2_6b_pp6")
counts = importlib.import_module("benchmark.counts.ouro_2_6b_pp6")

L, R = 2, 3
ARGS = dict(vocab_size=96, hidden_size=64, intermediate_size=80,
            num_hidden_layers=L, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, total_ut_steps=R,
            rope_theta=1e6, rms_norm_eps=1e-6, entropy_weight=0.1)
CFG = {"model": {"args": ARGS},
       "optimizer": {"kind": "adam", "args": {
           "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
           "epsilon": 1e-8}}}
B, S = 2, 32
IDS = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, ARGS["vocab_size"])


def weights(seed=3, args=ARGS):
    """The reference's leaves from a seed, with the norm scales and the
    gate's bias moved off 1 and 0 so that they matter."""
    leaves = ref.leaves({"model": {"args": args}})
    w = plain.make_weights(leaves, seed)
    key = jax.random.PRNGKey(1)
    return leaves, {
        n: (v + 0.1 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
            if leaves[n][1] in ("ones", "zeros") else v)
        for i, (n, v) in enumerate(sorted(w.items()))}


def graph(**more):
    dsl.reset()
    cost, _out, names = models.ouro(**{**ARGS, **more}, loss_chunk=8,
                                    attention_block=16)
    assert names == ["words"]
    return cost


def network(**more):
    return Topology(graph(**more)).network


def feed(ids=IDS):
    return {"words": Argument(value=ids,
                              mask=jnp.ones(ids.shape, jnp.float32))}


def cost_of(net, w):
    def program(p):
        out = net.apply({**w, **p}, feed(), train=True)
        return jnp.mean(out["out_head"].value)
    return program


def reference(w, cfg=CFG):
    def f(p):
        return ref.loss({**w, **p}, {"words": IDS}, cfg, plain.Arith())
    return f


def close(got, want, tol, name=""):
    a, b = np.asarray(got), np.asarray(want)
    assert np.abs(a - b).max() <= tol * np.abs(b).max() + 1e-9, name


# ------------------------------------------------- (e) one leaf a weight
def test_the_table_has_one_leaf_a_shared_weight():
    leaves, _ = weights()
    net = network()
    assert set(net.param_specs) == set(leaves)
    for name, (shape, _kind) in leaves.items():
        assert tuple(net.param_specs[name].shape) == tuple(shape), name
    # R x L blocks in the graph, L blocks' weights in the table
    blocks = [n for n in net.order if n.endswith("_attn")]
    assert len(blocks) == R * L
    assert sorted(n for n in net.param_specs if n.endswith("_attn.wq")) \
        == [f"_ut0_blk{i}_attn.wq" for i in range(L)]
    for t in range(R):
        assert net._layer_params[f"ut{t}_blk1_mlp"]["wd"] \
            == "_ut0_blk1_mlp.wd"
        assert net._layer_params[f"ut{t}_blk0_n3"]["w0"] \
            == "_ut0_blk0_n3.w0"
        assert net._layer_params[f"ut{t}_out_norm"]["w0"] \
            == "_ut0_out_norm.w0"
    size = sum(math.prod(s.shape) for s in net.param_specs.values())
    assert size == counts.param_count(CFG) == sum(
        math.prod(shape) for shape, _ in leaves.values())
    # the gate stays float32 under a lower compute dtype
    assert {n for n, s in net.param_specs.items() if s.compute_f32} \
        == {"_out_head.wgate", "_out_head.bgate"}
    # init_params and param_meta see the one leaf
    init = net.init_params(jax.random.PRNGKey(0))
    assert set(init) == set(net.param_meta()) == set(leaves)


def test_the_full_size_table_is_436m_not_1_67b():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ouro_2_6b_pp6.json")) as f:
        cfg = json.load(f)
    dsl.reset()
    cost, _, _ = models.ouro(**cfg["model"]["args"])
    net = Topology(cost).network
    size = sum(math.prod(s.shape) for s in net.param_specs.values())
    assert size == counts.param_count(cfg) == 436_277_249
    assert size == 8 * 51_388_416 + 2 * 6144 * 2048 + 2048 + 2049
    assert len([n for n in net.order if n.endswith("_attn")]) == 32


def test_a_checkpoint_holds_a_shared_weight_once(tmp_path):
    from paddle_tpu.trainer.checkpoint import load_params, save_params
    leaves, w = weights()
    path = str(tmp_path / "ouro.npz")
    save_params(path, w)
    got, _ = load_params(path)
    assert set(got) == set(leaves)
    with np.load(path) as z:
        stored = [k for k in z.files if "blk0_attn.wq" in k]
    assert len(stored) == 1
    np.testing.assert_array_equal(np.asarray(got["_ut0_blk0_attn.wq"]),
                                  np.asarray(w["_ut0_blk0_attn.wq"]))
    # and the program computes the same from what was loaded
    net = network()
    with jax.default_matmul_precision("highest"):
        a = cost_of(net, w)({})
        b = cost_of(net, {n: jnp.asarray(v) for n, v in got.items()})({})
    assert float(a) == float(b)


# --------------------------------------- (a) program against reference
@pytest.mark.parametrize("recompute", [True, False])
def test_loss_and_every_leafs_gradient(recompute):
    leaves, w = weights()
    net = network(recompute=recompute)
    trained = plain.trained(leaves)
    assert set(trained) == set(leaves)      # the gate's bias trains too
    p0 = {n: w[n] for n in trained}
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(cost_of(net, w))(p0)
        want, g_want = jax.value_and_grad(reference(w))(p0)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for n in trained:
        close(g_got[n], g_want[n], 1e-5, n)


def test_the_pallas_kernels_interpreted_give_the_same():
    from paddle_tpu.ops import common
    leaves, w = weights()
    net = network()
    p0 = {n: w[n] for n in plain.trained(leaves)}
    with jax.default_matmul_precision("highest"), \
            common.force_mode("interpret"), \
            common.record_dispatch() as tally:
        got, g_got = jax.value_and_grad(cost_of(net, w))(p0)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(reference(w))(p0)
    assert tally["flash_attention"] == {"interpret": R * L}
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for n in p0:
        close(g_got[n], g_want[n], 3e-5, n)


def _train(w, steps, compute_dtype=None):
    from paddle_tpu.data import DataFeeder, integer_value_sequence
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGD, events
    a = CFG["optimizer"]["args"]
    tr = SGD(cost=graph(),
             parameters={n: jnp.copy(v) for n, v in w.items()},
             update_equation=Adam(**a), compute_dtype=compute_dtype)
    feeder = DataFeeder({"words": integer_value_sequence(96)},
                        pad_multiple=S)
    rows = [(list(map(int, r)),) for r in np.asarray(IDS)]
    costs, first_moment = [], {}

    def handler(e):
        if not isinstance(e, events.EndIteration):
            return
        costs.append(e.cost)
        if len(costs) == 1:     # Adam's first moment after one step
            first_moment.update({n: np.asarray(slots["mom"]) for n, slots
                                 in tr.opt_state["slots"].items()})

    tr.train(lambda: iter([rows] * steps), feeder=feeder, num_passes=1,
             event_handler=handler)
    return tr, costs, first_moment


def _reference_steps(w, steps):
    from benchmark import check
    hp = check.hyper(CFG)
    params, state, losses = dict(w), plain.adam_init(w), []
    with jax.default_matmul_precision("highest"):
        for t in range(1, steps + 1):
            loss, grads = jax.value_and_grad(reference(w))(params)
            losses.append(float(loss))
            params, state = plain.adam_step(params, grads, state, hp, t)
    return params, losses


def test_three_adam_steps_through_sgd_in_float32():
    """Parameters after three steps of ``SGD.train`` against the
    reference's three, in float32 at ``highest``. The measure is the
    change ``P3 - P0`` leaf by leaf: Adam's first steps move every
    element by about the learning rate whatever its gradient, so an
    element whose gradient is round-off flips with it; the limit is 3e-5
    of the leaf's change in norm (observed at most 7.9e-6), and 1e-5 on
    every loss (observed 6e-8)."""
    _, w = weights()
    with jax.default_matmul_precision("highest"):
        tr, costs, _ = _train(w, 3)
    want, losses = _reference_steps(w, 3)
    assert costs == pytest.approx(losses, rel=1e-5)
    for n in w:
        moved = np.asarray(want[n]) - np.asarray(w[n])
        got = np.asarray(tr.params[n]) - np.asarray(w[n])
        assert np.linalg.norm(moved) > 0, n
        assert np.linalg.norm(got - moved) <= 3e-5 * np.linalg.norm(moved), n


def test_three_adam_steps_in_the_configurations_bfloat16():
    """``compute_dtype="bfloat16"`` as the configuration states it
    (float32 masters, the gate float32). Limits, with their reason: a
    bfloat16 product carries 8 bits, a relative error of 2^-9 = 2e-3 a
    rounding, through R x L = 6 block applications of about ten roundings
    each, so the loss agrees to 2e-2 (observed 2e-4) and a leaf's first
    gradient, read back from Adam's first moment, to a tenth of its norm
    (observed at most 4.8e-2); under Adam the change of the parameters
    after three steps is about the learning rate an element whatever the
    gradient's size, so its norm agrees far closer than its direction:
    5e-2 of the reference's (observed 7e-3)."""
    _, w = weights()
    tr, costs, first_moment = _train(w, 3, compute_dtype="bfloat16")
    cast = tr._cast_params(tr.params)
    assert cast["_out_head.wgate"].dtype == jnp.float32
    assert cast["_ut0_blk0_attn.wq"].dtype == jnp.bfloat16
    want, losses = _reference_steps(w, 3)
    assert costs == pytest.approx(losses, rel=2e-2)
    with jax.default_matmul_precision("highest"):
        g_want = jax.grad(reference(w))(dict(w))
    for n in w:
        g = first_moment[n] / (1 - 0.9)
        assert np.linalg.norm(g - np.asarray(g_want[n])) \
            <= 0.1 * np.linalg.norm(np.asarray(g_want[n])), n
        moved = np.linalg.norm(np.asarray(want[n]) - np.asarray(w[n]))
        got = np.linalg.norm(np.asarray(tr.params[n]) - np.asarray(w[n]))
        assert abs(got - moved) <= 5e-2 * moved, n


# ------------------------- (b) the loop is weight sharing, nothing else
def test_the_loop_equals_an_unrolled_network_with_copied_weights():
    """The same graph with every ``params_of`` taken off is a network of
    R x L layers, each with its own weights. Given copies of the shared
    leaves it computes the same cost, and a shared leaf's gradient is
    the sum of its R copies' gradients."""
    leaves, w = weights()
    looped = network()
    cost = graph()
    for layer in dsl.current_graph().layers.values():
        layer.params_of = None
    unrolled = Topology(cost).network
    assert len(unrolled.param_specs) == len(leaves) \
        + (R - 1) * (len(leaves) - 4)       # all but embed, head, gate x 2
    copies = {}
    for name in unrolled.param_specs:
        if name.startswith("_ut"):
            t, rest = name[3:].split("_", 1)
            copies[name] = w[f"_ut0_{rest}"]
        else:
            copies[name] = w[name]

    def unrolled_cost(p):
        out = unrolled.apply(p, feed(), train=True)
        return jnp.mean(out["out_head"].value)

    with jax.default_matmul_precision("highest"):
        a, g_loop = jax.value_and_grad(cost_of(looped, {}))(dict(w))
        b, g_copy = jax.value_and_grad(unrolled_cost)(copies)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for name in w:
        if not name.startswith("_ut0_"):
            close(g_loop[name], g_copy[name], 1e-5, name)
            continue
        rest = name[len("_ut0_"):]
        total = sum(g_copy[f"_ut{t}_{rest}"] for t in range(R))
        close(g_loop[name], total, 1e-5, name)
        # and every pass's copy takes a gradient of its own
        for t in range(R):
            assert float(jnp.abs(g_copy[f"_ut{t}_{rest}"]).max()) > 0


# ------------------------------------------ (c) one pass is ``lm_cost``
def test_one_pass_without_the_entropy_term_is_lm_cost():
    from paddle_tpu.core.registry import get_layer_impl
    from paddle_tpu.config.model_config import Input, LayerDef
    args = dict(ARGS, total_ut_steps=1)
    _, w = weights(args=args)
    net = network(total_ut_steps=1, entropy_weight=0.0)
    assert set(net.param_specs) == set(w)
    with jax.default_matmul_precision("highest"):
        out = net.apply(w, feed(), train=True)
        plain_cost = get_layer_impl("lm_cost").apply(
            LayerDef(name="c", type="lm_cost",
                     inputs=[Input("ut0_out_norm"), Input("words")],
                     attrs={"vocab_size": 96, "shift": 1, "chunk": 8}),
            {"w0": w["_out_head.w0"]},
            [out["ut0_out_norm"], feed()["words"]], None)
    np.testing.assert_allclose(np.asarray(out["out_head"].value),
                               np.asarray(plain_cost.value), rtol=1e-6)
    counters = out["out_head"].state["counters"]
    assert float(counters["loop_exit_step_mean"]) == 1.0      # p_1 = 1
    assert float(counters["loop_exit_entropy"]) == 0.0
    # whatever the entropy's weight: H(p) = 0 where there is one pass
    with jax.default_matmul_precision("highest"):
        again = network(total_ut_steps=1).apply(w, feed(), train=True)
    np.testing.assert_allclose(np.asarray(again["out_head"].value),
                               np.asarray(plain_cost.value), rtol=1e-6)


# ------------------------------------- (d) the exit distribution, counters
@pytest.mark.parametrize("passes", [1, 2, 4, 7])
def test_the_exit_distribution_sums_to_one_at_every_position(passes):
    from paddle_tpu.layers.lm import exit_distribution
    gates = 6.0 * jax.random.normal(jax.random.PRNGKey(passes),
                                    (passes, 3, 50))
    p, log_p = exit_distribution(gates)
    assert p.shape == (passes, 3, 50)
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(jnp.exp(log_p)), np.asarray(p))
    assert float(p.min()) >= 0.0
    # the equations' own spelling, a running product of what has not left
    lams = [jax.nn.sigmoid(g).reshape(-1) for g in gates[:-1]]
    want = ref.exit_distribution(lams, 150).reshape(p.shape)
    # (1 - sigmoid(g) cancels where the logarithms do not: absolute)
    np.testing.assert_allclose(np.asarray(p), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    step = jnp.einsum("r,rbt->bt", jnp.arange(1.0, passes + 1), p)
    assert float(step.min()) >= 1.0 and float(step.max()) <= passes + 1e-5
    entropy = -jnp.sum(p * log_p, axis=0)
    assert float(entropy.min()) >= 0.0
    assert float(entropy.max()) <= math.log(passes) + 1e-5


def test_a_gate_that_never_lets_go_or_always_does_pins_the_counter():
    from paddle_tpu.layers.lm import exit_distribution
    for g, first in ((40.0, 1.0), (-40.0, 0.0)):
        p, _ = exit_distribution(jnp.full((4, 2, 5), g))
        np.testing.assert_allclose(np.asarray(p[0]), first, atol=1e-6)
        np.testing.assert_allclose(np.asarray(p[-1]), 1.0 - first,
                                   atol=1e-6)
        assert not np.any(np.isnan(np.asarray(p)))


def test_the_counters_reach_the_window_through_sgd_train():
    _, w = weights()
    tr, _costs, _ = _train(w, 2)
    totals = tr.breakdown.totals
    assert tr.breakdown.steps == 2
    mean = totals["loop_exit_step_mean"] / 2
    assert 1.0 < mean < R
    assert 0.0 < totals["loop_exit_entropy"] / 2 < math.log(R)
    # what the layer itself counts on the first batch
    with jax.default_matmul_precision("highest"):
        out = network().apply(w, feed(), train=True)
    ce, p = jax.vmap(lambda ids: ref.row_parts(
        w, ids, ref._HashableDict(ARGS), plain.Arith()))(IDS)
    want = jnp.mean(jnp.einsum("r,brt->bt", jnp.arange(1.0, R + 1), p))
    assert float(out["out_head"].state["counters"]["loop_exit_step_mean"]) \
        == pytest.approx(float(want), rel=1e-5)


# ---------------------------------------------------- (f) what is refused
def _two(kind, first, second):
    dsl.reset()
    x = dsl.data(name="x", size=32, is_sequence=True)
    a = kind(x, name="a", **first)
    b = kind(a, name="b", params_of=a, **second)
    return lambda: Network(dsl.current_graph(), outputs=[b.name])


@pytest.mark.parametrize("kind,first,second", [
    ("swiglu", {"hidden": 48}, {"hidden": 64}),
    ("gqa_attention",
     {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "gate": False},
     {"num_heads": 4, "num_kv_heads": 4, "head_dim": 8, "gate": False}),
])
def test_a_shape_mismatch_with_the_named_layer_raises(kind, first, second):
    build = _two(getattr(dsl, kind), first, second)
    with pytest.raises(ValueError, match="shape mismatch"):
        build()
    # the same sizes share: one leaf a suffix
    net = _two(getattr(dsl, kind), first, first)()
    assert all(n.startswith("_a.") for n in net.param_specs)
    assert net._layer_params["b"] == net._layer_params["a"]


def test_a_norm_of_another_width_raises_through_the_same_resolution():
    """``rms_norm``'s scale is a ``w<i>`` suffix: it goes through the
    same resolution as every other, and an explicit ``ParamAttr(name=)``
    still wins over ``params_of``."""
    from paddle_tpu.config.model_config import ParamAttr
    dsl.reset()
    x = dsl.data(name="x", size=32, is_sequence=True)
    y = dsl.data(name="y", size=48, is_sequence=True)
    a = dsl.rms_norm(x, name="a")
    b = dsl.rms_norm(y, name="b", params_of="a")
    with pytest.raises(ValueError, match="shape mismatch"):
        Network(dsl.current_graph(), outputs=[a.name, b.name])
    dsl.reset()
    x = dsl.data(name="x", size=32, is_sequence=True)
    a = dsl.rms_norm(x, name="a")
    b = dsl.rms_norm(a, name="b", params_of=a)
    c = dsl.rms_norm(b, name="c", params_of=a,
                     param_attr=ParamAttr(name="_own.scale"))
    net = Network(dsl.current_graph(), outputs=[c.name])
    assert set(net.param_specs) == {"_a.w0", "_own.scale"}


def test_a_suffix_the_named_layer_lacks_raises():
    dsl.reset()
    x = dsl.data(name="x", size=32, is_sequence=True)
    dims = {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8}
    a = dsl.gqa_attention(x, name="a", gate=False, **dims)
    b = dsl.gqa_attention(a, name="b", gate=True, params_of=a, **dims)
    with pytest.raises(ValueError, match="has no"):
        Network(dsl.current_graph(), outputs=[b.name])
