"""``models.mellum2`` (Laguna's blocks with a softmax router over every
layer's experts, trained against a load-balancing term, q/k norms at a
head of 128, no gate, no shared expert) and its new parts against the
plain reference (``benchmark/reference/mellum2_12b_a2b5_ep8.py``) at a
small size on the CPU, seeded weights."""

import importlib
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import plain
from paddle_tpu import models
from paddle_tpu.config import dsl
from paddle_tpu.core.argument import Argument
from paddle_tpu.ops import common
from paddle_tpu.parallel import moe as moe_lib
from paddle_tpu.trainer.trainer import Topology

ref = importlib.import_module("benchmark.reference.mellum2_12b_a2b5_ep8")
counts = importlib.import_module("benchmark.counts.mellum2_12b_a2b5_ep8")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SLIDING, FULL = "sliding_attention", "full_attention"
ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
ARGS = dict(
    vocab_size=96, hidden_size=64, layer_types=[SLIDING] * 3 + [FULL],
    mlp_layer_types=["sparse"] * 4, num_attention_heads=8,
    num_key_value_heads=1, head_dim=16, sliding_window=8,
    rope_parameters=ROPE, num_experts=8, experts_held=4, expert_offset=2,
    num_experts_per_tok=4, moe_intermediate_size=24,
    router_aux_loss_coef=0.001, rms_norm_eps=1e-6)
CFG = {"model": {"args": ARGS},
       "optimizer": {"kind": "adam", "args": {
           "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
           "epsilon": 1e-8}}}
B, S = 2, 32
IDS = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, ARGS["vocab_size"])


def weights(seed=3):
    """The reference's leaves from a seed, with the norm scales (the q/k
    norms' among them) moved off 1 so that they matter."""
    leaves = ref.leaves(CFG)
    w = plain.make_weights(leaves, seed)
    key = jax.random.PRNGKey(1)
    return leaves, {
        n: (v + 0.1 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
            if leaves[n][1] == "ones" else v)
        for i, (n, v) in enumerate(sorted(w.items()))}


def graph(**more):
    dsl.reset()
    cost, _out, names = models.mellum2(**{**ARGS, **more}, loss_chunk=8,
                                       attention_block=8)
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
        return jnp.mean(out["cost"].value)
    return program


def reference(w):
    def f(p):
        return ref.loss({**w, **p}, {"words": IDS}, CFG, plain.Arith())
    return f


def close(got, want, tol, name=""):
    a, b = np.asarray(got), np.asarray(want)
    assert np.abs(a - b).max() <= tol * np.abs(b).max() + 1e-9, name


# --------------------------------------- (a) program against reference
def test_leaves_are_the_programs_parameters():
    leaves, _ = weights()
    net = network()
    assert set(net.param_specs) == set(leaves)
    for name, (shape, kind) in leaves.items():
        spec = net.param_specs[name]
        assert tuple(spec.shape) == tuple(shape), name
        assert spec.is_static == (kind == "static"), name
    # three sliding layers, then the full one; q/k scales of a head
    assert [n for n in net.param_specs if n.endswith(".wq")] == [
        "_blk0_swa.wq", "_blk1_swa.wq", "_blk2_swa.wq", "_blk3_attn.wq"]
    assert tuple(net.param_specs["_blk3_attn.wk"].shape) == (64, 16)
    assert tuple(net.param_specs["_blk0_swa.gq"].shape) == (16,)
    assert tuple(net.param_specs["_blk0_moe.wr"].shape) == (64, 8)
    assert tuple(net.param_specs["_blk0_moe.wg"].shape) == (4, 64, 24)
    # no gate, no shared expert, no dense layer
    assert not any(n.endswith(".wg") and ("_swa" in n or "_attn" in n)
                   for n in leaves)
    assert not any(n.endswith((".sg", ".su", ".sd")) or "_mlp" in n
                   for n in leaves)
    routers = {n for n, s in net.param_specs.items() if s.compute_f32}
    assert routers == {f"_blk{i}_moe.{s}" for i in range(4)
                       for s in ("wr", "br")}
    # the layer names the benchmark's trace reduction and metrics read
    layers = dsl.current_graph().layers
    assert {"blk0_swa", "blk3_attn", "blk2_moe", "blk1_a_norm",
            "blk1_f_norm", "out_norm", "out_head", "moe_balance"} \
        <= set(layers)
    assert layers["moe_balance"].input_names() == [
        f"blk{i}_moe" for i in range(4)]


@pytest.mark.parametrize("kernels,recompute", [
    ("ref", True), ("ref", False), ("interpret", True)])
def test_loss_and_every_leafs_gradient(kernels, recompute):
    """Float32 at ``highest``, the loss with its balancing term and every
    leaf's gradient to 1e-5 of the leaf's largest gradient element; with
    and without ``recompute``, on the reference path and with the Pallas
    kernels interpreted (a head of 16 in tiles of 8, a window of one
    tile)."""
    leaves, w = weights()
    net = network(recompute=recompute)
    p0 = {n: w[n] for n in plain.trained(leaves)}
    with jax.default_matmul_precision("highest"), \
            common.force_mode(kernels), common.record_dispatch() as tally:
        got, g_got = jax.value_and_grad(cost_of(net, w))(p0)
        want, g_want = jax.value_and_grad(reference(w))(p0)
    assert set(tally["flash_attention"]) == {kernels}
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for n in p0:
        close(g_got[n], g_want[n], 1e-5, n)


def _train(w, steps, compute_dtype=None):
    from paddle_tpu.data import DataFeeder, integer_value_sequence
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGD, events
    tr = SGD(cost=graph(),
             parameters={n: jnp.copy(v) for n, v in w.items()},
             update_equation=Adam(**CFG["optimizer"]["args"]),
             compute_dtype=compute_dtype)
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


def _reference_steps(leaves, w, steps):
    from benchmark import check
    hp = check.hyper(CFG)
    params = {n: w[n] for n in plain.trained(leaves)}
    state, losses = plain.adam_init(params), []
    with jax.default_matmul_precision("highest"):
        for t in range(1, steps + 1):
            loss, grads = jax.value_and_grad(reference(w))(params)
            losses.append(float(loss))
            params, state = plain.adam_step(params, grads, state, hp, t)
    return params, losses


def test_three_adam_steps_through_sgd_in_float32():
    """Parameters after three steps of ``SGD.train`` against the
    reference's three, in float32 at ``highest``: every loss to 1e-5. The
    parameters by their change ``P3 - P0``, leaf by leaf, to 1e-4 of the
    leaf's change in norm (Adam's first steps move every element by about
    the learning rate whatever its gradient, so an element whose gradient
    is round-off flips with it). The static bias does not move. The
    step's counter ``moe_balance`` is the term over ``k``, near 1 at a
    fresh router."""
    leaves, w = weights()
    with jax.default_matmul_precision("highest"):
        tr, costs, _ = _train(w, 3)
    want, losses = _reference_steps(leaves, w, 3)
    assert costs == pytest.approx(losses, rel=1e-5)
    for n in want:
        moved = np.asarray(want[n]) - np.asarray(w[n])
        got = np.asarray(tr.params[n]) - np.asarray(w[n])
        assert np.linalg.norm(moved) > 0, n
        assert np.linalg.norm(got - moved) <= 1e-4 * np.linalg.norm(moved), n
    np.testing.assert_array_equal(np.asarray(tr.params["_blk2_moe.br"]),
                                  np.asarray(w["_blk2_moe.br"]))
    assert 1.0 < tr.breakdown.totals["moe_balance"] / 3 < 2.0


def test_three_adam_steps_in_the_configurations_bfloat16():
    """``compute_dtype="bfloat16"`` as the configuration states it
    (float32 masters, routers float32). Limits, with their reason: a
    bfloat16 rounding is a relative error of 2^-9 = 2e-3, through four
    blocks of about ten roundings each, so the loss agrees to 2e-2 and a
    leaf's first gradient, read back from Adam's first moment, to a
    quarter of its norm (an expert's weight is the worst: its gradient
    comes from the few rows routed to it, and a token whose fourth and
    fifth probabilities lie within a rounding of each other changes
    expert); under Adam the change of the parameters after three steps
    is about the learning rate an element whatever the gradient's size,
    so its norm agrees far closer than its direction: 5e-2."""
    leaves, w = weights()
    tr, costs, first_moment = _train(w, 3, compute_dtype="bfloat16")
    cast = tr._cast_params(tr.params)
    assert cast["_blk1_moe.wr"].dtype == jnp.float32
    assert cast["_blk0_swa.gq"].dtype == jnp.bfloat16
    want, losses = _reference_steps(leaves, w, 3)
    assert costs == pytest.approx(losses, rel=2e-2)
    with jax.default_matmul_precision("highest"):
        g_want = jax.grad(reference(w))({n: w[n] for n in want})
    for n in want:
        g = first_moment[n] / (1 - 0.9)
        assert np.linalg.norm(g - np.asarray(g_want[n])) \
            <= 0.25 * np.linalg.norm(np.asarray(g_want[n])), n
        moved = np.linalg.norm(np.asarray(want[n]) - np.asarray(w[n]))
        got = np.linalg.norm(np.asarray(tr.params[n]) - np.asarray(w[n]))
        assert abs(got - moved) <= 5e-2 * moved, n


# ------------------------------------------- (b) the softmax router
def _router(seed=0, tokens=40, d=16, e=8):
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, d))
    wr = jax.random.normal(jax.random.PRNGKey(seed + 1), (d, e)) * d ** -0.5
    br = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 2), (e,))
    return x, wr, br


def test_softmax_route_is_an_explicit_softmax_top_k_and_normalise():
    x, wr, br = _router()
    ids, w, p = moe_lib.route(x, wr, br, 3, 1.0, score="softmax")
    logits = np.asarray(x, np.float64) @ np.asarray(wr, np.float64)
    want_p = np.exp(logits - logits.max(-1, keepdims=True))
    want_p /= want_p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(p), want_p, rtol=1e-5, atol=1e-7)
    want_ids = np.argsort(-want_p, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    chosen = np.take_along_axis(want_p, want_ids, axis=-1)
    # no bias in the choice, no scale and no eps in the weights
    np.testing.assert_allclose(np.asarray(w),
                               chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    # the weights' gradient reaches the chosen logits alone (the router's
    # weight is the logits where its input is the identity)
    u = jnp.asarray(logits, jnp.float32)
    g = jax.grad(lambda u: jnp.sum(moe_lib.route(
        jnp.eye(u.shape[0]), u, br, 3, 1.0, score="softmax")[1]
        * jnp.arange(1.0, 4.0)))(u)
    others = np.ones(u.shape, bool)
    np.put_along_axis(others, np.asarray(ids), False, axis=-1)
    assert np.abs(np.asarray(g)[others]).max() < 1e-6


@pytest.mark.parametrize("score,scale,eps", [("sparsemax", 1.0, 0.0),
                                             ("softmax", 2.5, 0.0),
                                             ("softmax", 1.0, 1e-6)])
def test_route_refuses_what_it_would_drop(score, scale, eps):
    """An unknown score, and a scale or an eps on a softmax router, whose
    weights take neither, are errors and not silently dropped."""
    x, wr, br = _router()
    with pytest.raises(ValueError):
        moe_lib.route(x, wr, br, 3, scale, eps, score=score)


def test_the_sigmoid_default_is_todays_route_bit_for_bit():
    """The default path is the sigmoid route as it was: the same
    operations (no exponential, no maximum: nothing of the softmax) and
    the same bits as the formula written out."""
    x, wr, br = _router(3)
    ids, w, s = moe_lib.route(x, wr, br, 3, 2.5)
    want_s = jax.nn.sigmoid(jnp.matmul(x, wr, precision="highest"))
    _, want_ids = lax.top_k(want_s + br, 3)
    chosen = jnp.take_along_axis(want_s, want_ids, axis=-1)
    want_w = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * 2.5
    np.testing.assert_array_equal(np.asarray(s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w))
    text = str(jax.make_jaxpr(
        lambda a: moe_lib.route(a, wr, br, 3, 2.5)[:2])(x))
    assert text == str(jax.make_jaxpr(
        lambda a: moe_lib.route(a, wr, br, 3, 2.5, score="sigmoid")[:2])(x))
    assert " exp" not in text and "reduce_max" not in text


# ----------------------------------------- (c) the balancing term
def _term(ids, p, live=None):
    """The cost layer's term from one layer's sums, as the model forms
    it (over every layer; here one)."""
    dsl.reset()
    x = dsl.data(name="x", size=p.shape[-1], is_sequence=True)
    cost = dsl.moe_balance_cost([x], coeff=1.0, name="moe_balance")
    impl = __import__("paddle_tpu.core.registry", fromlist=["x"]) \
        .get_layer_impl("moe_balance_cost")
    arg = Argument(value=jnp.zeros((1, 1, 1)),
                   state={"balance": moe_lib.balance_sums(ids, p, live)})
    out = impl.apply(dsl.current_graph().layers[cost.name], {}, [arg], None)
    return out.value[0, 0], out.state["counters"]["moe_balance"]


def test_the_term_against_a_hand_count():
    # 4 tokens, 4 experts, 2 a token
    p = jnp.asarray([[0.4, 0.3, 0.2, 0.1],
                     [0.1, 0.5, 0.3, 0.1],
                     [0.25, 0.25, 0.25, 0.25],
                     [0.7, 0.1, 0.1, 0.1]], jnp.float32)
    ids = jnp.asarray([[0, 1], [1, 2], [0, 1], [0, 1]], jnp.int32)
    # slots 3, 4, 1, 0 over N = 4: c = .75, 1, .25, 0; P = the column
    # means 1.45 / 4, 1.15 / 4, .85 / 4, .55 / 4
    want = 4 * (0.75 * 1.45 + 1.0 * 1.15 + 0.25 * 0.85) / 4
    term, ratio = _term(ids, p)
    assert float(term) == pytest.approx(want, rel=1e-6)
    assert float(ratio) == pytest.approx(want / 2, rel=1e-6)
    # a padded token counts in neither c nor P nor N
    live = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    want = 4 * (2 / 3 * 0.75 + 1.0 * 1.05 + 1 / 3 * 0.75) / 3
    assert float(_term(ids, p, live)[0]) == pytest.approx(want, rel=1e-6)


def test_the_term_reads_k_at_uniform_logits_and_its_gradient():
    """At a router whose logits are all equal, ``p = 1/E`` and the term
    is exactly ``k`` (the counter exactly 1). Its gradient reaches every
    logit and none through the choice: it is ``E / N^2 * p_{n,m} (C_m -
    sum_e C_e p_{n,e})``, ``C`` the slots held fixed."""
    T, E, k = 12, 8, 3
    u0 = jnp.zeros((T, E))
    ids = moe_lib.route(u0, jnp.eye(E), jnp.zeros(E), k, 1.0,
                        score="softmax")[0]
    term, ratio = _term(ids, jax.nn.softmax(u0))
    assert float(term) == k and float(ratio) == 1.0

    u = jax.random.normal(jax.random.PRNGKey(7), (T, E))

    def f(u):
        p = jax.nn.softmax(u)
        ids = lax.top_k(p, k)[1]
        return _term(ids, p)[0]

    g = np.asarray(jax.grad(f)(u), np.float64)
    p = np.asarray(jax.nn.softmax(u), np.float64)
    slots = np.zeros(E)
    np.add.at(slots, np.asarray(lax.top_k(p, k)[1]).ravel(), 1.0)
    want = E / T ** 2 * p * (slots[None] - (p * slots[None]).sum(-1,
                                                                 keepdims=True))
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-7)
    assert np.all(g != 0)


# -------------------------------------------- (d) the shares add up
def test_shares_add_up_and_the_term_is_the_whole_routers():
    """A small expert layer of the cell's kind (8 experts, 4 a token, a
    softmax router) over two shares of 4: the two partial sums equal
    what the uncut reference gives for the whole layer, each share the
    reference given the same share; the term's statistics are the same
    on both shares, since each router scores every expert."""
    d, h, e, k, tokens = 32, 16, 8, 4, 48
    params = moe_lib.init_moe_params(jax.random.PRNGKey(3), d, h, e, 0)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, d))

    def reference(p, held, offset):
        m = {"num_experts": e, "experts_held": held,
             "expert_offset": offset, "num_experts_per_tok": k}
        leaves = {f"_l_moe.{n}": v for n, v in p.items()}
        with jax.default_matmul_precision("highest"):
            return ref._experts(leaves, "l", x, m, plain.Arith())

    def share(lo, hi):
        return {n: v[lo:hi] if n in ("wg", "wu", "wd") else v
                for n, v in params.items()}

    total, sums = 0.0, []
    with jax.default_matmul_precision("highest"):
        for lo in (0, 4):
            part, _, _, stats = moe_lib.moe_ffn(
                share(lo, lo + 4), x, top_k=k, offset=lo, score="softmax")
            y, (probs, slots) = reference(share(lo, lo + 4), 4, lo)
            np.testing.assert_allclose(np.asarray(part), np.asarray(y),
                                       rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(np.asarray(stats["probs"]),
                                       np.asarray(probs), rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(stats["slots"]),
                                          np.asarray(slots))
            assert float(stats["tokens"]) == tokens
            total = total + part
            sums.append(stats)
    whole, _ = reference(params, e, 0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=5e-6)
    for key in ("probs", "slots", "tokens"):
        np.testing.assert_array_equal(np.asarray(sums[0][key]),
                                      np.asarray(sums[1][key]))


# --------------------------------------------- (f) the parameter count
@pytest.mark.parametrize("qk_norm,want", [(True, 340_350_208),
                                          (False, 340_349_184)])
def test_the_counts_are_the_programs_table(qk_norm, want):
    """At the cell's size the graph alone (no array is made): the
    program's trained parameters are ``counts.param_count``'s and the
    issue's table's; without the q/k norms, 4 x 256 fewer."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2_12b_a2b5_ep8.json")) as f:
        cfg = json.load(f)
    args = dict(cfg["model"]["args"], qk_norm=qk_norm)
    dsl.reset()
    cost = models.mellum2(**args)[0]
    specs = Topology(cost).network.param_specs
    trained = sum(math.prod(s.shape) for s in specs.values()
                  if not s.is_static)
    cfg["model"]["args"] = args
    assert trained == counts.param_count(cfg) == want
    assert trained == sum(math.prod(shape) for shape, kind
                          in ref.leaves(cfg).values() if kind != "static")
    # the small model's table too
    small = sum(math.prod(s.shape) for s in network().param_specs.values()
                if not s.is_static)
    assert small == counts.param_count(CFG)
