"""``models.lfm2_moe`` (gated short convolutions three to one with
grouped-query attention under a per-head q/k normalisation, the expert
layer without a shared expert, a head tied to the embedding) and its new
parts against the plain reference
(``benchmark/reference/lfm2_24b_a2b_ep8.py``) at a small size on the
CPU, seeded weights."""

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
from paddle_tpu.ops.short_conv import depthwise_time_conv, gated_short_conv
from paddle_tpu.trainer.trainer import Topology

ref = importlib.import_module("benchmark.reference.lfm2_24b_a2b_ep8")
counts = importlib.import_module("benchmark.counts.lfm2_24b_a2b_ep8")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONV, FULL = "conv", "full_attention"
ARGS = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96,
    layer_types=[CONV, FULL, CONV], num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    norm_eps=1e-5, num_experts=8, experts_held=4, expert_offset=2,
    num_experts_per_tok=2, moe_intermediate_size=48,
    routed_scaling_factor=1.0, norm_topk_eps=1e-6)
CFG = {"model": {"args": ARGS},
       "optimizer": {"kind": "adam", "args": {
           "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
           "epsilon": 1e-8}}}
B, S = 2, 32
IDS = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, ARGS["vocab_size"])


def weights(seed=3):
    """The reference's leaves from a seed, with the norm scales (the q/k
    norms' among them) and the expert bias moved off 1 and 0 so that they
    matter."""
    leaves = ref.leaves(CFG)
    w = plain.make_weights(leaves, seed)
    key = jax.random.PRNGKey(1)
    return leaves, {
        n: (v + 0.1 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
            if leaves[n][1] in ("ones", "static") else v)
        for i, (n, v) in enumerate(sorted(w.items()))}


def graph(**more):
    dsl.reset()
    cost, _out, names = models.lfm2_moe(**ARGS, loss_chunk=8,
                                        attention_block=16, **more)
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
    # the layers differ in operator, and their names say it
    assert tuple(net.param_specs["_blk0_sconv.wi"].shape) == (64, 192)
    assert tuple(net.param_specs["_blk0_sconv.wc"].shape) == (3, 64)
    assert tuple(net.param_specs["_blk1_attn.wk"].shape) == (64, 2 * 16)
    assert tuple(net.param_specs["_blk1_attn.gq"].shape) == (16,)
    assert "_blk0_mlp.wg" in leaves and "_blk1_mlp.wg" not in leaves
    assert not any(".wg" in n and "_attn" in n for n in leaves)   # no gate
    assert not any(n.endswith((".sg", ".su", ".sd")) for n in leaves)
    routers = {n for n, s in net.param_specs.items() if s.compute_f32}
    assert routers == {f"_blk{i}_moe.{s}" for i in (1, 2)
                       for s in ("wr", "br")}


@pytest.mark.parametrize("kernels", ["ref", "interpret"])
@pytest.mark.parametrize("recompute", [True, False])
def test_loss_and_every_leafs_gradient(recompute, kernels):
    """Float32 at ``highest``, to 1e-5 of a leaf's largest gradient
    element (observed 2.4e-6); with and without ``recompute``, on the
    reference path and with the Pallas kernels interpreted (a head of 16
    in tiles of 16)."""
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
    reference's three, in float32 at ``highest``: every loss to 1e-5
    (observed 0). The parameters by their change ``P3 - P0``, leaf by
    leaf: Adam's first steps move every element by about the learning
    rate whatever its gradient, so an element whose gradient is round-off
    flips with it; the limit is 1e-4 of the leaf's change in norm
    (observed at most 1.3e-5). The static expert bias does not move."""
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


def test_three_adam_steps_in_the_configurations_bfloat16():
    """``compute_dtype="bfloat16"`` as the configuration states it
    (float32 masters, routers float32). Limits, with their reason: a
    bfloat16 rounding is a relative error of 2^-9 = 2e-3, through three
    blocks of about ten roundings each (``s = B * X`` among them), so the
    loss agrees to 2e-2 (observed 9e-4) and a leaf's first gradient, read
    back from Adam's first moment, to a quarter of its norm (observed at
    most 0.12, on an expert's weight: its gradient comes from the few
    rows routed to it, and a token whose two best scores lie within a
    rounding of each other changes expert); under Adam the change of the
    parameters after three steps is about the learning rate an element
    whatever the gradient's size, so its norm agrees far closer than its
    direction: 5e-2 (observed 1.2e-2). The step hands back the experts'
    rows."""
    leaves, w = weights()
    tr, costs, first_moment = _train(w, 3, compute_dtype="bfloat16")
    cast = tr._cast_params(tr.params)
    assert cast["_blk1_moe.wr"].dtype == jnp.float32
    assert cast["_blk0_sconv.wc"].dtype == jnp.bfloat16
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
    assert 0 < tr.breakdown.totals["moe_rows_mean"] <= 3 * B * S


# ------------------------------------- (b) the gated short convolution
def _conv_inputs(d=16, T=12, k=3, seed=0):
    r = np.random.default_rng(seed)
    bcx = jnp.asarray(r.normal(size=(2, T, 3 * d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(k, d)), jnp.float32)
    mask = jnp.asarray(np.arange(T)[None] < np.array([[T], [T - 4]]),
                       jnp.float32)
    return bcx, w, mask


def _by_shifted_sums(bcx, w, mask=None):
    """``C * (w[2] s_t + w[1] s_{t-1} + w[0] s_{t-2})``, spelled out."""
    d = w.shape[1]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    s = b * x if mask is None else b * x * mask[..., None]
    back1 = jnp.pad(s, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    back2 = jnp.pad(s, ((0, 0), (2, 0), (0, 0)))[:, :-2]
    return c * (w[2] * s + w[1] * back1 + w[0] * back2)


def _by_lax_conv(bcx, w, mask=None):
    """The same through ``lax.conv_general_dilated`` with one group a
    channel and two steps of padding on the left."""
    k, d = w.shape
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    s = b * x if mask is None else b * x * mask[..., None]
    conv = lax.conv_general_dilated(
        s, w[:, None, :], window_strides=(1,), padding=((k - 1, 0),),
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=d,
        precision=lax.Precision.HIGHEST)
    return c * conv


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("other", [_by_shifted_sums, _by_lax_conv])
def test_gated_short_conv_forward_and_gradients(other, masked):
    bcx, w, mask = _conv_inputs()
    mask = mask if masked else None
    g = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 16)),
                    jnp.float32)

    def through(fn):
        out, back = jax.vjp(lambda a, b: fn(a, b, mask), bcx, w)
        return (out, *back(g))

    for got, want in zip(through(gated_short_conv), through(other)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [0, 5, 11])
def test_gated_short_conv_is_causal_and_three_taps_long(t):
    """Changing position ``t`` of the input changes no output before
    ``t`` and none after ``t + 2``; and it does change ``t .. t + 2``."""
    bcx, w, _ = _conv_inputs()
    other = bcx.at[:, t].add(1.0)
    moved = np.abs(np.asarray(gated_short_conv(other, w)
                              - gated_short_conv(bcx, w))).max(axis=(0, 2))
    assert not moved[:t].any() and not moved[t + 3:].any()
    assert moved[t:t + 3].all()


def test_a_padded_step_feeds_no_later_one():
    bcx, w, mask = _conv_inputs()
    other = bcx.at[1, 8:].add(3.0)          # row 1 is live up to 8
    a = np.asarray(gated_short_conv(bcx, w, mask))
    b = np.asarray(gated_short_conv(other, w, mask))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1, :8], b[1, :8])


def test_the_lookahead_row_convolution_is_the_same_taps_reversed():
    """``RowConvLayer`` runs over the same core: looking ahead is the
    causal convolution of the reversed sequence with the taps reversed."""
    r = np.random.default_rng(2)
    s = jnp.asarray(r.normal(size=(2, 9, 5)), jnp.float32)
    w = jnp.asarray(r.normal(size=(3, 5)), jnp.float32)
    ahead = depthwise_time_conv(s, w, causal=False)
    back = depthwise_time_conv(s[:, ::-1], w[::-1], causal=True)[:, ::-1]
    np.testing.assert_allclose(np.asarray(ahead), np.asarray(back),
                               rtol=1e-6, atol=1e-6)
    want = sum(np.pad(np.asarray(s), ((0, 0), (0, 2), (0, 0)))[:, j:j + 9]
               * np.asarray(w)[j] for j in range(3))
    np.testing.assert_allclose(np.asarray(ahead), want, rtol=1e-5, atol=1e-6)


def test_short_conv_layer_alone_against_the_reference():
    from paddle_tpu.core.registry import get_layer_impl
    _, w = weights()
    network()
    cfg = dsl.current_graph().layers["blk0_sconv"]
    assert cfg.attrs["kernel"] == 3
    params = {k.split(".")[1]: v for k, v in w.items()
              if k.startswith("_blk0_sconv.")}
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, 64))
    with jax.default_matmul_precision("highest"):
        got = get_layer_impl("short_conv").apply(
            cfg, params, [Argument(value=u, mask=jnp.ones((B, S)))], None)
        want = jnp.stack([ref._short_conv(w, 0, u[b], ARGS, plain.Arith())
                          for b in range(B)])
    np.testing.assert_allclose(np.asarray(got.value), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# ------------------------------------------------ (c) the share adds up
def test_shares_add_up():
    """A small LFM2 expert layer (8 experts, 2 a token, the normaliser's
    1e-6, no shared expert) over two shares of 4: the two partial sums
    add up to what the uncut reference gives for the whole layer (no
    shared expert to count once); a share equals the reference given the
    same share."""
    from paddle_tpu.parallel import moe as moe_lib
    d, h, e, k, tokens = 32, 16, 8, 2, 48
    params = moe_lib.init_moe_params(jax.random.PRNGKey(3), d, h, e,
                                     d_shared=0)
    assert "sg" not in params
    params["br"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (e,))
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, d))

    def by_reference(p, held, offset):
        m = {"num_experts": e, "experts_held": held,
             "expert_offset": offset, "num_experts_per_tok": k,
             "routed_scaling_factor": 1.0, "norm_topk_eps": 1e-6}
        leaves = {f"_l_moe.{n}": v for n, v in p.items()}
        with jax.default_matmul_precision("highest"):
            return ref._experts(leaves, "l", x, m, plain.Arith())

    def share(lo, hi):
        return {n: v[lo:hi] if n in ("wg", "wu", "wd") else v
                for n, v in params.items()}

    total = 0.0
    with jax.default_matmul_precision("highest"):
        for lo in (0, 4):
            part, rows, _, _ = moe_lib.moe_ffn(
                share(lo, lo + 4), x, top_k=k, offset=lo, norm_eps=1e-6)
            np.testing.assert_allclose(
                np.asarray(part), np.asarray(by_reference(
                    share(lo, lo + 4), 4, lo)), rtol=2e-5, atol=2e-6)
            total = total + part
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(by_reference(params, e, 0)),
                               rtol=2e-5, atol=5e-6)
    # the normaliser's eps is in the weights, and only where asked
    ids, plain_w, _ = moe_lib.route(x, params["wr"], params["br"], k, 1.0)
    _, eps_w, _ = moe_lib.route(x, params["wr"], params["br"], k, 1.0,
                                 1e-6)
    np.testing.assert_allclose(np.asarray(plain_w.sum(-1)), 1.0, rtol=1e-6)
    assert np.all(np.asarray(eps_w) <= np.asarray(plain_w))
    assert "1e-06" not in str(jax.make_jaxpr(
        lambda a: moe_lib.route(a, params["wr"], params["br"], k, 1.0))(x))


# ----------------------------------------------------- (d) the tied head
def _untied():
    """The same graph with the head a leaf of its own, ``[d, V]``."""
    cost = graph()
    head = dsl.current_graph().layers["out_head"]
    head.attrs.pop("tied")
    head.inputs[0].param_attr = None
    return Topology(cost).network


def test_the_tied_head_is_the_embeddings_leaf():
    leaves, w = weights()
    tied = network()
    assert "_out_head.w0" not in tied.param_specs
    assert [n for n in tied.param_specs
            if "embed" in n or "head" in n] == ["_embed.w0"]
    assert tied._layer_params["out_head"] == {"w0": "_embed.w0"}
    assert tied._layer_params["embed"] == {"w0": "_embed.w0"}
    untied = _untied()
    assert tuple(untied.param_specs["_out_head.w0"].shape) == (64, 96)
    copies = dict(w, **{"_out_head.w0": w["_embed.w0"].T})
    with jax.default_matmul_precision("highest"):
        a, g_tied = jax.value_and_grad(cost_of(tied, {}))(dict(w))
        b, g_two = jax.value_and_grad(cost_of(untied, {}))(copies)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    # one leaf, its gradient the sum of the lookup's scatter and the
    # head's product, each of which is there
    assert np.abs(np.asarray(g_two["_embed.w0"])).max() > 0
    assert np.abs(np.asarray(g_two["_out_head.w0"])).max() > 0
    close(g_tied["_embed.w0"],
          g_two["_embed.w0"] + g_two["_out_head.w0"].T, 1e-5)
    for n in plain.trained(leaves):
        if n != "_embed.w0":
            close(g_tied[n], g_two[n], 1e-5, n)


def test_the_inference_output_shares_the_tied_leaf():
    _, w = weights()
    dsl.reset()
    _cost, out, _ = models.lfm2_moe(**ARGS, loss_chunk=8, attention_block=16)
    net = Topology(out).network
    assert net._layer_params["output"] == {"w0": "_embed.w0"}
    assert "_output.w0" not in net.param_specs
    with jax.default_matmul_precision("highest"):
        probs = net.apply(w, feed(), train=False)["output"].value
    assert probs.shape == (B, S, 96)
    np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.0, rtol=1e-5)


def test_a_checkpoint_holds_the_tied_leaf_once(tmp_path):
    from paddle_tpu.trainer.checkpoint import load_params, save_params
    leaves, w = weights()
    path = str(tmp_path / "lfm2.npz")
    save_params(path, w)
    got, _ = load_params(path)
    assert set(got) == set(leaves)
    with np.load(path) as z:
        stored = [k for k in z.files if "embed" in k or "out_head" in k]
    assert len(stored) == 1
    net = network()
    with jax.default_matmul_precision("highest"):
        a = cost_of(net, w)({})
        b = cost_of(net, {n: jnp.asarray(v) for n, v in got.items()})({})
    assert float(a) == float(b)


def test_the_full_size_table_is_469m_with_one_leaf_for_both():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b_ep8.json")) as f:
        cfg = json.load(f)
    dsl.reset()
    cost, _, _ = models.lfm2_moe(**cfg["model"]["args"])
    net = Topology(cost).network
    trained = sum(math.prod(s.shape) for s in net.param_specs.values()
                  if not s.is_static)
    assert trained == counts.param_count(cfg) == 469_284_992
    # the issue's arithmetic: a dense conv layer, a full + 3 conv expert
    # layers, an eighth of the vocabulary once, the final norm
    assert trained == 89_139_200 + 86_118_528 + 3 * 92_416_000 \
        + 8192 * 2048 + 2048
    static = sum(math.prod(s.shape) for s in net.param_specs.values()
                 if s.is_static)
    assert static == 4 * 64
    assert set(net.param_specs) == set(ref.leaves(cfg))
    kinds = [n.rsplit("_", 1)[1] for n in net.order
             if n.endswith(("_sconv", "_attn"))]
    assert kinds == ["sconv", "attn", "sconv", "sconv", "sconv"]
    assert counts.param_count(CFG) == sum(
        math.prod(shape) for shape, kind in ref.leaves(CFG).values()
        if kind != "static")


# --------------------------------------------- (e) the q/k normalisation
def _gqa(qk_norm):
    dsl.reset()
    x = dsl.data(name="x", size=64, is_sequence=True)
    more = dict(qk_norm=True, qk_norm_eps=1e-5) if qk_norm else {}
    layer = dsl.gqa_attention(x, num_heads=4, num_kv_heads=2, head_dim=16,
                              rope_theta=1e6, gate=False, block=16,
                              name="blk1_attn", **more)
    return dsl.current_graph().layers[layer.name]


def test_qk_norm_off_is_the_layer_as_it_was_and_on_is_the_references():
    from paddle_tpu.core.registry import ShapeInfo, get_layer_impl
    impl = get_layer_impl("gqa_attention")
    _, w = weights()
    params = {k.split(".")[1]: v for k, v in w.items()
              if k.startswith("_blk1_attn.")}
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, 64))
    arg = [Argument(value=u, mask=jnp.ones((B, S)))]
    off, on = _gqa(False), _gqa(True)
    assert "qk_norm" not in off.attrs
    info = [ShapeInfo(size=64, is_sequence=True)]
    assert set(impl.params(off, info)) == {"wq", "wk", "wv", "wo"}
    assert set(impl.params(on, info)) == {"wq", "wk", "wv", "wo", "gq",
                                          "gk"}
    plain_params = {k: v for k, v in params.items() if k not in ("gq", "gk")}
    text = str(jax.make_jaxpr(
        lambda p: impl.apply(off, p, arg, None).value)(plain_params))
    assert "rsqrt" not in text          # no statistic anywhere in it
    with jax.default_matmul_precision("highest"):
        # scales of one and no eps are no normalisation of a unit head;
        # off, the layer never reads them
        a = impl.apply(off, plain_params, arg, None).value
        b = impl.apply(off, params, arg, None).value
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        got = impl.apply(on, params, arg, None).value
        want = jnp.stack([ref._attention(w, 1, u[i], ARGS, plain.Arith())
                          for i in range(B)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    assert np.abs(np.asarray(got - a)).max() > 1e-3
