"""``models.laguna`` (grouped-query attention whose layers differ in
kind: sliding-window and full, per-layer head counts, two rotary
schemes, a per-head output gate, over the expert layer) and its layers
against the plain reference (``benchmark/reference/laguna_xs2_ep32.py``)
at a tiny size on the CPU, seeded weights."""

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
from paddle_tpu.ops import common
from paddle_tpu.trainer.trainer import Topology

ref = importlib.import_module("benchmark.reference.laguna_xs2_ep32")

FULL, SLIDING = "full_attention", "sliding_attention"
ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 16, "beta_slow": 1,
           "beta_fast": 4, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}
ARGS = dict(
    vocab_size=64, hidden_size=32, intermediate_size=48,
    layer_types=[FULL, SLIDING, SLIDING, FULL],
    num_attention_heads_per_layer=[6, 8, 8, 6],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    num_key_value_heads=2, head_dim=8, sliding_window=8,
    rope_parameters=ROPE, gating=True, num_experts=16, experts_held=4,
    expert_offset=4, num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, moe_routed_scaling_factor=2.5,
    rms_norm_eps=1e-6)
CFG = {"model": {"args": ARGS}}
B, S = 2, 32


def weights(seed=3):
    """The reference's leaves from a seed, with the norm scales and the
    selection bias moved off 1 and 0 so that they matter."""
    leaves = ref.leaves(CFG)
    w = plain.make_weights(leaves, seed)
    key = jax.random.PRNGKey(1)
    return leaves, {
        n: (v + 0.1 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
            if leaves[n][1] in ("ones", "static") else v)
        for i, (n, v) in enumerate(sorted(w.items()))}


def network(**more):
    dsl.reset()
    cost, _out, names = models.laguna(**ARGS, loss_chunk=8,
                                      attention_block=16, **more)
    assert names == ["words"]
    return Topology(cost).network


def feed(ids):
    return {"words": Argument(value=ids,
                              mask=jnp.ones(ids.shape, jnp.float32))}


IDS = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, ARGS["vocab_size"])


def test_leaves_are_the_programs_parameters():
    leaves, _ = weights()
    net = network()
    assert set(net.param_specs) == set(leaves)
    for name, (shape, kind) in leaves.items():
        spec = net.param_specs[name]
        assert tuple(spec.shape) == tuple(shape), name
        assert spec.is_static == (kind == "static"), name
    # the layers differ in kind, and their names say it
    assert tuple(net.param_specs["_blk0_attn.wq"].shape) == (32, 6 * 8)
    assert tuple(net.param_specs["_blk1_swa.wq"].shape) == (32, 8 * 8)
    assert tuple(net.param_specs["_blk1_swa.wk"].shape) == (32, 2 * 8)
    assert tuple(net.param_specs["_blk3_attn.wg"].shape) == (32, 6)
    routers = {n for n, s in net.param_specs.items() if s.compute_f32}
    assert routers == {f"_blk{i}_moe.{s}" for i in (1, 2, 3)
                       for s in ("wr", "br")}


@pytest.mark.parametrize("kernels", ["ref", "interpret"])
@pytest.mark.parametrize("recompute", [True, False])
def test_loss_and_every_leafs_gradient(recompute, kernels):
    """Per-layer head counts (6, 8) over 2 key-value heads, a window of
    8 over 32 tokens in tiles of 16, with and without ``recompute``; on
    the reference path and with the Pallas kernels interpreted."""
    leaves, w = weights()
    net = network(recompute=recompute)
    trained = plain.trained(leaves)

    def program(p):
        out = net.apply({**w, **p}, feed(IDS), train=True)
        return jnp.mean(out["out_head"].value)

    def reference(p):
        return ref.loss({**w, **p}, {"words": IDS}, CFG, plain.Arith())

    p0 = {n: w[n] for n in trained}
    with jax.default_matmul_precision("highest"), \
            common.force_mode(kernels), common.record_dispatch() as tally:
        got, g_got = jax.value_and_grad(program)(p0)
        want, g_want = jax.value_and_grad(reference)(p0)
    assert set(tally["flash_attention"]) == {kernels}
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for n in trained:
        a, b = np.asarray(g_got[n]), np.asarray(g_want[n])
        assert np.abs(a - b).max() <= 3e-5 * np.abs(b).max() + 1e-9, n


@pytest.mark.parametrize("i", [0, 1])
def test_attention_layer_alone(i):
    """A full layer (6 heads, YaRN over half the head) and a sliding one
    (8 heads, a window of 8, plain rotary over the whole head), each
    against the reference's attention."""
    from paddle_tpu.core.registry import get_layer_impl
    _, w = weights()
    network()
    name = "blk0_attn" if i == 0 else "blk1_swa"
    cfg = dsl.current_graph().layers[name]
    assert cfg.attrs["window"] == (None if i == 0 else 8)
    params = {k.split(".")[1]: v for k, v in w.items()
              if k.startswith(f"_{name}.")}
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, 32))
    with jax.default_matmul_precision("highest"):
        got = get_layer_impl("gqa_attention").apply(
            cfg, params, [Argument(value=u, mask=jnp.ones((B, S)))], None)
        want = jnp.stack([ref._attention(w, i, u[b], ARGS, plain.Arith())
                          for b in range(B)])
    np.testing.assert_allclose(np.asarray(got.value), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    counters = (got.state or {}).get("counters", {})
    if i == 0:
        assert not counters
    else:
        # 32 tokens, a window of 8, tiles of 16: q block 0 walks one
        # tile, q block 1 two; 36 + 24 * 8 pairs are visible
        assert float(counters["swa_pairs_visited"]) == 3 * 16 * 16
        assert float(counters["swa_pairs_visible"]) == 36 + 24 * 8


def test_rotary_by_halves_turns_the_first_r_and_leaves_the_rest():
    """Against the complex-number form: (x[j] + i x[j + r/2]) *
    exp(i pos inv_freq[j]) over the first r elements, times the factor;
    the rest of the head untouched."""
    from paddle_tpu.layers.attention import rotary_halves
    d, r, T, factor = 16, 8, 50, 1.25
    inv = 10000.0 ** (-2.0 * np.arange(r // 2) / r)
    x = np.random.default_rng(0).normal(size=(2, 3, T, d)).astype(np.float32)
    z = (x[..., :r // 2] + 1j * x[..., r // 2:r]) * factor \
        * np.exp(1j * np.arange(T)[:, None] * inv)
    want = np.concatenate([z.real, z.imag, x[..., r:]], axis=-1)
    got = np.asarray(rotary_halves(jnp.asarray(x), inv, factor))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., r:], x[..., r:])
    np.testing.assert_allclose(
        np.asarray(ref.rotary(jnp.asarray(x[0, 0]),
                              jnp.asarray(inv, jnp.float32), factor)),
        want[0, 0], rtol=1e-5, atol=1e-5)
    # Hugging Face's spelling: x cos + rotate_half(x) sin
    cos = np.cos(np.arange(T)[:, None] * np.r_[inv, inv]) * factor
    sin = np.sin(np.arange(T)[:, None] * np.r_[inv, inv]) * factor
    turned = x[..., :r]
    half = np.concatenate([-turned[..., r // 2:], turned[..., :r // 2]], -1)
    np.testing.assert_allclose(got[..., :r], turned * cos + half * sin,
                               rtol=1e-5, atol=1e-5)


def test_yarn_inv_freq_against_numbers_worked_by_hand():
    """Laguna-XS.2's full layers: r = 64, b = 500000, factor 64, L =
    4096, beta_fast 64, beta_slow 1. dim(n) = 64 ln(4096 / (2 pi n)) /
    (2 ln 500000): dim(64) = 64 * 2.32101 / 26.24473 = 5.66 -> low 5;
    dim(1) = 64 * 6.47989 / 26.24473 = 15.80 -> high 16. So pairs 0..5
    keep b^(-2i/64), pairs 16..31 are divided by 64, and pair i between
    blends with ramp (i - 5) / 11."""
    from paddle_tpu.layers.attention import yarn_inv_freq
    got = yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert got.shape == (32,)
    b = 500000.0
    assert got[0] == pytest.approx(1.0)
    assert got[5] == pytest.approx(b ** (-10 / 64), rel=1e-6)
    assert got[5] == pytest.approx(0.128687, rel=1e-4)
    assert got[16] == pytest.approx(b ** -0.5 / 64, rel=1e-6)
    assert got[16] == pytest.approx(2.20971e-5, rel=1e-4)
    assert got[31] == pytest.approx(b ** (-62 / 64) / 64, rel=1e-6)
    # pair 10: ramp 5/11, plain 500000^(-20/64) = 0.0165604
    plain10 = b ** (-20 / 64)
    assert plain10 == pytest.approx(0.0165604, rel=1e-4)
    assert got[10] == pytest.approx(
        (1 - 5 / 11) * plain10 + (5 / 11) * plain10 / 64, rel=1e-6)
    assert got[10] == pytest.approx(0.00915058, rel=1e-4)
    # the reference's own spelling gives the same 32 numbers and the
    # config's attention factor, 0.1 ln 64 + 1
    rope = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5}
    freqs, factor = ref.inv_freq(rope, 128)
    np.testing.assert_allclose(np.asarray(freqs), got, rtol=1e-6)
    assert factor == pytest.approx(0.1 * math.log(64) + 1)
    # a sliding layer: plain frequencies over the whole head, factor 1
    freqs, factor = ref.inv_freq(ROPE[SLIDING], 128)
    np.testing.assert_allclose(
        np.asarray(freqs), 10000.0 ** (-np.arange(64) / 64), rtol=1e-6)
    assert factor == 1.0


def test_one_bfloat16_step_through_sgd_with_recompute():
    """``SGD.train`` under ``compute_dtype="bfloat16"`` with ``recompute``
    on: the cost is near the float32 reference's, every trained leaf
    moves, the selection bias does not, and the step hands back the
    experts' rows and the sliding layers' pairs."""
    from paddle_tpu.data import DataFeeder, integer_value_sequence
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGD, events

    leaves, w = weights()
    dsl.reset()
    cost, _, _ = models.laguna(**ARGS, loss_chunk=8, attention_block=16,
                               recompute=True)
    tr = SGD(cost=cost,
             parameters={n: jnp.copy(v) for n, v in w.items()},
             update_equation=Adam(learning_rate=1e-3),
             compute_dtype="bfloat16")
    cast = tr._cast_params(tr.params)
    assert cast["_blk1_moe.wr"].dtype == jnp.float32
    assert cast["_blk1_swa.wq"].dtype == jnp.bfloat16
    feeder = DataFeeder({"words": integer_value_sequence(64)},
                        pad_multiple=S)
    rows = [(list(map(int, r)),) for r in np.asarray(IDS)]
    costs = []
    tr.train(lambda: iter([rows]), feeder=feeder, num_passes=1,
             event_handler=lambda e: costs.append(e.cost)
             if isinstance(e, events.EndIteration) else None)
    want = float(ref.loss(w, {"words": IDS}, CFG, plain.Arith()))
    assert costs[0] == pytest.approx(want, rel=2e-2)
    for n in plain.trained(leaves):
        assert np.any(np.asarray(tr.params[n]) != np.asarray(w[n])), n
    np.testing.assert_array_equal(np.asarray(tr.params["_blk2_moe.br"]),
                                  np.asarray(w["_blk2_moe.br"]))
    totals = tr.breakdown.totals
    assert 0 < totals["moe_rows_mean"] <= B * S
    # the mean over the two sliding layers of a static count
    assert totals["swa_pairs_visited"] == 3 * 16 * 16
    assert totals["swa_pairs_visible"] == 36 + 24 * 8


def test_shares_add_up():
    """A small Laguna expert layer (256 experts, 8 a token, weights
    scaled 2.5) over its deployment's 32 shares of 8: the 32 partial
    sums, with the shared expert counted once, equal what the uncut
    reference gives for the whole layer; the share the cell holds
    (experts 8..15) equals the reference given the same share."""
    from paddle_tpu.parallel import moe as moe_lib
    d, h, e, k, tokens = 32, 16, 256, 8, 48
    params = moe_lib.init_moe_params(jax.random.PRNGKey(3), d, h, e)
    params["br"] = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (e,))
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, d))

    def reference(p, held, offset, shared=True):
        m = {"num_experts": e, "experts_held": held,
             "expert_offset": offset, "num_experts_per_tok": k,
             "moe_routed_scaling_factor": 2.5}
        leaves = {f"_l_moe.{n}": v for n, v in p.items()
                  if shared or n not in ("sg", "su", "sd")}
        with jax.default_matmul_precision("highest"):
            return ref._experts(leaves, "l", x, m, plain.Arith())

    def share(lo, hi):
        return {n: v[lo:hi] if n in ("wg", "wu", "wd") else v
                for n, v in params.items()}

    with jax.default_matmul_precision("highest"):
        total = moe_lib.swiglu(x, params["sg"], params["su"], params["sd"])
        for i in range(32):
            part, _, _, _ = moe_lib.moe_ffn(share(8 * i, 8 * i + 8), x,
                                            top_k=k, scale=2.5, offset=8 * i,
                                            shared=False)
            if i == 1:
                np.testing.assert_allclose(
                    np.asarray(part), np.asarray(reference(
                        share(8, 16), 8, 8, shared=False)),
                    rtol=2e-5, atol=2e-6)
            total = total + part
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(reference(params, e, 0)),
                               rtol=2e-5, atol=5e-6)
