"""The decoder-only language model builder (``models.joyai_llm_flash``)
and its layers against the plain reference
(``benchmark/reference/joyai_llm_flash_ep32.py``) at a tiny size on the
CPU, seeded weights."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import plain
from paddle_tpu import models
from paddle_tpu.config import dsl
from paddle_tpu.core.argument import Argument
from paddle_tpu.trainer.trainer import Topology

ref = importlib.import_module("benchmark.reference.joyai_llm_flash_ep32")

ARGS = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=48, moe_intermediate_size=16,
    n_routed_experts=16, experts_held=4, expert_offset=4,
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=2.5,
    num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=32e6,
    rms_norm_eps=1e-6, num_nextn_predict_layers=1, mtp_loss_weight=0.3)
CFG = {"model": {"args": ARGS}}
B, S = 3, 24


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
    cost, _out, names = models.joyai_llm_flash(**ARGS, loss_chunk=8, **more)
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
    routers = {n for n, s in net.param_specs.items() if s.compute_f32}
    assert routers == {f"_{t}_moe.{s}" for t in ("blk1", "blk2", "mtp")
                       for s in ("wr", "br")}


@pytest.mark.parametrize("recompute", [True, False])
def test_loss_and_every_leafs_gradient(recompute):
    leaves, w = weights()
    net = network(recompute=recompute)
    trained = plain.trained(leaves)

    def program(p):
        out = net.apply({**w, **p}, feed(IDS), train=True)
        return jnp.mean(out["cost"].value)

    def reference(p):
        return ref.loss({**w, **p}, {"words": IDS}, CFG, plain.Arith())

    p0 = {n: w[n] for n in trained}
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(program)(p0)
        want, g_want = jax.value_and_grad(reference)(p0)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for n in trained:
        a, b = np.asarray(g_got[n]), np.asarray(g_want[n])
        assert np.abs(a - b).max() <= 2e-5 * np.abs(b).max() + 1e-9, n


def test_multi_token_loss_is_its_definition():
    """CE(main, t_{i+1}) over S-1 positions + 0.3 * CE(mtp, t_{i+2}) over
    S-2: the two heads' costs, each recomputed from the layer below it."""
    _, w = weights()
    net = network()
    out = net.apply(w, feed(IDS), train=True)

    def ce(hidden, shift):
        logits = hidden @ w["_out_head.w0"]
        logp = jax.nn.log_softmax(logits[:, :S - shift], axis=-1)
        picked = jnp.take_along_axis(logp, IDS[:, shift:, None], axis=-1)
        return -jnp.mean(picked[..., 0], axis=1)

    with jax.default_matmul_precision("highest"):
        main = ce(out["out_norm"].value, 1)
        mtp = ce(out["mtp_out_norm"].value, 2)
    np.testing.assert_allclose(np.asarray(out["out_head"].value[:, 0]),
                               np.asarray(main), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["mtp_head"].value[:, 0]),
                               np.asarray(0.3 * mtp), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out["cost"].value[:, 0]),
        np.asarray(main + 0.3 * mtp), rtol=1e-5)
    # the module's second input is the embedding one position on
    np.testing.assert_array_equal(
        np.asarray(out["mtp_shift"].value[:, :-1]),
        np.asarray(out["embed"].value[:, 1:]))


def test_latent_attention_layer_alone():
    from paddle_tpu.core.registry import get_layer_impl
    _, w = weights()
    dsl.reset()
    x = dsl.data(name="x", size=ARGS["hidden_size"], is_sequence=True)
    dsl.mla_attention(
        x, num_heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, rope_theta=32e6, name="blk1_attn")
    cfg = dsl.current_graph().layers["blk1_attn"]
    params = {k.split(".")[1]: v for k, v in w.items()
              if k.startswith("_blk1_attn.")}
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, 32))
    with jax.default_matmul_precision("highest"):
        got = get_layer_impl("mla_attention").apply(
            cfg, params, [Argument(value=u, mask=jnp.ones((B, S)))], None)
        want = jnp.stack([ref._attention(w, "blk1", u[i], ARGS,
                                         plain.Arith()) for i in range(B)])
    np.testing.assert_allclose(np.asarray(got.value), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_rotary_pairs_are_interleaved():
    """Against the complex-number form: (x[2i] + i x[2i+1]) * exp(i pos
    theta^(-2i/d))."""
    from paddle_tpu.layers.attention import rotary_interleaved
    theta, d, T = 32e6, 8, 50
    x = np.random.default_rng(0).normal(size=(2, 3, T, d)).astype(np.float32)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    ang = np.arange(T)[:, None] * theta ** (-np.arange(0, d, 2) / d)
    z = z * np.exp(1j * ang)
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(
        np.asarray(rotary_interleaved(jnp.asarray(x), theta)), want,
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref._rotary(jnp.asarray(x[0, 0]), theta)), want[0, 0],
        rtol=1e-5, atol=1e-5)


def test_one_bfloat16_step_through_sgd_with_recompute():
    """``SGD.train`` under ``compute_dtype="bfloat16"`` with ``recompute``
    on: the cost is near the float32 reference's, the router stays
    float32 inside the step, every trained leaf moves, the selection
    bias does not, and the step hands back the experts' rows."""
    from paddle_tpu.data import DataFeeder, integer_value_sequence
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGD, events

    leaves, w = weights()
    dsl.reset()
    cost, _, _ = models.joyai_llm_flash(**ARGS, loss_chunk=8, recompute=True)
    tr = SGD(cost=cost,
             parameters={n: jnp.copy(v) for n, v in w.items()},
             update_equation=Adam(learning_rate=1e-3),
             compute_dtype="bfloat16")
    cast = tr._cast_params(tr.params)
    assert cast["_blk1_moe.wr"].dtype == jnp.float32
    assert cast["_blk1_moe.wg"].dtype == jnp.bfloat16
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
    np.testing.assert_array_equal(np.asarray(tr.params["_mtp_moe.br"]),
                                  np.asarray(w["_mtp_moe.br"]))
    totals = tr.breakdown.totals
    # a step's mean over the 3 expert layers of the rows a held expert
    # got: B*S*k*held/E = 18 under a uniform router, at most B*S
    assert 0 < totals["moe_rows_mean"] <= B * S
    assert totals["moe_rows_max"] >= totals["moe_rows_mean"]
