"""Per-layer rematerialization: `layer_attr={"recompute": True}` wraps
the layer in `jax.checkpoint` — gradients identical, a remat region in
the jaxpr, batch-norm state updates still flow (they thread through the
checkpointed function as explicit outputs)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.config import dsl
from paddle_tpu.config.model_config import Input, LayerDef
from paddle_tpu.core import registry
from paddle_tpu.core.argument import Argument
from paddle_tpu.core.network import Network
from paddle_tpu.core.registry import LayerImpl, ParamSpec, ShapeInfo
from paddle_tpu.ops import common
from paddle_tpu.ops.attention import flash_attention
from paddle_tpu.optim import Momentum
from paddle_tpu.trainer import SGD


def _model(recompute):
    dsl.reset()
    x = dsl.data(name="x", size=16)
    lab = dsl.data(name="label", size=4)
    h = dsl.fc(input=x, size=32, act="relu", name="h",
               layer_attr={"recompute": True} if recompute else None)
    hb = dsl.batch_norm(input=h, name="hb",
                        layer_attr={"recompute": True} if recompute
                        else None)
    out = dsl.fc(input=hb, size=4, act="softmax", name="out")
    return dsl.classification_cost(input=out, label=lab)


def _feed(n=32):
    rng = np.random.RandomState(0)
    return {
        "x": Argument(value=jnp.asarray(rng.randn(n, 16), jnp.float32)),
        "label": Argument(value=jnp.asarray(
            rng.randint(0, 4, size=n), jnp.int32)),
    }


def _one_step(recompute):
    tr = SGD(cost=_model(recompute),
             update_equation=Momentum(learning_rate=0.1, momentum=0.9),
             seed=3)
    p, o, m = tr._train_step(tr.params, tr.opt_state, _feed(),
                             jax.random.PRNGKey(0), 0)
    return ({k: np.asarray(jax.device_get(v)) for k, v in p.items()},
            float(m["cost"]))


def test_recompute_matches_plain():
    p0, c0 = _one_step(False)
    p1, c1 = _one_step(True)
    assert abs(c0 - c1) < 1e-6
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    # batch-norm moving stats updated through the checkpointed region
    assert not np.allclose(p1["_hb.w1"], 0.0)


def test_recompute_on_nested_group_keeps_static_state():
    """A recomputed layer whose Argument.state carries static Python
    metadata (a nested group's shape ints) must not leak that metadata
    through jax.checkpoint as tracers — downstream shape arithmetic
    stays static."""
    B, S, T, D_ = 2, 3, 4, 5
    dsl.reset()
    x = dsl.data(name="x", size=D_, is_sequence=True)

    def outer_step(sub):
        def inner_step(xt):
            m = dsl.memory(name="h", size=D_)
            return dsl.fc(input=[xt, m], size=D_, act="tanh", name="h",
                          bias_attr=False)

        inner = dsl.recurrent_group(inner_step, sub, name="inner_rnn")
        return dsl.last_seq(inner, name="olast")

    out = dsl.recurrent_group(outer_step, dsl.SubsequenceInput(x),
                              name="outer_rnn")
    pooled = dsl.pooling(input=out, pooling_type="avg", name="pooled")
    graph = dsl.current_graph()
    graph.layers[out.name].attrs["recompute"] = True

    net = Network(graph, outputs=[pooled.name])
    params = net.init_params(jax.random.PRNGKey(0))
    feed = {"x": Argument(
        value=jnp.asarray(np.random.RandomState(0).randn(
            B, S, T, D_).astype(np.float32)),
        mask=jnp.ones((B, S, T), jnp.float32))}

    def loss(p):
        return jnp.sum(net.apply(p, feed, train=True,
                                 rng=jax.random.PRNGKey(1))[
                                     pooled.name].value ** 2)

    val, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(val))
    assert float(jnp.abs(grads["_h.w0"]).sum()) > 0


def test_recompute_emits_remat_region():
    tr = SGD(cost=_model(True),
             update_equation=Momentum(learning_rate=0.1), seed=3)
    jaxpr = jax.make_jaxpr(
        lambda p, o, f, k: tr._train_step(p, o, f, k, 0))(
            tr.params, tr.opt_state, _feed(), jax.random.PRNGKey(0))
    assert "remat" in str(jaxpr) or "checkpoint" in str(jaxpr)

    tr2 = SGD(cost=_model(False),
              update_equation=Momentum(learning_rate=0.1), seed=3)
    jaxpr2 = jax.make_jaxpr(
        lambda p, o, f, k: tr2._train_step(p, o, f, k, 0))(
            tr2.params, tr2.opt_state, _feed(), jax.random.PRNGKey(0))
    assert "remat" not in str(jaxpr2) and "checkpoint" not in str(jaxpr2)


# ------------------------------------------------------------------
# What a recomputed layer keeps: its inputs, and what a kernel's forward
# rule named `common.KEPT_RESIDUAL` (the flash core's output and
# log-sum-exp), so the forward kernel is not run again in the backward.

_B, _T, _D, _HEADS = 2, 128, 32, 2


class _ProjFlashProj(LayerImpl):
    """x Wq, x Wk, x Wv -> flash_attention -> Wo; q and k of ``dqk`` a
    head, v of ``dv``."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=in_infos[0].size, is_sequence=True)

    def params(self, cfg, in_infos):
        d, a = in_infos[0].size, cfg.attrs
        return {"wq": ParamSpec(shape=(d, _HEADS * a["dqk"])),
                "wk": ParamSpec(shape=(d, _HEADS * a["dqk"])),
                "wv": ParamSpec(shape=(d, _HEADS * a["dv"])),
                "wo": ParamSpec(shape=(_HEADS * a["dv"], d))}

    def apply(self, cfg, params, ins, ctx):
        x = ins[0].value

        def split(y):
            return y.reshape(_B, _T, _HEADS, -1).transpose(0, 2, 1, 3)

        out = flash_attention(
            split(x @ params["wq"]), split(x @ params["wk"]),
            split(x @ params["wv"]), None, causal=cfg.attrs["causal"],
            block_q=64, block_k=64)
        return Argument(
            value=out.transpose(0, 2, 1, 3).reshape(_B, _T, -1)
            @ params["wo"], mask=ins[0].mask)


@pytest.fixture
def proj_flash_proj():
    """The layer type, registered for one test only (other files count
    the registry's types)."""
    name = "_test_proj_flash_proj"
    registry.register_layer(name)(_ProjFlashProj)
    yield name
    del registry._LAYER_REGISTRY[name]


def _loss_of(build, recompute):
    """loss(params, x) over a one-layer network, and its parameters."""
    dsl.reset()
    x = dsl.data(name="x", size=_D, is_sequence=True)
    layer = build(x)
    dsl.current_graph().layers[layer.name].attrs["recompute"] = recompute
    net = Network(dsl.current_graph(), outputs=[layer.name])
    weight = jnp.asarray(
        np.random.RandomState(1).randn(_B, _T, _D), jnp.float32)

    def loss(params, xv):
        out = net.apply(params, {"x": Argument(value=xv)}, train=True,
                        rng=jax.random.PRNGKey(1))[layer.name].value
        return jnp.sum(out * weight)        # its backward needs `weight`

    return loss, net.init_params(jax.random.PRNGKey(0))


def _x():
    return jnp.asarray(np.random.RandomState(0).randn(_B, _T, _D),
                       jnp.float32)


def _grad(loss):
    return jax.grad(loss, argnums=(0, 1))


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in a jaxpr and every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, primitive)
    return n


def _kernels(loss, params, xv):
    """``pallas_call``s in the jaxpr of the loss's gradient."""
    return _count(jax.make_jaxpr(_grad(loss))(params, xv).jaxpr,
                  "pallas_call")


def _saved(loss, params, xv):
    """[(shape, dtype, where from)] of what the layer's backward pass is
    handed, the loss's own constant left out."""
    from jax._src.ad_checkpoint import saved_residuals
    return sorted((tuple(a.shape), str(a.dtype), why)
                  for a, why in saved_residuals(loss, params, xv)
                  if why != "from a constant")


def _bare_checkpoint(monkeypatch):
    """The executor's checkpoint as it was: no policy, inputs only."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


@pytest.mark.parametrize("dqk,dv", [(64, 64), (48, 32)])
@pytest.mark.parametrize("causal", [False, True])
def test_recomputed_attention_keeps_the_cores_output(causal, dqk, dv,
                                                     proj_flash_proj,
                                                     monkeypatch):
    def build(x):
        return dsl._add(LayerDef(
            name="attn", type=proj_flash_proj,
            inputs=[Input(x.name)], bias=False,
            attrs={"causal": causal, "dqk": dqk, "dv": dv}))

    xv = _x()
    with common.force_mode("interpret"):
        plain, params = _loss_of(build, False)
        remat, _ = _loss_of(build, True)
        # forward and the one backward kernel: the forward kernel is not
        # traced again
        assert _kernels(remat, params, xv) == 2
        assert _kernels(plain, params, xv) == 2
        g_plain = _grad(plain)(params, xv)
        g_remat = _grad(remat)(params, xv)
        saved = _saved(remat, params, xv)
        with monkeypatch.context() as m:
            _bare_checkpoint(m)
            bare, _ = _loss_of(build, True)
            assert _kernels(bare, params, xv) == 3
            g_bare = _grad(bare)(params, xv)
    for a, b, c in zip(jax.tree_util.tree_leaves(g_remat),
                       jax.tree_util.tree_leaves(g_plain),
                       jax.tree_util.tree_leaves(g_bare)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), np.asarray(c))
    # beside the arguments, the two named arrays and nothing else (JAX
    # hands on a residual that the forward pass also reads through an
    # identity `reduce_precision`, which takes the name's place in the
    # list: the core's output is the one such)
    inner = [s for s in saved if "from the argument" not in s[2]]
    assert sorted(s[0] for s in inner) == sorted(
        [(_B * _HEADS, _T), (_B * _HEADS, _T, dv)]), saved  # lse, out
    assert all(common.KEPT_RESIDUAL in s[2] or "reduce_precision" in s[2]
               for s in inner), saved
    assert any(common.KEPT_RESIDUAL in s[2] for s in inner), saved
    assert all(int(np.prod(s[0])) < _B * _HEADS * _T * _T
               for s in saved), saved


def test_recomputed_swiglu_saves_what_it_saved(monkeypatch):
    """A layer in which no kernel names a residual keeps its inputs,
    under the policy as under the bare checkpoint."""
    def build(x):
        return dsl.swiglu(x, hidden=48, name="mlp")

    xv = _x()
    remat, params = _loss_of(build, True)
    saved = _saved(remat, params, xv)
    with monkeypatch.context() as m:
        _bare_checkpoint(m)
        bare, _ = _loss_of(build, True)
        assert saved == _saved(bare, params, xv)
    assert saved and all("from the argument" in s[2] for s in saved), saved


@pytest.mark.parametrize("window", [32, None])
def test_recomputed_grouped_query_layer_keeps_the_cores_output(
        window, monkeypatch):
    """A ``gqa_attention`` layer (4 query heads over 2 key-value heads of
    16, sliding or full) under ``recompute``: beside its arguments it
    keeps the core's output and log-sum-exp and nothing else, so the
    gradient holds 2 ``pallas_call``s (forward, backward) where the bare
    checkpoint runs the forward kernel again; gradients as without
    ``recompute``. A sliding layer's counters come through the
    checkpoint."""
    heads, kv, hd = 4, 2, 16

    def build(x):
        return dsl.gqa_attention(x, num_heads=heads, num_kv_heads=kv,
                                 head_dim=hd, window=window, block=32,
                                 name="swa")

    xv = _x()
    with common.force_mode("interpret"):
        plain, params = _loss_of(build, False)
        remat, _ = _loss_of(build, True)
        assert _kernels(remat, params, xv) == 2
        assert _kernels(plain, params, xv) == 2
        g_plain = _grad(plain)(params, xv)
        g_remat = _grad(remat)(params, xv)
        saved = _saved(remat, params, xv)
        with monkeypatch.context() as m:
            _bare_checkpoint(m)
            bare, _ = _loss_of(build, True)
            assert _kernels(bare, params, xv) == 3
    for a, b in zip(jax.tree_util.tree_leaves(g_remat),
                    jax.tree_util.tree_leaves(g_plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    inner = [s for s in saved if "from the argument" not in s[2]]
    assert sorted(s[0] for s in inner) == sorted(
        [(_B * heads, _T), (_B * heads, _T, hd)]), saved    # lse, out
    assert any(common.KEPT_RESIDUAL in s[2] for s in inner), saved
    # the layer's counters are arrays of the checkpointed function
    dsl.reset()
    x = dsl.data(name="x", size=_D, is_sequence=True)
    layer = build(x)
    dsl.current_graph().layers[layer.name].attrs["recompute"] = True
    net = Network(dsl.current_graph(), outputs=[layer.name])
    out = net.apply(params, {"x": Argument(value=xv)}, train=True,
                    rng=jax.random.PRNGKey(1))[layer.name]
    if window:
        # 128 tokens, a window of 32 in tiles of 32: 7 of 16 tiles
        assert float(out.state["counters"]["swa_pairs_visited"]) \
            == 7 * 32 * 32
        assert float(out.state["counters"]["swa_pairs_visible"]) \
            == 32 * 33 // 2 + 96 * 32
    else:
        assert not out.state
