"""Optimizer semantics tests — the analogue of
``paddle/math/tests/test_TrainingAlgorithm.cpp``, which checks the fused
kernels against reference implementations (``OriginalOptimizerApi.h``):
here each Optimizer is checked against a hand-written numpy step of the
formulas in TrainingAlgorithmOp.cu."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.optim import (AdaDelta, AdaGrad, Adam, Adamax,
                              DecayedAdaGrad, Momentum, RMSProp,
                              create_optimizer)


def _run(opt, p0, grads_seq):
    params = {"w": jnp.asarray(p0)}
    state = opt.init(params)
    for g in grads_seq:
        params, state = opt.update({"w": jnp.asarray(g)}, state, params,
                                   batch_size=4)
    return np.asarray(params["w"]), state


def test_momentum_matches_reference_formula():
    p0 = np.array([1.0, -2.0, 3.0], np.float32)
    gs = [np.array([0.1, 0.2, -0.3], np.float32),
          np.array([-0.05, 0.1, 0.2], np.float32)]
    lr, mu, decay = 0.1, 0.9, 0.01
    opt = Momentum(learning_rate=lr, momentum=mu, l2_rate=decay)
    got, _ = _run(opt, p0, gs)
    # sgdUpdate: mom = mu*mom - lr*(g + decay*p); p += mom
    p, mom = p0.copy(), np.zeros_like(p0)
    for g in gs:
        mom = mu * mom - lr * (g + decay * p)
        p = p + mom
    np.testing.assert_allclose(got, p, rtol=1e-6)


def test_adagrad_formula():
    p0 = np.array([0.5, -0.5], np.float32)
    gs = [np.array([0.3, -0.1], np.float32),
          np.array([0.2, 0.4], np.float32)]
    opt = AdaGrad(learning_rate=0.1, epsilon=1e-6)
    got, _ = _run(opt, p0, gs)
    p, accum, mom = p0.copy(), np.zeros_like(p0), np.zeros_like(p0)
    for g in gs:
        accum = accum + g * g
        lr_vec = 1.0 / np.sqrt(accum + 1e-6)
        mom = 0.0 * mom - 0.1 * lr_vec * g
        p = p + mom
    np.testing.assert_allclose(got, p, rtol=1e-5)


def test_adam_formula():
    p0 = np.array([1.0, 2.0], np.float32)
    gs = [np.array([0.1, -0.2], np.float32)] * 3
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 0.01
    opt = Adam(learning_rate=lr, beta1=b1, beta2=b2, epsilon=eps)
    got, _ = _run(opt, p0, gs)
    p, m, v = p0.copy(), np.zeros_like(p0), np.zeros_like(p0)
    for t, g in enumerate(gs, start=1):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        alpha = lr * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        p = p - alpha * m / (np.sqrt(v) + eps)
    np.testing.assert_allclose(got, p, rtol=1e-5)


def test_rmsprop_formula():
    p0 = np.array([0.3, -0.7], np.float32)
    gs = [np.array([0.2, 0.1], np.float32),
          np.array([-0.1, 0.3], np.float32)]
    rou, eps, lr = 0.95, 1e-6, 0.05
    opt = RMSProp(learning_rate=lr, rou=rou, epsilon=eps)
    got, _ = _run(opt, p0, gs)
    p = p0.copy()
    G = np.zeros_like(p0); F = np.zeros_like(p0); mom = np.zeros_like(p0)
    for g in gs:
        G = rou * G + (1 - rou) * g * g
        F = rou * F + (1 - rou) * g
        scale = 1.0 / np.sqrt(G - F * F + eps)
        mom = 0.0 * mom - lr * scale * g
        p = p + mom
    np.testing.assert_allclose(got, p, rtol=1e-5)


def test_l1_shrink():
    opt = Momentum(learning_rate=0.1, l1_rate=0.5)
    p0 = np.array([0.04, -0.03, 1.0], np.float32)
    got, _ = _run(opt, p0, [np.zeros(3, np.float32)])
    # after zero-grad step, |p| shrinks by l1*lr = 0.05, clamped at 0
    np.testing.assert_allclose(got, [0.0, 0.0, 0.95], atol=1e-6)


def test_static_params_skipped():
    opt = Momentum(learning_rate=1.0)
    from paddle_tpu.core.registry import ParamSpec
    params = {"w": jnp.ones(3), "frozen": jnp.ones(3)}
    meta = {"w": ParamSpec(shape=(3,)),
            "frozen": ParamSpec(shape=(3,), is_static=True)}
    state = opt.init(params, meta)
    assert "frozen" not in state["slots"]
    new_p, _ = opt.update({"w": jnp.ones(3), "frozen": jnp.ones(3)},
                          state, params, meta)
    np.testing.assert_allclose(np.asarray(new_p["frozen"]), 1.0)
    assert not np.allclose(np.asarray(new_p["w"]), 1.0)


def test_lr_schedules():
    from paddle_tpu.optim.schedules import learning_rate_at
    assert float(learning_rate_at("constant", 0.1, 0, 0, 100)) == pytest.approx(0.1)
    assert float(learning_rate_at("poly", 0.1, 0.01, 0.5, 100)) == pytest.approx(
        0.1 * (1 + 0.01 * 100) ** -0.5)
    assert float(learning_rate_at("linear", 0.1, 1e-4, 0.01, 500)) == pytest.approx(
        0.1 - 1e-4 * 500)
    assert float(learning_rate_at("discexp", 0.1, 0.5, 100, 250)) == pytest.approx(
        0.1 * 0.5 ** 2)


def test_factory():
    assert isinstance(create_optimizer("adam", learning_rate=0.1), Adam)
    assert isinstance(create_optimizer("sgd"), Momentum)
    with pytest.raises(KeyError):
        create_optimizer("nope")


def test_model_averaging():
    opt = Momentum(learning_rate=0.1, average_window=2.0)
    p0 = np.array([1.0], np.float32)
    got, state = _run(opt, p0, [np.array([1.0], np.float32)] * 3)
    assert "avg" in state
    assert np.isfinite(np.asarray(state["avg"]["w"])).all()


def test_manual_schedule_piecewise():
    from paddle_tpu.optim.schedules import learning_rate_at
    # boundaries at 100 and 200 samples; factors 1.0 / 0.5 / 0.1
    lr = learning_rate_at("manual", 0.2, 0, 0, 50, args="100:1.0,200:0.5,300:0.1")
    np.testing.assert_allclose(float(lr), 0.2, rtol=1e-6)
    lr = learning_rate_at("manual", 0.2, 0, 0, 150, args="100:1.0,200:0.5,300:0.1")
    np.testing.assert_allclose(float(lr), 0.1, rtol=1e-6)
    lr = learning_rate_at("manual", 0.2, 0, 0, 999, args="100:1.0,200:0.5,300:0.1")
    np.testing.assert_allclose(float(lr), 0.02, rtol=1e-6)


def test_pass_manual_schedule():
    from paddle_tpu.optim.schedules import learning_rate_at
    lr = learning_rate_at("pass_manual", 1.0, 0, 0, 0,
                          args="1:1.0,2:0.5", num_passes=0)
    assert float(lr) == 1.0
    lr = learning_rate_at("pass_manual", 1.0, 0, 0, 0,
                          args="1:1.0,2:0.5", num_passes=5)
    assert float(lr) == 0.5


def test_nesterov_momentum_differs_and_converges():
    p0 = np.array([1.0, -1.0], np.float32)
    gs = [p0.copy() * 0.5] * 5
    plain, _ = _run(Momentum(learning_rate=0.1, momentum=0.9), p0, gs)
    nest, _ = _run(Momentum(learning_rate=0.1, momentum=0.9, nesterov=True),
                   p0, gs)
    assert not np.allclose(plain, nest)


def test_model_averaging_apply():
    opt = Momentum(learning_rate=0.5, average_window=10)
    params = {"w": jnp.asarray(np.array([0.0], np.float32))}
    state = opt.init(params)
    for _ in range(4):
        params, state = opt.update(
            {"w": jnp.asarray(np.array([1.0], np.float32))}, state, params)
    avg = opt.averaged_params(state, params)
    # averaged value lags the raw trained value (running mean of iterates)
    assert float(avg["w"][0]) > float(params["w"][0])
    assert float(avg["w"][0]) < 0.0  # moved in the gradient direction


def test_model_averaging_fractional_window_is_not_a_noop():
    """The reference's average_window is a FRACTION of updates so far
    (TrainerConfig.proto:70-74; ModelAverage(average_window=0.5) is the
    normal v1 usage) — the averaged params must lag the raw iterates,
    not equal them."""
    opt = Momentum(learning_rate=0.5, average_window=0.5)
    params = {"w": jnp.asarray(np.array([0.0], np.float32))}
    state = opt.init(params)
    for _ in range(8):
        params, state = opt.update(
            {"w": jnp.asarray(np.array([1.0], np.float32))}, state, params)
    avg = opt.averaged_params(state, params)
    assert float(avg["w"][0]) > float(params["w"][0]) + 1e-4  # lags
    assert float(avg["w"][0]) < 0.0


def test_update_with_partial_grads_keeps_other_slots():
    """An update carrying gradients for a SUBSET of parameters must not
    erase the others' optimizer state (momentum history stays intact and
    later full updates keep working)."""
    opt = Momentum(learning_rate=0.1, momentum=0.9)
    params = {"a": jnp.zeros(2), "b": jnp.zeros(2)}
    state = opt.init(params)
    g = jnp.ones(2)
    params, state = opt.update({"a": g, "b": g}, state, params)
    mom_b = np.asarray(state["slots"]["b"]["mom"]).copy()
    params, state = opt.update({"a": g}, state, params)  # subset
    assert "b" in state["slots"], "b's slots erased by a partial update"
    np.testing.assert_allclose(np.asarray(state["slots"]["b"]["mom"]),
                               mom_b)
    params2, state = opt.update({"a": g, "b": g}, state, params)
    assert float(params2["b"][0]) != float(params["b"][0])  # still trains


def test_static_pruning_hook_keeps_weights_zero():
    """StaticPruningHook (ParameterUpdaterHook.cpp:39): the smallest-|w|
    fraction is masked at init and stays exactly zero through updates."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.core.registry import ParamSpec
    from paddle_tpu.optim.optimizers import Momentum

    rng = np.random.RandomState(0)
    p0 = rng.randn(16, 8).astype(np.float32)
    meta = {"w": ParamSpec(shape=(16, 8), sparsity_ratio=0.5)}
    opt = Momentum(learning_rate=0.1, momentum=0.9)
    params = {"w": jnp.asarray(p0)}
    state = opt.init(params, meta)
    mask = np.asarray(state["slots"]["w"]["prune_mask"])
    assert abs(mask.mean() - 0.5) < 0.1  # ~half pruned
    for _ in range(5):
        g = jnp.asarray(rng.randn(16, 8).astype(np.float32))
        params, state = opt.update({"w": g}, state, params, meta,
                                   batch_size=4)
    w = np.asarray(params["w"])
    assert np.all(w[mask == 0] == 0.0)      # pruned stay zero
    assert np.any(w[mask == 1] != p0[mask == 1])  # others trained


def test_pruning_hook_via_v1_config_attr():
    """ParameterAttribute(update_hooks=HookAttribute('pruning', r)) flows
    through the compat surface into the engine ParamSpec."""
    from paddle_tpu.compat.trainer_config_helpers.attrs import (
        HookAttribute, ParameterAttribute)
    attr = ParameterAttribute(
        update_hooks=HookAttribute("pruning", sparsity_ratio=0.7))
    assert attr.to_param_attr().sparsity_ratio == 0.7


_CHAIN_CASES = {
    "plain": {},
    "l2": {"l2_rate": 8e-4},
    "clipping": {"gradient_clipping_threshold": 0.5},
    "l1": {"l1_rate": 0.05},
    "prune_mask": {"mask": True},
    "all": {"l2_rate": 8e-4, "gradient_clipping_threshold": 0.5,
            "l1_rate": 0.05, "mask": True},
}


@pytest.mark.parametrize("case", sorted(_CHAIN_CASES))
@pytest.mark.parametrize("kind", ["adam", "momentum"])
def test_update_param_is_the_numpy_chain(kind, case):
    """``_update_param``'s dense branch, whole: value clipping, L2 inside
    the gradient, the optimizer's chain in the leaf's own shape, the l1
    shrink and the prune mask, against the same chain spelled in NumPy
    float32. There is one spelling of the dense update (no kernel stands
    in for ``_apply_one``), so this is what every leaf of every step
    runs."""
    from paddle_tpu.core.registry import ParamSpec
    opts = dict(_CHAIN_CASES[case])
    masked = opts.pop("mask", False)
    opt = (Adam(learning_rate=0.1, beta1=0.9, beta2=0.95, **opts)
           if kind == "adam"
           else Momentum(learning_rate=0.1, momentum=0.9, **opts))
    rng = np.random.RandomState(3)
    f32 = np.float32
    p, g, m, v = (rng.randn(3, 5, 7).astype(f32) for _ in range(4))
    v = np.abs(v)
    mask = (rng.rand(3, 5, 7) > 0.4).astype(f32)
    slots = {"mom": m} if kind == "momentum" else {"mom": m, "v": v}
    if masked:
        slots["prune_mask"] = mask
    lr_t, lr_mult, t = f32(0.05), 0.5, 3
    spec = ParamSpec(shape=p.shape, learning_rate=lr_mult)

    got_p, got_s = opt._update_param(
        jnp.asarray(g), jnp.asarray(p),
        {k: jnp.asarray(a) for k, a in slots.items()}, spec,
        jnp.float32(lr_t), jnp.int32(t))

    lr = f32(lr_t * f32(lr_mult))
    th = opt.gradient_clipping_threshold
    gc = np.clip(g, -th, th) if th > 0 else g
    gd = gc + f32(opt.l2_rate) * p
    if kind == "momentum":
        want_s = {"mom": f32(0.9) * m - lr * gd}
        want_p = p + want_s["mom"]
    else:
        b1, b2 = f32(0.9), f32(0.95)
        want_s = {"mom": b1 * m + (f32(1) - b1) * gd,
                  "v": b2 * v + (f32(1) - b2) * np.square(gd)}
        alpha = lr * np.sqrt(f32(1) - b2 ** f32(t)) / (f32(1) - b1 ** f32(t))
        want_p = p - alpha * want_s["mom"] / (
            np.sqrt(want_s["v"]) + f32(opt.epsilon))
    if opt.l1_rate > 0:
        want_p = np.sign(want_p) * np.maximum(
            np.abs(want_p) - f32(opt.l1_rate) * lr, f32(0))
    if masked:
        want_p = want_p * mask
        want_s["prune_mask"] = mask

    assert set(got_s) == set(want_s)
    np.testing.assert_allclose(np.asarray(got_p), want_p,
                               rtol=2e-6, atol=1e-7)
    for k, want in want_s.items():
        np.testing.assert_allclose(np.asarray(got_s[k]), want,
                                   rtol=2e-6, atol=1e-7)
    if masked:
        assert np.all(np.asarray(got_p)[mask == 0] == 0.0)
