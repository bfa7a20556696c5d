"""``correct`` has to come out false for the control and for each fault
a training cell can have, with the rest of a run driven as it is."""

import os

import pytest

from benchmark import control, run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "BENCHMARK.tiny.json")
LSTM, RESNET, DP4 = ("lstm_tiny.tiny_train_bs8", "resnet_tiny.tiny_train_bs8",
                     "lstm_tiny.tiny_train_dp4_bs16")


def _run(cell, **kw):
    return run.run_cell(cell, 77, 0.2, False, bench_file=TINY,
                        on_chip=False, **kw)


@pytest.mark.parametrize("cell", [LSTM, RESNET])
def test_the_lower_precision_control_is_not_correct(cell):
    result = _run(cell, control=True)
    assert result["correct"] is False
    assert any(v > limit for v, limit in result["compared"].values())


@pytest.mark.parametrize("cell,fault", [
    (LSTM, "unchanged"), (LSTM, "half"), (RESNET, "half"), (DP4, "half")])
def test_a_fault_in_the_timed_path_is_not_correct(cell, fault):
    result = _run(cell, tamper=control.FAULTS[fault])
    assert result["correct"] is False


def test_a_state_left_unchanged_reads_one():
    result = _run(LSTM, tamper=control.unchanged)
    assert result["numbers"]["change_median"] == pytest.approx(1.0)
    # no first moment: what is worked out from the state is the L2 term
    # alone, nowhere near the reference's gradient
    assert result["numbers"]["grad_median"] > 0.5


def test_a_cost_that_is_not_finite_is_not_correct():
    def poison(prog):
        step = prog.trainer._train_step

        def faulty(*args):
            p, o, metrics = step(*args)
            return p, o, dict(metrics, cost=metrics["cost"] * float("nan"))

        prog.trainer._train_step = faulty

    result = _run(LSTM, tamper=poison)
    assert result["correct"] is False and result["failed"] >= 1


def test_the_one_pass_product_rounds_both_operands_in_both_passes():
    import jax
    import jax.numpy as jnp
    from benchmark.reference import plain
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.normal(k[0], (5, 7, 16))
    b = jax.random.normal(k[1], (16, 12))
    g = jax.random.normal(k[2], (5, 7, 12))

    def r(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def hi(x, y):
        return jnp.matmul(x, y, precision=plain.HIGHEST)

    arith = plain.Arith(product="bfloat16")
    out, vjp = jax.vjp(arith.mm, a, b)
    da, db = vjp(g)
    assert jnp.array_equal(out, hi(r(a), r(b)))
    assert jnp.allclose(da, hi(r(g), r(b).T), rtol=1e-6, atol=1e-6)
    assert jnp.allclose(db, hi(r(a).reshape(-1, 16).T, r(g).reshape(-1, 12)),
                        rtol=1e-6, atol=1e-6)
    # and it is not the float32 product: about 2^-9 away
    exact = hi(a, b)
    gap = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < gap < 1e-2


def test_the_reference_computes_in_what_the_configuration_states():
    import json
    from benchmark import check
    from benchmark.reference import plain
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "lstm_imdb_h1280.json")) as f:
        cfg = json.load(f)
    assert check.stated_arith(cfg) == plain.Arith(product="bfloat16")
    with open(os.path.join(HERE, "configs", "lstm_tiny.json")) as f:
        assert check.stated_arith(json.load(f)) == plain.Arith()
