"""Bitwise neutrality of the FSDP gather-overlap chain: overlap-on
training IS baseline training.

The r18 tentpole's acceptance bar: the double-buffered all-gather
spelling (``optim/zero1.py:FsdpUpdater.full_params`` — an
``optimization_barrier`` prefetch chain, identity on values) must not
change a single trained bit. Closure-enforced matrix (the
``test_exact_resume_matrix`` pattern): every overlap-relevant
composition feature — {fsdp, pipeline, grad_accum, telemetry, rnn} —
appears in at least one cell, and each cell trains both overlap arms on
the 8-device virtual mesh and demands final params, optimizer state and
RNG bit-identical to the off arm. The overlap arm uses
``fsdp_overlap="force"`` so the chain is actually staged on CPU (the
auto mode stands down off-TPU to keep audit compiles sync-spelled).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.config import dsl
from paddle_tpu.core.argument import Argument
from paddle_tpu.optim import Adam
from paddle_tpu.parallel import create_mesh
from paddle_tpu.trainer import SGD

WIDTH, CLASSES, B = 8, 3, 16
HID, T = 4, 5  # the rnn cell's lstm width / sequence length
BATCHES, PASSES = 4, 2

# cell -> {features}; the closure vocabulary
MATRIX = {
    "fsdp": {"fsdp"},
    "fsdp_rnn": {"fsdp", "rnn"},
    "fsdp_pipeline": {"fsdp", "pipeline"},
    "fsdp_grad_accum": {"fsdp", "grad_accum"},
    "fsdp_telemetry": {"fsdp", "telemetry"},
}
REQUIRED_FEATURES = {"fsdp", "pipeline", "grad_accum", "telemetry",
                     "rnn"}

HEALTH = {"period": 2, "sentry": True, "policy": "skip_batch"}


def test_matrix_closure():
    seen = set().union(*MATRIX.values())
    missing = REQUIRED_FEATURES - seen
    assert not missing, f"overlap matrix lost coverage for {missing}"
    assert all("fsdp" in f for f in MATRIX.values()), \
        "every cell must actually shard params (the overlap's subject)"
    assert any(len(f) >= 2 for f in MATRIX.values()), \
        "need at least one composed cell"


def _build(features, seed=5):
    dsl.reset()
    if "rnn" in features:
        # non-default activation: the lstmemory layer takes its inline
        # scan step, not ops/lstm.py
        x = dsl.data(name="x", size=4 * HID, is_sequence=True)
        lbl = dsl.data(name="label", size=CLASSES)
        r = dsl.lstmemory(input=x, act="relu")
        h = dsl.pooling(input=r, pooling_type="max")
        mesh = create_mesh(n_data=2, n_fsdp=2)
    elif "pipeline" in features:
        x = dsl.data(name="x", size=WIDTH)
        lbl = dsl.data(name="label", size=CLASSES)
        h = dsl.fc(input=x, size=WIDTH, act="tanh", name="blk0_0",
                   layer_attr={"device": 0})
        h = dsl.fc(input=h, size=WIDTH, act="tanh", name="blk1_0",
                   layer_attr={"device": 1})
        mesh = create_mesh(n_data=2, n_fsdp=2, n_pipe=2)
    else:
        x = dsl.data(name="x", size=WIDTH)
        lbl = dsl.data(name="label", size=CLASSES)
        h = dsl.fc(input=x, size=WIDTH, act="tanh")
        h = dsl.dropout(input=h, rate=0.25)
        mesh = create_mesh(n_data=2, n_fsdp=2)
    out = dsl.fc(input=h, size=CLASSES, act="softmax", name="out")
    cost = dsl.classification_cost(input=out, label=lbl)
    return SGD(cost=cost, update_equation=Adam(learning_rate=3e-3),
               mesh=mesh, seed=seed)


def _reader(features):
    rng = np.random.RandomState(11)
    if "rnn" in features:
        X = rng.randn(BATCHES * B, T, 4 * HID).astype(np.float32)
        Y = rng.randint(0, CLASSES, size=BATCHES * B).astype(np.int32)
        mask = np.ones((B, T), np.float32)

        def reader():
            for i in range(0, BATCHES * B, B):
                yield {"x": Argument(value=jnp.asarray(X[i:i + B]),
                                     mask=jnp.asarray(mask)),
                       "label": Argument(value=jnp.asarray(Y[i:i + B]))}

        return reader
    X = rng.randn(BATCHES * B, WIDTH).astype(np.float32)
    W = rng.randn(WIDTH, CLASSES)
    Y = np.argmax(X @ W, axis=1).astype(np.int32)

    def reader():
        for i in range(0, BATCHES * B, B):
            yield {"x": Argument(value=jnp.asarray(X[i:i + B])),
                   "label": Argument(value=jnp.asarray(Y[i:i + B]))}

    return reader


def _train_kwargs(features, overlap):
    kw = {"fsdp": True,
          "fsdp_overlap": "force" if overlap else False}
    if "grad_accum" in features:
        kw["grad_accum_steps"] = 2
    if "pipeline" in features:
        kw["pipeline"] = True
    if "telemetry" in features:
        kw["health"] = HEALTH
    return kw


def _final_state(tr):
    from paddle_tpu.trainer.checkpoint import _flatten
    params = {k: np.asarray(jax.device_get(v))
              for k, v in tr._params_for_save().items()}
    opt = _flatten(tr._opt_state_for_save())
    return params, opt, np.asarray(jax.device_get(tr._rng))


def _run_arm(features, overlap):
    tr = _build(features)
    reader = _reader(features)
    kw = _train_kwargs(features, overlap)
    for _ in range(PASSES):
        tr.train(reader, num_passes=1, **kw)
    assert tr._fsdp is not None, "fsdp stood down in-matrix"
    assert len(tr._fsdp.plan) >= 2, \
        "nothing to double-buffer — the cell no longer tests the chain"
    assert tr._fsdp.overlap_mode == ("force" if overlap else False)
    sb = tr.step_breakdown()
    if overlap:
        # the chain's structural claim: only the first gather and the
        # last reduce are exposed, whatever the composition
        assert sb["fsdp_exposed_collectives"] == 2
    else:
        assert (sb["fsdp_exposed_collectives"]
                == 2 * sb["fsdp_gathers_per_step"])
    return _final_state(tr)


@pytest.mark.parametrize("cell", sorted(MATRIX), ids=sorted(MATRIX))
def test_overlap_is_bitwise_neutral(cell):
    features = MATRIX[cell]
    want_params, want_opt, want_rng = _run_arm(features, False)
    got_params, got_opt, got_rng = _run_arm(features, True)
    tag = f"{cell}[overlap=True]"
    assert set(got_params) == set(want_params), tag
    for k in want_params:
        np.testing.assert_array_equal(
            got_params[k], want_params[k],
            err_msg=f"{tag}: param {k} diverged")
    assert set(got_opt) == set(want_opt), tag
    for k in want_opt:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(got_opt[k])),
            np.asarray(jax.device_get(want_opt[k])),
            err_msg=f"{tag}: opt slot {k} diverged")
    np.testing.assert_array_equal(got_rng, want_rng,
                                  err_msg=f"{tag}: rng diverged")
