"""The one kernel plane (``paddle_tpu/ops/``, ``parallel/moe.py``) and
its one policy (``ops/common.py``), plus the fused optimizer update's
parity tests.

Contracts from ``docs/kernels.md``:

- every kernel entry decides by ``common.mode()`` alone: its reference
  under ``force_mode("ref")``, its kernel under
  ``force_mode("interpret")`` at a shape inside the budget, and
  ``record_dispatch`` shows which — read here for every entry under
  both modes in one place (each kernel's parity with its reference is
  in its own file: ``tests/test_ops_pallas.py``, ``tests/test_moe.py``);
- the Pallas spelling of the optimizer chains (run here in interpreter
  mode on the CPU) matches ``Optimizer._apply_one`` to float32
  roundoff, and where the kernels do not apply the routing IS
  ``_apply_one``, bit for bit.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import common, opt_update
from paddle_tpu.optim.optimizers import AdaGrad, Adam, Momentum


def _rng(seed=0):
    return np.random.RandomState(seed)


# ------------------------------------------------------ the one policy

def _f32(r, *shape, scale=1.0):
    return jnp.asarray(r.randn(*shape).astype(np.float32) * scale)


def _call_lstm():
    from paddle_tpu.ops import lstm_sequence
    r, (T, B, H) = _rng(0), (3, 4, 8)
    lstm_sequence(_f32(r, T, B, 4 * H), jnp.ones((T, B)),
                  _f32(r, H, 4 * H, scale=0.1), _f32(r, 4 * H, scale=0.1),
                  *(_f32(r, H, scale=0.1) for _ in range(3)),
                  _f32(r, B, H), _f32(r, B, H))


def _call_gru():
    from paddle_tpu.ops import gru_sequence
    r, (T, B, H) = _rng(1), (3, 3, 8)
    gru_sequence(_f32(r, T, B, 3 * H), jnp.ones((T, B)),
                 _f32(r, H, 2 * H, scale=0.2), _f32(r, H, H, scale=0.2),
                 _f32(r, 3 * H, scale=0.1), _f32(r, B, H))


def _call_flash_attention():
    from paddle_tpu.ops import flash_attention
    r, shape = _rng(2), (1, 2, 32, 8)
    flash_attention(_f32(r, *shape), _f32(r, *shape), _f32(r, *shape),
                    jnp.ones((1, 32)), causal=True, block_q=16, block_k=16)


def _call_crf():
    from paddle_tpu.ops import crf_log_z
    r, (B, T, C) = _rng(3), (2, 5, 9)
    crf_log_z(_f32(r, B, T, C), jnp.ones((B, T)), _f32(r, C, C),
              _f32(r, C), _f32(r, C))


def _call_ctc():
    from paddle_tpu.layers.chain import ctc_loss
    r, (B, T, C, L) = _rng(4), (2, 9, 6, 3)
    ctc_loss(jax.nn.log_softmax(_f32(r, B, T, C), axis=-1),
             jnp.asarray(r.randint(0, C - 1, size=(B, L)), jnp.int32),
             jnp.ones((B, T)), jnp.ones((B, L)), blank=C - 1)


def _call_grouped_matmul():
    from paddle_tpu.parallel.moe import grouped_matmul
    r = _rng(5)
    grouped_matmul(_f32(r, 128, 128), _f32(r, 2, 128, 128),
                   jnp.asarray([80, 40], jnp.int32))


def _call_momentum():
    p, g, m, _ = _opt_operands()
    opt_update.apply_one(Momentum(learning_rate=0.1, momentum=0.9), p, g,
                         {"mom": m}, jnp.float32(0.05), 1e-4, jnp.int32(3))


def _call_adam():
    p, g, m, v = _opt_operands(4)
    opt_update.apply_one(Adam(learning_rate=0.1), p, g,
                         {"mom": m, "v": jnp.abs(v)}, jnp.float32(0.02),
                         1e-4, jnp.int32(7))


# entry -> (its name in the tally, a call at a shape inside the budget,
# the name its reference path notes, the name its kernel notes)
ENTRIES = {
    "lstm": ("lstm", _call_lstm, "ref", "resident"),
    "gru": ("gru", _call_gru, "ref", "interpret"),
    "flash_attention": ("flash_attention", _call_flash_attention,
                        "ref", "interpret"),
    "crf": ("crf", _call_crf, "ref", "interpret"),
    "ctc": ("ctc", _call_ctc, "ref", "interpret"),
    "moe_grouped_matmul": ("moe_grouped_matmul", _call_grouped_matmul,
                           "ref", "interpret"),
    "opt_update_momentum": ("opt_update", _call_momentum,
                            "apply_one", "fused"),
    "opt_update_adam": ("opt_update", _call_adam, "apply_one", "fused"),
}


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_every_kernel_entry_follows_the_one_policy(entry, mode,
                                                   monkeypatch):
    """``ops/common.py`` is the only switch: under ``force_mode`` every
    entry that notes a dispatch takes the path the mode names, whatever
    the environment of the deleted second dispatcher says."""
    monkeypatch.setenv("PADDLE_TPU_FUSED_RNN", "0")
    monkeypatch.setenv("PADDLE_TPU_FUSED_OPTIM", "0")
    name, call, ref_path, kernel_path = ENTRIES[entry]
    with common.force_mode(mode), common.record_dispatch() as tally:
        call()
    want = ref_path if mode == "ref" else kernel_path
    assert set(tally) == {name}, tally
    assert set(tally[name]) == {want}, tally


def test_the_policy_test_covers_every_noting_entry():
    """``ENTRIES`` names every kernel that calls ``common.note``: a new
    entry joins the one policy's test with its first dispatch."""
    from paddle_tpu.analysis.ast_lints import _iter_source_files
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    noted = set()
    for path in _iter_source_files(root, ("paddle_tpu",)):
        with open(path, encoding="utf-8") as f:
            noted |= set(re.findall(r'common\.note\(\s*"(\w+)"', f.read()))
    assert noted == {e[0] for e in ENTRIES.values()}


# -------------------------------------------- optimizer kernel parity

def _opt_operands(seed=0, shape=(13, 7)):
    r = _rng(seed)
    mk = lambda: jnp.asarray(r.randn(*shape).astype(np.float32))
    return mk(), mk(), mk(), mk()  # p, g, mom, v


def test_momentum_fused_interpret_matches_apply_one():
    opt = Momentum(learning_rate=0.1, momentum=0.9)
    p, g, m, _ = _opt_operands()
    lr = jnp.float32(0.05)
    t = jnp.int32(3)
    ref_p, ref_s = opt._apply_one(p, g, {"mom": m}, lr, 1e-4, t)
    with common.force_mode("interpret"):
        got_p, got_s = opt_update.apply_one(opt, p, g, {"mom": m},
                                            lr, 1e-4, t)
    assert set(got_s) == set(ref_s) == {"mom"}
    np.testing.assert_allclose(got_p, ref_p, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(got_s["mom"], ref_s["mom"],
                               rtol=1e-6, atol=1e-7)


def test_adam_fused_interpret_matches_apply_one():
    opt = Adam(learning_rate=0.1)
    p, g, m, v = _opt_operands(4)
    v = jnp.abs(v)  # second-moment slots are non-negative
    lr = jnp.float32(0.02)
    t = jnp.int32(7)
    ref_p, ref_s = opt._apply_one(p, g, {"mom": m, "v": v}, lr, 1e-4, t)
    with common.force_mode("interpret"):
        got_p, got_s = opt_update.apply_one(
            opt, p, g, {"mom": m, "v": v}, lr, 1e-4, t)
    assert set(got_s) == set(ref_s) == {"mom", "v"}
    np.testing.assert_allclose(got_p, ref_p, rtol=1e-6, atol=1e-7)
    for k in ref_s:
        np.testing.assert_allclose(got_s[k], ref_s[k],
                                   rtol=1e-6, atol=1e-7)


def test_fused_optimizer_fallback_is_apply_one_bitwise():
    """Off-TPU (mode 'ref') the routing is the identity: apply_one
    returns exactly what _apply_one returns, bit for bit."""
    opt = Adam(learning_rate=0.1)
    p, g, m, v = _opt_operands(5)
    v = jnp.abs(v)
    lr = jnp.float32(0.02)
    t = jnp.int32(2)
    with common.force_mode("ref"):
        got_p, got_s = opt_update.apply_one(
            opt, p, g, {"mom": m, "v": v}, lr, 0.0, t)
    ref_p, ref_s = opt._apply_one(p, g, {"mom": m, "v": v}, lr, 0.0, t)
    assert np.array_equal(np.asarray(got_p), np.asarray(ref_p))
    for k in ref_s:
        assert np.array_equal(np.asarray(got_s[k]), np.asarray(ref_s[k]))


@pytest.mark.parametrize("case", ["nesterov", "adagrad_slots",
                                  "not_float32", "reference_mode"])
def test_ineligible_shapes_route_to_apply_one(case):
    """Nesterov momentum, a slot set the kernels do not know, operands
    that are not float32 and the reference mode all take the optimizer's
    own _apply_one: same bits, and the tally says so."""
    p, g, m, v = _opt_operands(6)
    lr, t, mode = jnp.float32(0.05), jnp.int32(1), "interpret"
    opt, slots = Momentum(learning_rate=0.1, momentum=0.9), {"mom": m}
    if case == "nesterov":
        opt = Momentum(learning_rate=0.1, momentum=0.9, nesterov=True)
    elif case == "adagrad_slots":
        opt, slots = AdaGrad(learning_rate=0.1), {"mom": m,
                                                  "accum": jnp.abs(v)}
    elif case == "not_float32":
        p, g = p.astype(jnp.bfloat16), g.astype(jnp.bfloat16)
        slots = {"mom": m.astype(jnp.bfloat16)}
    else:
        mode = "ref"
    with common.force_mode(mode), common.record_dispatch() as tally:
        got = opt_update.apply_one(opt, p, g, slots, lr, 0.0, t)
    assert tally == {"opt_update": {"apply_one": 1}}
    ref = opt._apply_one(p, g, slots, lr, 0.0, t)
    assert np.array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    assert set(got[1]) == set(ref[1])
    for k in ref[1]:
        assert np.array_equal(np.asarray(got[1][k]), np.asarray(ref[1][k]))


def test_prune_mask_slot_rides_through_fused_path():
    """A prune_mask slot must not break eligibility (the mask is the
    CALLER's to re-apply, matching _apply_one's contract) and must not
    appear in the fused path's returned slots."""
    opt = Momentum(learning_rate=0.1, momentum=0.9)
    p, g, m, _ = _opt_operands(7)
    mask = jnp.ones_like(p)
    lr = jnp.float32(0.05)
    t = jnp.int32(1)
    with common.force_mode("interpret"):
        got_p, got_s = opt_update.apply_one(
            opt, p, g, {"mom": m, "prune_mask": mask}, lr, 0.0, t)
    ref_p, ref_s = opt._apply_one(
        p, g, {"mom": m, "prune_mask": mask}, lr, 0.0, t)
    assert set(got_s) == set(ref_s) == {"mom"}
    np.testing.assert_allclose(got_p, ref_p, rtol=1e-6, atol=1e-7)
