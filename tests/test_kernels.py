"""The one kernel plane (``paddle_tpu/ops/``, ``parallel/moe.py``) and
its one policy (``ops/common.py``).

Contract from ``docs/kernels.md``:

- every kernel entry decides by ``common.mode()`` alone: its reference
  under ``force_mode("ref")``, its kernel under
  ``force_mode("interpret")`` at a shape inside the budget, and
  ``record_dispatch`` shows which — read here for every entry under
  both modes in one place (each kernel's parity with its reference is
  in its own file: ``tests/test_ops_pallas.py``, ``tests/test_moe.py``).
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import common


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


def _call_flash_backward():
    from paddle_tpu.ops import flash_attention
    r, shape = _rng(2), (1, 2, 32, 8)
    jax.grad(lambda q: flash_attention(
        q, _f32(r, *shape), _f32(r, *shape), jnp.ones((1, 32)), causal=True,
        block_q=16, block_k=16).sum())(_f32(r, *shape))


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


# entry -> (its name in the tally, a call at a shape inside the budget,
# the name its reference path notes, the name its kernel notes).
# `flash_backward` is noted where the kernels' backward rule is traced,
# beside the forward's note: the reference path is differentiated by JAX
# and notes no backward. `moe_gmm_tiles` is noted beside
# `moe_grouped_matmul`'s kernel path, one a megablox call, with the
# call's tiles
ENTRIES = {
    "lstm": ("lstm", _call_lstm, "ref", "resident"),
    "gru": ("gru", _call_gru, "ref", "interpret"),
    "flash_attention": ("flash_attention", _call_flash_attention,
                        "ref", "interpret"),
    "flash_backward": ("flash_backward", _call_flash_backward,
                       None, "fused"),
    "crf": ("crf", _call_crf, "ref", "interpret"),
    "ctc": ("ctc", _call_ctc, "ref", "interpret"),
    "moe_grouped_matmul": ("moe_grouped_matmul", _call_grouped_matmul,
                           "ref", "interpret"),
    "moe_gmm_tiles": ("moe_gmm_tiles", _call_grouped_matmul,
                      None, "fwd 128x128x128"),
}
# an entry noted beside another's note -> that other
BESIDE = {"flash_backward": "flash_attention",
          "moe_gmm_tiles": "moe_grouped_matmul"}


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
    if name in BESIDE:
        assert tally.pop(BESIDE[name]) == {mode: 1}
    if name == "moe_grouped_matmul" and mode == "interpret":
        assert tally.pop("moe_gmm_tiles") == {"fwd 128x128x128": 1}, tally
    assert set(tally) == ({name} if want else set()), tally
    assert want is None or set(tally[name]) == {want}, tally


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
