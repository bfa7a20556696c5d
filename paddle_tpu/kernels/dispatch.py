"""Trace-time flags for the fused-kernel plane (``docs/kernels.md``).

Two independent switches, both resolved at TRACE time (they pick which
program gets staged, never a runtime branch):

- fused RNN cells (``--fused_rnn`` / ``PADDLE_TPU_FUSED_RNN``, default
  OFF): routes the non-default-activation LSTM/GRU cell math in
  ``layers/recurrent.py`` through ``kernels.rnn_cells``. The
  default-activation sequence paths already run the fused
  ``ops.lstm/gru`` recurrences and are unaffected.
- fused optimizer update (``PADDLE_TPU_FUSED_OPTIM``, default ON):
  routes the dense Momentum/Adam elementwise chain in
  ``optim/optimizers.py`` through ``kernels.opt_update``. Off-TPU the
  fused entry falls straight back to ``Optimizer._apply_one`` — the
  selection is bitwise-invisible there by construction.

Pallas-vs-reference selection within the plane rides the shared
``ops/common.py`` policy (``use_pallas``/``force_mode``), same as every
other kernel in the tree.
"""

from __future__ import annotations

import contextlib
import os


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("", "0", "off", "false", "no")


_FUSED_RNN = _env_flag("PADDLE_TPU_FUSED_RNN", False)
_FUSED_OPT = _env_flag("PADDLE_TPU_FUSED_OPTIM", True)


def rnn_cells_enabled() -> bool:
    """Is the fused-RNN-cell switch on for the layer being traced? Never
    inside a partitioned step: the cell kernels sit in per-step scan
    bodies with no per-device wrapper, and a Mosaic kernel cannot be
    partitioned (``ops/common.py``, "the step mesh")."""
    from paddle_tpu.ops.common import partitioned
    return _FUSED_RNN and not partitioned()


def fused_optimizer_enabled() -> bool:
    return _FUSED_OPT


def set_fused_rnn(flag: bool) -> None:
    global _FUSED_RNN
    _FUSED_RNN = bool(flag)


def set_fused_optimizer(flag: bool) -> None:
    global _FUSED_OPT
    _FUSED_OPT = bool(flag)


@contextlib.contextmanager
def fused_rnn(flag: bool = True):
    """Scope the fused-RNN-cell switch (tests and bench A/B sides)."""
    global _FUSED_RNN
    prev, _FUSED_RNN = _FUSED_RNN, bool(flag)
    try:
        yield
    finally:
        _FUSED_RNN = prev


@contextlib.contextmanager
def fused_optimizer(flag: bool = True):
    """Scope the fused-optimizer switch (tests and bench A/B sides)."""
    global _FUSED_OPT
    prev, _FUSED_OPT = _FUSED_OPT, bool(flag)
    try:
        yield
    finally:
        _FUSED_OPT = prev
