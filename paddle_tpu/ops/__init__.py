"""Pallas TPU kernel library — the framework's `paddle/cuda` equivalent.

The reference ships a hand-written device kernel library (`paddle/cuda`:
fused LSTM/GRU cell kernels `hl_gpu_lstm.cuh` / `hl_gru_ops.cuh`, sequence
scatter/gather `hl_sequence.h`, top-k `hl_top_k.h`) under the C `hl_*` API
with CPU stubs so GPU-less builds still run.  Here the same role is played
by Pallas TPU kernels with two fallback tiers:

- on TPU: the Pallas kernel (compiled by Mosaic, data staged through VMEM);
- elsewhere (CPU test meshes): either the kernel under ``interpret=True``
  or a pure ``lax.scan``/``jnp`` reference — the reference implementations
  are also the ground truth the kernels are unit-tested against.

Selection is automatic (see ``common.use_pallas``); nothing else in the
framework needs to know which tier ran. ``common`` is the one policy for
every kernel in the tree (``docs/kernels.md``): the entries here and the
grouped products of ``parallel/moe.py``. The optimizers' element-wise
updates are no kernel: the compiler's own loop fusion makes one pass of
each (``optim/optimizers.py``).
"""

from paddle_tpu.ops.common import use_pallas, force_mode
from paddle_tpu.ops.lstm import lstm_sequence, lstm_sequence_ref
from paddle_tpu.ops.gru import gru_sequence, gru_sequence_ref
from paddle_tpu.ops.attention import (blockwise_attention, flash_attention,
                                      mha_reference)
from paddle_tpu.ops.crf import crf_log_z, crf_log_z_ref
from paddle_tpu.ops.ctc import ctc_ll, ctc_ll_ref

__all__ = [
    "use_pallas", "force_mode",
    "lstm_sequence", "lstm_sequence_ref",
    "gru_sequence", "gru_sequence_ref",
    "blockwise_attention", "flash_attention", "mha_reference",
    "crf_log_z", "crf_log_z_ref",
    "ctc_ll", "ctc_ll_ref",
]
