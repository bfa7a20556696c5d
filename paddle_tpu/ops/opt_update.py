"""Fused optimizer-update kernels (the dense Momentum/Adam chains).

Reference precedent: ``paddle/math/TrainingAlgorithmOp.cu`` fuses each
optimizer's whole elementwise update into one kernel; the jnp spelling
in ``optim/optimizers.py`` stages it as 6-10 separate HBM-bound HLOs
per parameter. ``apply_one`` is the single routing point: called from
``Optimizer._update_param``'s dense branch, so the replicated step, the
ZeRO-1 shard-wise update and the packed FSDP update all reuse it.

Contract (``docs/kernels.md``):

- the fallback IS ``Optimizer._apply_one`` — off-TPU, in the reference
  mode (``common.force_mode("ref")`` / ``PADDLE_TPU_KERNELS=ref``, the
  one switch) or for any optimizer/slot/dtype shape the kernels don't
  cover the routing is the identity, bitwise by construction;
- the Pallas spelling is numerically the same chain; its outputs feed
  the same slot dict shape ``_update_param`` expects (``prune_mask``
  re-attachment happens in the caller, as for ``_apply_one``);
- operands flatten and zero-pad to ``[rows x LANE]`` tiles via
  ``concatenate`` (CLAUDE.md bit-stability note); the padded region is
  a fixed point of both chains (all-zero in, all-zero out — Adam's
  ``eps`` keeps the quotient finite), so the unpad slice is exact;
- the grid walks ``BLOCK_ROWS``-row blocks, so VMEM use is bounded by
  the block (Adam: 7 operands x 2 buffers x 512 KiB = 7 MiB) whatever
  the parameter's size, and no size gate is needed — as one
  whole-parameter block the kernel had to decline anything over its
  VMEM gate (the headline LSTM's 15 MB embedding among them);
- the parameter and every slot alias their outputs
  (``input_output_aliases``), so the donated train step keeps its
  in-place update through the custom call;
- XLA cannot partition a Mosaic kernel, so traced into a step that is
  partitioned over a mesh (``ops/common.py``, "the step mesh") the
  kernel runs on every device over its own replica of the parameter
  (``common.replica_local``); inside the ZeRO-1/FSDP ``shard_map``s it
  runs over the device's shard as on one chip; on a mesh with a model,
  seq or pipe axis, where a parameter may be sharded, ``_apply_one``
  runs (``record_dispatch`` counts it).

Traced scalars (lr / Adam's bias-corrected alpha) ride SMEM ``(1, 1)``
blocks; static hyper-parameters are kernel constants.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.ops import common


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_flat(x):
    """Flatten and zero-pad to an ``[R, LANE]`` tile, R a multiple of 8."""
    n = x.size
    cols = common.LANE
    rows = max(8, _ceil_to(-(-n // cols), 8))
    flat = jnp.reshape(x, (n,))
    pad = rows * cols - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    return jnp.reshape(flat, (rows, cols))


def _unpad_flat(y, like):
    return jnp.reshape(jnp.reshape(y, (-1,))[:like.size], like.shape)


def _smem_scalar(v):
    return jnp.reshape(jnp.asarray(v, jnp.float32), (1, 1))


# rows of one grid block: 1024 x LANE x 4 B = 512 KiB per operand
BLOCK_ROWS = 1024


def _elementwise_call(kernel, tiles, scalars, aliases, n_out):
    """One ``pallas_call`` over ``[R, LANE]`` tiles in ``BLOCK_ROWS``-row
    blocks (the last block may be ragged: elementwise, so its
    out-of-range rows are never written back). ``aliases`` maps tile
    inputs onto the outputs they update in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows = tiles[0].shape[0]
    block = (min(rows, BLOCK_ROWS), common.LANE)
    tile = pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0),
                          memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block[0]),),
        in_specs=[tile] * len(tiles) + [scalar] * len(scalars),
        out_specs=(tile,) * n_out,
        out_shape=(jax.ShapeDtypeStruct(tiles[0].shape, jnp.float32),)
        * n_out,
        input_output_aliases=aliases,
        interpret=common.interpret(),
    )(*tiles, *scalars)


def _per_device(fused, arrays):
    """``fused`` as the step being traced can run it over ``arrays``
    (the parameter, its gradient, its slots), or None where it cannot:
    operands that are not same-shape f32, the reference mode, or a step
    partitioned over a mesh on which a parameter may be sharded
    (``common.replica_local``). On a data-parallel mesh every device
    runs the kernel over its own replica."""
    shape = arrays[0].shape
    for a in arrays:
        if a.dtype != jnp.float32 or a.shape != shape:
            return None
    return common.replica_local(fused) if common.use_pallas() else None


# --------------------------------------------------------------- momentum

def _momentum_kernel(mu, p_ref, g_ref, m_ref, lr_ref, decay_ref,
                     p_out, m_out):
    lr = lr_ref[0, 0]
    decay = decay_ref[0, 0]
    mom = mu * m_ref[:] - lr * (g_ref[:] + decay * p_ref[:])
    p_out[:] = p_ref[:] + mom
    m_out[:] = mom


def _momentum_fused(p, g, m, lr, mu, decay):
    p2, m2 = _elementwise_call(
        functools.partial(_momentum_kernel, mu),
        (_pad_flat(p), _pad_flat(g), _pad_flat(m)),
        (_smem_scalar(lr), _smem_scalar(decay)),
        aliases={0: 0, 2: 1}, n_out=2)
    return _unpad_flat(p2, p), {"mom": _unpad_flat(m2, m)}


# ------------------------------------------------------------------- adam

def _adam_kernel(b1, b2, eps, p_ref, g_ref, m_ref, v_ref, alpha_ref,
                 decay_ref, p_out, m_out, v_out):
    alpha = alpha_ref[0, 0]
    decay = decay_ref[0, 0]
    g = g_ref[:] + decay * p_ref[:]
    mom = b1 * m_ref[:] + (1 - b1) * g
    v = b2 * v_ref[:] + (1 - b2) * jnp.square(g)
    p_out[:] = p_ref[:] - alpha * mom / (jnp.sqrt(v) + eps)
    m_out[:] = mom
    v_out[:] = v


def _adam_fused(p, g, m, v, lr, t, b1, b2, eps, decay):
    tf = t.astype(jnp.float32)
    # the bias correction is scalar math — hoisted out of the kernel
    alpha = lr * jnp.sqrt(1 - jnp.power(b2, tf)) / (1 - jnp.power(b1, tf))
    p2, m2, v2 = _elementwise_call(
        functools.partial(_adam_kernel, b1, b2, eps),
        (_pad_flat(p), _pad_flat(g), _pad_flat(m), _pad_flat(v)),
        (_smem_scalar(alpha), _smem_scalar(decay)),
        aliases={0: 0, 2: 1, 3: 2}, n_out=3)
    return _unpad_flat(p2, p), {"mom": _unpad_flat(m2, m),
                                "v": _unpad_flat(v2, v)}


# ---------------------------------------------------------------- routing

def apply_one(opt, p, g, slots, lr, decay, t):
    """Fused stand-in for ``opt._apply_one`` on the dense path. The slot
    dict may carry ``prune_mask`` (ignored here, re-attached by
    ``_update_param``, matching ``_apply_one``'s contract)."""
    kind = type(opt).__name__
    keys = set(slots) - {"prune_mask"}
    fused = arrays = None
    if (kind == "Momentum" and not getattr(opt, "nesterov", False)
            and keys == {"mom"}):
        arrays = (p, g, slots["mom"])
        fused = lambda p, g, m, lr, decay, t: _momentum_fused(  # noqa: E731
            p, g, m, lr, opt.momentum, decay)
    elif kind == "Adam" and keys == {"mom", "v"}:
        arrays = (p, g, slots["mom"], slots["v"])
        fused = lambda p, g, m, v, lr, decay, t: _adam_fused(  # noqa: E731
            p, g, m, v, lr, t, opt.beta1, opt.beta2, opt.epsilon, decay)
    run = _per_device(fused, arrays) if fused else None
    if run is None:
        common.note("opt_update", "apply_one")
        return opt._apply_one(p, g, slots, lr, decay, t)
    common.note("opt_update", "fused")
    # traced scalars cross a shard_map as arrays
    return run(*arrays, jnp.asarray(lr, jnp.float32),
               jnp.asarray(decay, jnp.float32), jnp.asarray(t))
