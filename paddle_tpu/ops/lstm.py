"""Fused LSTM sequence op: two Pallas kernels and a ``lax.scan``, one
analytic backward.

TPU-native equivalent of the reference's fused LSTM cell kernels
(`paddle/cuda/include/hl_gpu_lstm.cuh:46-67`, driven per-timestep by
`LstmLayer.cpp`): the whole recurrence runs as ONE Pallas kernel — the grid
iterates time (TPU grids execute sequentially), the recurrent weight stays
resident in VMEM across all T steps, and each step fuses the [B,H]x[H,4H]
recurrent matmul (MXU) with the gate nonlinearities (VPU). The input
projection x·W_in (the big MXU matmul) happens outside, batched over all
timesteps, exactly as the reference splits `Layer::forward` projection from
the fused cell.

Cell math (reference gate order [input, input_gate, forget_gate,
output_gate], peephole diagonals checkI/F/O):

    i  = tanh(a_i)
    ig = sigmoid(a_ig + c_prev * pI)
    fg = sigmoid(a_fg + c_prev * pF)
    c  = i*ig + c_prev*fg
    og = sigmoid(a_og + c * pO)
    h  = og * tanh(c)

Padded timesteps (mask==0) hold the carried state; outputs are zeroed —
this preserves the reference's ragged-sequence semantics
(`Argument.sequenceStartPositions`) in a static-shape layout.

Every dispatched path (the resident kernel, the tiled kernel, the scan
that runs where neither fits) is a ``jax.custom_vjp`` with the same
backward, ``_bwd_rule``: an analytic reverse-time ``lax.scan`` over
residuals saved by the forward (activated gates + state chains, the
cuDNN-style "save gates, no recompute" strategy) that carries ``(dh, dc)``
alone and emits ``dgates``; the weight gradient is one product over the
stacked ``dgates`` after the scan. JAX differentiates only
``lstm_sequence_ref``, the tests' gold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import common


def _cell(gates, c, check_i, check_f, check_o):
    """One step of the cell math above on pre-activation ``gates`` [B,4H]
    and the previous cell state: (i, ig, fg, og, c_new, h_new)."""
    a_i, a_ig, a_fg, a_og = jnp.split(gates, 4, axis=-1)
    i = jnp.tanh(a_i)
    ig = jax.nn.sigmoid(a_ig + c * check_i)
    fg = jax.nn.sigmoid(a_fg + c * check_f)
    c_new = i * ig + c * fg
    og = jax.nn.sigmoid(a_og + c_new * check_o)
    return i, ig, fg, og, c_new, og * jnp.tanh(c_new)


def lstm_sequence_ref(xs, mask, w, gate_bias, check_i, check_f, check_o,
                      h0, c0):
    """Pure lax.scan reference, differentiated by JAX itself: the tests'
    gold for values and gradients of every dispatched path. xs [T,B,4H]
    (pre-projected inputs), mask [T,B], w [H,4H]. Returns (ys [T,B,H],
    hT, cT)."""

    def step(carry, inp):
        h, c = carry
        x_t, m_t = inp
        *_, c_new, h_new = _cell(x_t + h @ w + gate_bias, c, check_i,
                                 check_f, check_o)
        m = m_t[:, None]
        h_next = jnp.where(m > 0, h_new, h)
        c_next = jnp.where(m > 0, c_new, c)
        return (h_next, c_next), h_new * m

    (hT, cT), ys = lax.scan(step, (h0, c0), (xs, mask))
    return ys, hT, cT


def _lstm_scan(xs, mask, w, pI, pF, pO, h0, c0, with_residuals):
    """The "ref" dispatch: the reference's scan over xs with the gate
    bias already folded in. With residuals it also emits, step by step,
    what ``_bwd_rule`` reads: the state a step took in, the cell state it
    left (both guarded) and its activated gates."""

    def step(carry, inp):
        h, c = carry
        x_t, m_t = inp
        i, ig, fg, og, c_new, h_new = _cell(x_t + h @ w, c, pI, pF, pO)
        m = m_t[:, None]
        h_next = jnp.where(m > 0, h_new, h)
        c_next = jnp.where(m > 0, c_new, c)
        y = h_new * m
        if with_residuals:
            return (h_next, c_next), (y, h, c, c_next, jnp.concatenate(
                [i, ig, fg, og], axis=-1))
        return (h_next, c_next), y

    (hT, cT), out = lax.scan(step, (h0, c0), (xs, mask))
    if with_residuals:
        ys, *res = out
        return ys, hT, cT, res
    return out, hT, cT


# ---------------------------------------------------------------- pallas fwd

def _lstm_kernel(with_residuals, xs_ref, mask_ref, w_ref, pI_ref, pF_ref,
                 pO_ref, h0_ref, c0_ref, *refs):
    if with_residuals:
        ys_ref, hs_ref, cs_ref, gates_ref, h_s, c_s = refs
    else:
        ys_ref, hT_ref, cT_ref, h_s, c_s = refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[:] = h0_ref[:]
        c_s[:] = c0_ref[:]

    # Gate math runs in f32 whatever the storage dtype: the MXU
    # accumulates in f32 anyway, a v5e has no bf16 vector unit, and
    # Mosaic's bf16 logistic fails its own verifier (chip run, PERF.md).
    # Every cast below is a no-op for f32 operands.
    f32 = jnp.float32
    h = h_s[:]
    c = c_s[:].astype(f32)
    H = c.shape[-1]
    m = mask_ref[0]  # [B, 1] (mask is fed as [T, B, 1] for tiling rules)
    gates = xs_ref[0].astype(f32) + jnp.dot(h, w_ref[:],
                                            preferred_element_type=f32)
    a_i = gates[:, :H]
    a_ig = gates[:, H:2 * H]
    a_fg = gates[:, 2 * H:3 * H]
    a_og = gates[:, 3 * H:]
    i = jnp.tanh(a_i)
    ig = jax.nn.sigmoid(a_ig + c * pI_ref[0].astype(f32))
    fg = jax.nn.sigmoid(a_fg + c * pF_ref[0].astype(f32))
    c_new = i * ig + c * fg
    og = jax.nn.sigmoid(a_og + c_new * pO_ref[0].astype(f32))
    h_new = og * jnp.tanh(c_new)

    dt = ys_ref.dtype
    h_next = jnp.where(m > 0, h_new, h.astype(f32)).astype(dt)
    c_next = jnp.where(m > 0, c_new, c).astype(dt)
    h_s[:] = h_next
    c_s[:] = c_next
    ys_ref[0] = (h_new * m).astype(dt)
    if with_residuals:
        hs_ref[0] = h_next
        cs_ref[0] = c_next
        gates_ref[0] = jnp.concatenate([i, ig, fg, og], axis=-1).astype(dt)
    else:
        # final-state outputs use a constant index map; the last grid step's
        # write is what the caller sees
        hT_ref[:] = h_next
        cT_ref[:] = c_next


def _lstm_pallas(xs, mask, w, pI, pF, pO, h0, c0, with_residuals):
    T, B, H4 = xs.shape
    H = H4 // 4
    dt = xs.dtype
    t_block = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda t: (t,) + (0,) * len(shape),
        memory_space=pltpu.VMEM)
    full = lambda *shape: pl.BlockSpec(
        shape, lambda t: (0,) * len(shape), memory_space=pltpu.VMEM)
    if with_residuals:
        out_shapes = (
            jax.ShapeDtypeStruct((T, B, H), dt),       # ys
            jax.ShapeDtypeStruct((T, B, H), dt),       # hs (guarded chain)
            jax.ShapeDtypeStruct((T, B, H), dt),       # cs (guarded chain)
            jax.ShapeDtypeStruct((T, B, 4 * H), dt),   # activated gates
        )
        out_specs = (t_block(B, H), t_block(B, H), t_block(B, H),
                     t_block(B, 4 * H))
    else:
        out_shapes = (
            jax.ShapeDtypeStruct((T, B, H), dt),       # ys
            jax.ShapeDtypeStruct((B, H), dt),          # hT
            jax.ShapeDtypeStruct((B, H), dt),          # cT
        )
        out_specs = (t_block(B, H), full(B, H), full(B, H))
    return pl.pallas_call(
        functools.partial(_lstm_kernel, with_residuals),
        grid=(T,),
        in_specs=[
            t_block(B, 4 * H),            # xs
            t_block(B, 1),                # mask as [T, B, 1]
            full(H, 4 * H),               # w
            full(1, H), full(1, H), full(1, H),   # peepholes
            full(B, H), full(B, H),       # h0, c0
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt)],
        interpret=common.interpret(),
    )(xs, mask[..., None], w, pI.reshape(1, H), pF.reshape(1, H),
      pO.reshape(1, H), h0, c0)


# ------------------------------------------------- pallas fwd, tiled-H
# For big hidden sizes (BASELINE.md h=1280: w alone is 26 MB fp32) the
# weight cannot stay VMEM-resident. This variant tiles the HIDDEN
# dimension: grid (T, J) with J = H/Hb column blocks iterated innermost;
# block (t, j) streams w[:, 4 gate columns of block j] from HBM, computes
# that block's gates/cell update, and keeps only the full h/c state
# (2*B*H) resident in scratch. The cell math is elementwise in the H
# columns, so blocks are independent within a timestep; the sequential
# TPU grid guarantees every j of step t completes before step t+1 reads
# the full h.

def _lstm_kernel_tiled(with_residuals, hb, xs_ref, mask_ref, w_ref, pI_ref,
                       pF_ref, pO_ref, h0_ref, c0_ref, *refs):
    if with_residuals:
        ys_ref, hs_ref, cs_ref, gates_ref, h_s, hn_s, c_s = refs
    else:
        ys_ref, hT_ref, cT_ref, h_s, hn_s, c_s = refs
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(t == 0, j == 0))
    def _():
        h_s[:] = h0_ref[:]
        c_s[:] = c0_ref[:]

    cols = pl.dslice(j * hb, hb)
    # every j block of this timestep must see the SAME h_{t-1}: h_s holds
    # the previous step all timestep long; new values buffer in hn_s and
    # commit after the last block. Gate math in f32 (see _lstm_kernel).
    f32 = jnp.float32
    h = h_s[:]                      # full [B, H] = h_{t-1}
    c = c_s[:, cols].astype(f32)    # [B, hb]
    m = mask_ref[0]                 # [B, 1]
    B = h.shape[0]
    H = h.shape[1]
    # w block [H, 4, hb] -> [H, 4*hb]
    wb = w_ref[:].reshape(H, 4 * hb)
    gates = (xs_ref[0].reshape(B, 4 * hb).astype(f32)
             + jnp.dot(h, wb, preferred_element_type=f32)
             ).reshape(B, 4, hb)
    a_i, a_ig, a_fg, a_og = (gates[:, 0], gates[:, 1], gates[:, 2],
                             gates[:, 3])
    i = jnp.tanh(a_i)
    ig = jax.nn.sigmoid(a_ig + c * pI_ref[0].astype(f32))
    fg = jax.nn.sigmoid(a_fg + c * pF_ref[0].astype(f32))
    c_new = i * ig + c * fg
    og = jax.nn.sigmoid(a_og + c_new * pO_ref[0].astype(f32))
    h_new = og * jnp.tanh(c_new)

    dt = ys_ref.dtype
    h_prev = h_s[:, cols].astype(f32)
    h_next = jnp.where(m > 0, h_new, h_prev).astype(dt)
    c_next = jnp.where(m > 0, c_new, c).astype(dt)
    hn_s[:, cols] = h_next
    c_s[:, cols] = c_next

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        h_s[:] = hn_s[:]

    ys_ref[0] = (h_new * m).astype(dt)
    if with_residuals:
        hs_ref[0] = h_next
        cs_ref[0] = c_next
        gates_ref[0] = jnp.stack([i, ig, fg, og], axis=1).astype(dt)
    else:
        hT_ref[:] = h_next
        cT_ref[:] = c_next


def _pick_hblock(H: int, B: int, itemsize: int) -> int:
    """Largest lane-aligned divisor of H whose per-block working set
    fits the VMEM budget; 0 if none. Counted as Mosaic allocates it
    (chip run, PERF.md: H=1280 at hb=256 asked for 17.84 MiB against
    the 16 MiB scoped limit): the streamed weight block three times
    (two pipeline buffers plus the in-kernel [H,4,hb]->[H,4hb] copy),
    every other block twice, the state scratch once."""
    for hb in (1024, 512, 256, 128):
        if H % hb:
            continue
        resident = itemsize * (
            3 * H * 4 * hb        # weight block
            + 2 * 2 * B * 4 * hb  # xs in / activated gates out
            + 2 * 3 * B * hb      # ys, hs, cs out
            + 2 * 2 * B * H       # h0, c0 in
            + 3 * B * H           # h (prev + commit buffer) / c scratch
        ) + 2 * 4 * B * common.LANE   # lane-padded [B, 1] mask block
        if resident <= common.VMEM_BUDGET_BYTES:
            return hb
    return 0


def _lstm_pallas_tiled(xs, mask, w, pI, pF, pO, h0, c0, with_residuals,
                       hb):
    T, B, H4 = xs.shape
    H = H4 // 4
    J = H // hb
    dt = xs.dtype
    xs4 = xs.reshape(T, B, 4, H)
    w4 = w.reshape(H, 4, H)
    if with_residuals:
        out_shapes = (
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((T, B, 4, H), dt),
        )
        out_specs = (
            pl.BlockSpec((1, B, hb), lambda t, j: (t, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, hb), lambda t, j: (t, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, hb), lambda t, j: (t, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, 4, hb), lambda t, j: (t, 0, 0, j),
                         memory_space=pltpu.VMEM),
        )
    else:
        out_shapes = (
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
        )
        out_specs = (
            pl.BlockSpec((1, B, hb), lambda t, j: (t, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, hb), lambda t, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, hb), lambda t, j: (0, j),
                         memory_space=pltpu.VMEM),
        )
    res = pl.pallas_call(
        functools.partial(_lstm_kernel_tiled, with_residuals, hb),
        grid=(T, J),
        in_specs=[
            pl.BlockSpec((1, B, 4, hb), lambda t, j: (t, 0, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, 1), lambda t, j: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4, hb), lambda t, j: (0, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hb), lambda t, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hb), lambda t, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hb), lambda t, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), lambda t, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), lambda t, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt),
                        pltpu.VMEM((B, H), dt)],
        interpret=common.interpret(),
    )(xs4, mask[..., None], w4, pI.reshape(1, H), pF.reshape(1, H),
      pO.reshape(1, H), h0, c0)
    if with_residuals:
        ys, hs, cs, gates4 = res
        return ys, hs, cs, gates4.reshape(T, B, 4 * H)
    return res


# ------------------------------------------------------------- custom vjp

@jax.custom_vjp
def _lstm_core(xs, mask, w, pI, pF, pO, h0, c0):
    # primal-only path (inference): lean kernel without backward residuals
    ys, hT, cT = _lstm_pallas(xs, mask, w, pI, pF, pO, h0, c0,
                              with_residuals=False)
    return ys, hT, cT


def _kernel_residuals(mask, w, pI, pF, pO, h0, c0, hs, cs, gates):
    """``_bwd_rule``'s residuals from a training kernel's outputs: the
    states every step took in are the guarded chains shifted by one
    (h_prev[t] = hs[t-1], h0 at t=0)."""
    h_prev = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    c_prev = jnp.concatenate([c0[None], cs[:-1]], axis=0)
    return mask, w, pI, pF, pO, h_prev, c_prev, cs, gates


def _fwd_rule(xs, mask, w, pI, pF, pO, h0, c0):
    ys, hs, cs, gates = _lstm_pallas(xs, mask, w, pI, pF, pO, h0, c0,
                                     with_residuals=True)
    res = _kernel_residuals(mask, w, pI, pF, pO, h0, c0, hs, cs, gates)
    return (ys, hs[-1], cs[-1]), res


def _hb_of(xs):
    T, B, H4 = xs.shape
    return _pick_hblock(H4 // 4, B, jnp.dtype(xs.dtype).itemsize)


@jax.custom_vjp
def _lstm_core_tiled(xs, mask, w, pI, pF, pO, h0, c0):
    ys, hT, cT = _lstm_pallas_tiled(xs, mask, w, pI, pF, pO, h0, c0,
                                    with_residuals=False, hb=_hb_of(xs))
    return ys, hT, cT


def _fwd_rule_tiled(xs, mask, w, pI, pF, pO, h0, c0):
    ys, hs, cs, gates = _lstm_pallas_tiled(
        xs, mask, w, pI, pF, pO, h0, c0, with_residuals=True,
        hb=_hb_of(xs))
    res = _kernel_residuals(mask, w, pI, pF, pO, h0, c0, hs, cs, gates)
    return (ys, hs[-1], cs[-1]), res


@jax.custom_vjp
def _lstm_core_scan(xs, mask, w, pI, pF, pO, h0, c0):
    return _lstm_scan(xs, mask, w, pI, pF, pO, h0, c0,
                      with_residuals=False)


def _fwd_rule_scan(xs, mask, w, pI, pF, pO, h0, c0):
    ys, hT, cT, res = _lstm_scan(xs, mask, w, pI, pF, pO, h0, c0,
                                 with_residuals=True)
    return (ys, hT, cT), (mask, w, pI, pF, pO, *res)


# Cotangents through y = tanh(x) and y = sigmoid(x). These, and the
# sums in ``_bwd_rule``'s step, round in the order JAX's own rules do, so
# ``dgates`` and the state gradients equal an autodiff'd scan's bit for
# bit (``tests/test_ops_pallas.py`` holds them to it). On the chip every
# product rounds its operands to bfloat16, and over 100 steps that turns
# a last-bit difference in ``dgates`` into 1e-4 of a leaf's gradient and
# 1.5e-3 of a bias leaf's: past the benchmark's check (PERF.md, PR 34).

def _dtanh(ct, y):
    u = ct * (1 - y)
    return u + u * y


def _dsigmoid(ct, y):
    return ct * (y * (1 - y))


def _bwd_rule(res, grads):
    """The analytic reverse-time scan of every path. ``res`` holds, per
    step, the states it took in (``h_prev``, ``c_prev``), the cell state
    it left (``cs``) and its activated gates."""
    dys, dhT, dcT = grads
    mask, w, pI, pF, pO, h_prev, c_prev, cs, gates = res
    H = cs.shape[-1]
    dt = cs.dtype
    f32 = jnp.float32

    def step(carry, inp):
        dh, dc = carry
        dy_t, m_t, g_t, c_new, c_pv = inp
        # the mask stays f32 (count data, never cast); under a bf16
        # compute dtype its products promote, so each is cast back to
        # the carry dtype — a no-op in f32
        m = m_t[:, None]
        i = g_t[:, :H]
        ig = g_t[:, H:2 * H]
        fg = g_t[:, 2 * H:3 * H]
        og = g_t[:, 3 * H:]
        dh_new = (m * (dh + dy_t)).astype(dt)
        dc_new = (m * dc).astype(dt)
        tc = jnp.tanh(c_new)
        da_og = _dsigmoid(dh_new * tc, og)
        # tanh(c_new)'s cotangent reaches the sum as its two terms
        u = (dh_new * og) * (1 - tc)
        dc_tot = dc_new + u + u * tc + da_og * pO
        da_i = _dtanh(dc_tot * ig, i)
        da_ig = _dsigmoid(dc_tot * i, ig)
        da_fg = _dsigmoid(dc_tot * c_pv, fg)
        dc_prev = (((1 - m) * dc).astype(dt) + dc_tot * fg + da_fg * pF
                   + da_ig * pI)
        dgates = jnp.concatenate([da_i, da_ig, da_fg, da_og], axis=-1)
        dh_prev = ((1 - m) * dh).astype(dt) + lax.dot_general(
            dgates, w, (((1,), (1,)), ((), ())), preferred_element_type=dt)
        # a step's share of the peephole gradients, summed over its rows
        # while they are at hand: over the stacked dgates afterwards the
        # same sums cost a pass over HBM (0.87 ms a layer at h=1280: chip
        # run, PERF.md PR 34)
        dpeep = jnp.stack([jnp.sum((da * c_).astype(f32), axis=0)
                           for da, c_ in ((da_ig, c_pv), (da_fg, c_pv),
                                          (da_og, c_new))])
        return (dh_prev, dc_prev), (dgates, dpeep)

    # the carry is (dh, dc) alone: a weight-shaped sum in it crosses HBM
    # every step (26 MB read and written at h=1280). The weight gradient
    # is one [T*B,H]^T x [T*B,4H] product over the stacked dgates after
    # the scan, summed in f32 as the peephole gradients are.
    (dh0, dc0), (dxs, dpeeps) = lax.scan(
        step, (dhT, dcT), (dys, mask, gates, cs, c_prev), reverse=True)
    dW = jnp.einsum("tbh,tbg->hg", h_prev, dxs, preferred_element_type=f32)
    dpI, dpF, dpO = jnp.sum(dpeeps, axis=0)
    return (dxs, None, dW.astype(w.dtype), dpI.astype(pI.dtype),
            dpF.astype(pF.dtype), dpO.astype(pO.dtype), dh0, dc0)


_lstm_core.defvjp(_fwd_rule, _bwd_rule)
_lstm_core_tiled.defvjp(_fwd_rule_tiled, _bwd_rule)
_lstm_core_scan.defvjp(_fwd_rule_scan, _bwd_rule)


# ---------------------------------------------------------------- public

def _resident_bytes(B: int, H: int, itemsize: int) -> int:
    """VMEM the resident kernel's training spelling holds, calibrated on
    the chip (PERF.md): the weight ONCE — its block index never changes
    and (64, 640) compiles, which two 6.5 MB copies plus the step blocks
    could not under the 16 MiB limit — every per-step block twice
    (double-buffered), the h/c scratch once. The [B, 1] mask block pads
    to a full lane tile."""
    step_blocks = (2 * B * 4 * H    # xs in, activated gates out
                   + 5 * B * H      # h0, c0 in; ys, hs, cs out
                   + 3 * 8 * H)     # peepholes (one sublane tile each)
    return (itemsize * (H * 4 * H + 2 * step_blocks + 2 * B * H)
            + 2 * 4 * B * common.LANE)


def lstm_dispatch(B: int, H: int, itemsize: int = 4) -> str:
    """Which implementation these shapes take: "resident" (weight stays
    in VMEM all T steps), "tiled" (big hidden sizes stream gate-column
    blocks — BASELINE.md h=1280), or "ref" (lax.scan). Exposed so tests
    can pin the benchmark shapes to their intended path."""
    if common.mode() == "ref":
        return "ref"
    if _resident_bytes(B, H, itemsize) <= common.VMEM_BUDGET_BYTES:
        return "resident"
    if H % 128 == 0 and _pick_hblock(H, B, itemsize):
        return "tiled"
    return "ref"


BENCH_SHAPES = [(64, 256), (64, 512), (64, 1280), (128, 256), (128, 1280),
                (256, 256), (256, 1280), (512, 512)]


def kernel_dispatch_table():
    """{"lstm_bs{B}_h{H}": path} for every BASELINE.md rnn-table shape
    (benchmark/README.md:108-161): what ``lstm_dispatch`` decides at
    each, in one place (``tests/test_ops_pallas.py`` pins it)."""
    return {f"lstm_bs{b}_h{h}": lstm_dispatch(b, h)
            for b, h in BENCH_SHAPES}


def lstm_sequence(xs, mask, w, gate_bias, check_i, check_f, check_o, h0, c0,
                  reverse=False):
    """Fused LSTM over a padded [T,B,4H] gate-projection sequence.

    Dispatch (``lstm_dispatch``): the resident Pallas kernel when the
    recurrent weight fits VMEM for all T steps, the tiled Pallas kernel
    (weight streamed in gate-column blocks) for big hidden sizes, else
    the same recurrence as a lax.scan (noted as ``ref``). ``reverse=True``
    runs the recurrence back-to-front (outputs stay in input time order).
    Traced into a step partitioned over a mesh (``common.step_mesh``)
    whose batch axes divide B, each device runs the kernel on its own
    rows (``common.batch_local``) and dispatch sees the per-device batch;
    under one that cannot split B the scan runs. Returns (ys [T,B,H],
    hT, cT). Differentiable on every path, by ``_bwd_rule``.
    """
    if reverse:
        ys, hT, cT = lstm_sequence(jnp.flip(xs, 0), jnp.flip(mask, 0), w,
                                   gate_bias, check_i, check_f, check_o,
                                   h0, c0)
        return jnp.flip(ys, 0), hT, cT
    T, B, H4 = xs.shape
    H = H4 // 4
    split = common.batch_split(B)
    path = common.note("lstm", lstm_dispatch(
        B // split, H, jnp.dtype(xs.dtype).itemsize) if split else "ref")
    xs_b = xs + gate_bias  # fold bias into the pre-projected input once
    if path == "ref":
        return _lstm_core_scan(xs_b, mask, w, check_i, check_f, check_o,
                               h0, c0)
    core = common.batch_local(
        _lstm_core if path == "resident" else _lstm_core_tiled, split,
        in_dims=(1, 1, None, None, None, None, 0, 0), out_dims=(1, 0, 0))
    return core(xs_b, mask, w, check_i, check_f, check_o, h0, c0)
