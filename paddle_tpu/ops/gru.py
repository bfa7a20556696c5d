"""Fused GRU sequence kernel (Pallas) with analytic backward.

TPU-native equivalent of the reference's fused GRU cell kernels
(`paddle/cuda/include/hl_gru_ops.cuh:28-81`, driven by `GruLayer.cpp`).
Same design as ops/lstm.py: the grid iterates time sequentially, both
recurrent weights stay resident in VMEM, each step fuses the two recurrent
matmuls with the gate math.

Cell math (reference gate order [update z, reset r, candidate c]):

    z = sigmoid(x_z + h·Wg_z)        Wg = [H, 2H] for (z, r)
    r = sigmoid(x_r + h·Wg_r)
    c = tanh(x_c + (r*h)·Ws)         Ws = [H, H]
    h' = (1-z)*h + z*c

Mask semantics identical to ops/lstm.py (state held through padding,
outputs zeroed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import common


def gru_sequence_ref(xs, mask, w_gate, w_state, bias, h0):
    """Pure lax.scan reference. xs [T,B,3H], mask [T,B], w_gate [H,2H],
    w_state [H,H], bias [3H]. Returns (ys [T,B,H], hT)."""
    H = h0.shape[-1]

    def step(carry, inp):
        h = carry
        x_t, m_t = inp
        x_t = x_t + bias
        zr = x_t[:, :2 * H] + h @ w_gate
        z = jax.nn.sigmoid(zr[:, :H])
        r = jax.nn.sigmoid(zr[:, H:])
        c = jnp.tanh(x_t[:, 2 * H:] + (r * h) @ w_state)
        h_new = h - z * h + z * c
        m = m_t[:, None]
        h_next = jnp.where(m > 0, h_new, h)
        return h_next, h_new * m

    hT, ys = lax.scan(step, h0, (xs, mask))
    return ys, hT


# ---------------------------------------------------------------- pallas fwd

def _gru_kernel(with_residuals, xs_ref, mask_ref, wg_ref, ws_ref, h0_ref,
                *refs):
    if with_residuals:
        ys_ref, hs_ref, gates_ref, h_s = refs
    else:
        ys_ref, hT_ref, h_s = refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[:] = h0_ref[:]

    # gate math in f32 whatever the storage dtype (ops/lstm.py:
    # _lstm_kernel); every cast is a no-op for f32 operands
    f32 = jnp.float32
    h = h_s[:]
    hf = h.astype(f32)
    H = h.shape[-1]
    x = xs_ref[0].astype(f32)
    zr = x[:, :2 * H] + jnp.dot(h, wg_ref[:], preferred_element_type=f32)
    z = jax.nn.sigmoid(zr[:, :H])
    r = jax.nn.sigmoid(zr[:, H:])
    c = jnp.tanh(x[:, 2 * H:] + jnp.dot(
        (r * hf).astype(h.dtype), ws_ref[:], preferred_element_type=f32))
    h_new = hf - z * hf + z * c
    m = mask_ref[0]  # [B, 1] (mask fed as [T, B, 1] for tiling rules)
    dt = ys_ref.dtype
    h_next = jnp.where(m > 0, h_new, hf).astype(dt)
    h_s[:] = h_next
    ys_ref[0] = (h_new * m).astype(dt)
    if with_residuals:
        hs_ref[0] = h_next
        gates_ref[0] = jnp.concatenate([z, r, c], axis=-1).astype(dt)
    else:
        hT_ref[:] = h_next


def _gru_pallas(xs, mask, w_gate, w_state, h0, with_residuals):
    T, B, H3 = xs.shape
    H = H3 // 3
    dt = xs.dtype
    t_block = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda t: (t,) + (0,) * len(shape),
        memory_space=pltpu.VMEM)
    full = lambda *shape: pl.BlockSpec(
        shape, lambda t: (0,) * len(shape), memory_space=pltpu.VMEM)
    if with_residuals:
        out_specs = (t_block(B, H), t_block(B, H), t_block(B, 3 * H))
        out_shape = (jax.ShapeDtypeStruct((T, B, H), dt),
                     jax.ShapeDtypeStruct((T, B, H), dt),
                     jax.ShapeDtypeStruct((T, B, 3 * H), dt))
    else:
        out_specs = (t_block(B, H), full(B, H))
        out_shape = (jax.ShapeDtypeStruct((T, B, H), dt),
                     jax.ShapeDtypeStruct((B, H), dt))
    return pl.pallas_call(
        functools.partial(_gru_kernel, with_residuals),
        grid=(T,),
        in_specs=[t_block(B, 3 * H), t_block(B, 1), full(H, 2 * H),
                  full(H, H), full(B, H)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((B, H), dt)],
        interpret=common.interpret(),
    )(xs, mask[..., None], w_gate, w_state, h0)


# ------------------------------------------------------------- custom vjp

@jax.custom_vjp
def _gru_core(xs, mask, w_gate, w_state, h0):
    # primal-only path (inference): lean kernel without backward residuals
    return _gru_pallas(xs, mask, w_gate, w_state, h0, with_residuals=False)


def _fwd_rule(xs, mask, w_gate, w_state, h0):
    ys, hs, gates = _gru_pallas(xs, mask, w_gate, w_state, h0,
                                with_residuals=True)
    return (ys, hs[-1]), (mask, w_gate, w_state, h0, hs, gates)


def _bwd_rule(res, grads):
    dys, dhT = grads
    mask, w_gate, w_state, h0, hs, gates = res
    T, B, H = hs.shape
    h_prev = jnp.concatenate([h0[None], hs[:-1]], axis=0)

    dt = hs.dtype
    f32 = jnp.float32

    def step(carry, inp):
        dh, dWg, dWs = carry
        dy_t, m_t, g_t, h_pv = inp
        # f32 mask products cast back to the carry dtype (see ops/lstm.py)
        m = m_t[:, None]
        z = g_t[:, :H]
        r = g_t[:, H:2 * H]
        c = g_t[:, 2 * H:]
        dh_new = (m * (dh + dy_t)).astype(dt)
        dz = dh_new * (c - h_pv)
        da_c = (dh_new * z) * (1 - c * c)
        drh = da_c @ w_state.T
        dr = drh * h_pv
        da_z = dz * z * (1 - z)
        da_r = dr * r * (1 - r)
        da_zr = jnp.concatenate([da_z, da_r], axis=-1)
        dh_prev = (((1 - m) * dh).astype(dt) + dh_new * (1 - z) + drh * r
                   + da_zr @ w_gate.T)
        dWg = dWg + jnp.dot(h_pv.T, da_zr, preferred_element_type=f32)
        dWs = dWs + jnp.dot((r * h_pv).T, da_c, preferred_element_type=f32)
        dxs_t = jnp.concatenate([da_z, da_r, da_c], axis=-1)
        return (dh_prev, dWg, dWs), dxs_t

    (dh0, dWg, dWs), dxs = lax.scan(
        step, (dhT, jnp.zeros(w_gate.shape, f32),
               jnp.zeros(w_state.shape, f32)),
        (dys, mask, gates, h_prev), reverse=True)
    return (dxs, None, dWg.astype(w_gate.dtype),
            dWs.astype(w_state.dtype), dh0)


_gru_core.defvjp(_fwd_rule, _bwd_rule)


# ---------------------------------------------------------------- public

def gru_sequence(xs, mask, w_gate, w_state, bias, h0, reverse=False):
    """Fused GRU over a padded [T,B,3H] gate-projection sequence.
    ``reverse=True`` runs back-to-front (outputs stay in input time order).
    Traced into a step partitioned over a mesh whose batch axes divide
    B, each device runs the kernel on its own rows
    (``common.batch_local``).
    Returns (ys [T,B,H], hT). Differentiable either way."""
    if reverse:
        ys, hT = gru_sequence(jnp.flip(xs, 0), jnp.flip(mask, 0), w_gate,
                              w_state, bias, h0)
        return jnp.flip(ys, 0), hT
    T, B, H3 = xs.shape
    H = H3 // 3
    split = common.batch_split(B)
    Bl = B // max(split, 1)
    itemsize = jnp.dtype(xs.dtype).itemsize
    # counted like the LSTM's (ops/lstm.py:_resident_bytes): constant-
    # index weights once, per-step blocks twice, h scratch once, plus
    # the lane-padded [B, 1] mask block
    resident = (itemsize * (3 * H * H + 2 * 9 * Bl * H + Bl * H)
                + 2 * 4 * Bl * common.LANE)
    if split == 0 or not common.use_pallas(resident):
        common.note("gru", "ref")
        # Big hidden sizes fall back to the scan reference. Unlike the
        # LSTM (ops/lstm.py:_lstm_pallas_tiled), a gate-column-tiled GRU
        # needs two phases per timestep (the candidate matmul consumes
        # the FULL reset gate), doubling weight streaming — measured
        # benefit over XLA's scan is not established, and no BASELINE
        # benchmark shape exceeds the resident budget for GRU.
        return gru_sequence_ref(xs, mask, w_gate, w_state, bias, h0)
    common.note("gru", common.pallas_path())
    core = common.batch_local(_gru_core, split,
                              in_dims=(1, 1, None, None, 0),
                              out_dims=(1, 0))
    return core(xs + bias, mask, w_gate, w_state, h0)
