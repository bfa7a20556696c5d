"""Fused LSTM/GRU cell kernels (one step, gates+activations+state).

The per-step counterpart of the fused sequence recurrences in
``ops/lstm.py``/``ops/gru.py``, for the paths that cannot use them: the
non-default-activation inline steps of ``layers/recurrent.py:LstmLayer/
GruLayer`` and the single-step ``LstmStepLayer``/``GruStepLayer``
(recurrent-group bodies), where the cell math is re-traced as a dozen
separate elementwise HLOs per step. Reference precedent:
``paddle/cuda/include/hl_gpu_lstm.cuh:46``/``hl_gpu_gru.cuh`` fuse the
same chain into one kernel launch.

Contract (``docs/kernels.md``):

- the reference spelling (``_lstm_math``/``_gru_math``) is the EXACT
  inline math of ``layers/recurrent.py`` — same ops in the same order —
  so routing a layer through the fallback is bitwise-invisible;
- the Pallas path is taken only at trace time (``common.use_pallas``,
  TPU or forced) and only for the default activation set; its backward
  is the ``jax.vjp`` of the reference spelling (recompute strategy —
  a one-step cell is cheap to recompute, residuals are the inputs);
- operands pad batch→multiple of 8 and hidden→multiple of ``LANE`` with
  zeros via ``concatenate`` (never ``jnp.pad``; CLAUDE.md bit-stability
  note), and the padded region provably stays finite for the default
  activations, so the ``[:B, :H]`` slice is the whole story.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.ops import common


def _act(name):
    # lazy import: kernels must stay importable without the layer plane
    from paddle_tpu.layers.activations import apply_activation
    return lambda x: apply_activation(name or "tanh", x)


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad2(x, rows: int, cols: int):
    r, c = x.shape
    if c < cols:
        x = jnp.concatenate(
            [x, jnp.zeros((r, cols - c), x.dtype)], axis=1)
    if r < rows:
        x = jnp.concatenate(
            [x, jnp.zeros((rows - r, x.shape[1]), x.dtype)], axis=0)
    return x


# ------------------------------------------------------------------- LSTM

def _lstm_math(gates, c_prev, check_i, check_f, check_o,
               act_in, act_gate, act_state):
    """The inline LstmLayer/LstmStepLayer step, verbatim (gates already
    hold x_t + h @ w + gate_bias)."""
    g_in, g_ig, g_fg, g_og = jnp.split(gates, 4, axis=-1)
    g_in = act_in(g_in)
    g_ig = act_gate(g_ig + c_prev * check_i)
    g_fg = act_gate(g_fg + c_prev * check_f)
    state = g_in * g_ig + c_prev * g_fg
    g_og = act_gate(g_og + state * check_o)
    return g_og * act_state(state), state


def _lstm_ref_default(gates, c_prev, check_i, check_f, check_o):
    return _lstm_math(gates, c_prev, check_i, check_f, check_o,
                      _act("tanh"), _act("sigmoid"), _act("tanh"))


def _lstm_cell_kernel(gi_ref, gig_ref, gfg_ref, gog_ref, c_ref,
                      pI_ref, pF_ref, pO_ref, out_ref, state_ref):
    # f32 math whatever the storage dtype (ops/lstm.py:_lstm_kernel)
    f32 = jnp.float32
    c = c_ref[:].astype(f32)
    i = jnp.tanh(gi_ref[:].astype(f32))
    ig = jax.nn.sigmoid(gig_ref[:].astype(f32) + c * pI_ref[0].astype(f32))
    fg = jax.nn.sigmoid(gfg_ref[:].astype(f32) + c * pF_ref[0].astype(f32))
    state = i * ig + c * fg
    og = jax.nn.sigmoid(gog_ref[:].astype(f32)
                        + state * pO_ref[0].astype(f32))
    state_ref[:] = state.astype(state_ref.dtype)
    out_ref[:] = (og * jnp.tanh(state)).astype(out_ref.dtype)


def _lstm_pallas(gates, c_prev, check_i, check_f, check_o):
    B, H = c_prev.shape
    Bp, Hp = _ceil_to(B, 8), _ceil_to(H, common.LANE)
    g_in, g_ig, g_fg, g_og = jnp.split(gates, 4, axis=-1)
    blocks = [_pad2(a, Bp, Hp) for a in (g_in, g_ig, g_fg, g_og, c_prev)]
    peeps = [_pad2(p.reshape(1, H), 1, Hp)
             for p in (check_i, check_f, check_o)]
    full = common.resident_block
    from jax.experimental import pallas as pl
    out, state = pl.pallas_call(
        _lstm_cell_kernel,
        grid=(1,),
        in_specs=[full(Bp, Hp)] * 5 + [full(1, Hp)] * 3,
        out_specs=(full(Bp, Hp), full(Bp, Hp)),
        out_shape=(jax.ShapeDtypeStruct((Bp, Hp), c_prev.dtype),
                   jax.ShapeDtypeStruct((Bp, Hp), c_prev.dtype)),
        interpret=common.interpret(),
    )(*blocks, *peeps)
    return out[:B, :H], state[:B, :H]


@jax.custom_vjp
def _lstm_fused(gates, c_prev, check_i, check_f, check_o):
    return _lstm_pallas(gates, c_prev, check_i, check_f, check_o)


def _lstm_fused_fwd(gates, c_prev, check_i, check_f, check_o):
    return (_lstm_fused(gates, c_prev, check_i, check_f, check_o),
            (gates, c_prev, check_i, check_f, check_o))


def _lstm_fused_bwd(res, ct):
    _, vjp = jax.vjp(_lstm_ref_default, *res)
    return vjp(ct)


_lstm_fused.defvjp(_lstm_fused_fwd, _lstm_fused_bwd)


def _lstm_pallas_ok(gates, c_prev, checks, default_acts):
    if not default_acts or gates.ndim != 2 or c_prev.ndim != 2:
        return False
    if any(p.ndim != 1 for p in checks):
        return False
    B, H = c_prev.shape
    Bp, Hp = _ceil_to(B, 8), _ceil_to(H, common.LANE)
    itemsize = jnp.dtype(c_prev.dtype).itemsize
    resident = (7 * Bp * Hp + 3 * Hp) * itemsize
    ok = common.use_pallas(resident)
    common.note("lstm_cell", common.pallas_path() if ok else "ref")
    return ok


def lstm_cell(gates, c_prev, check_i, check_f, check_o,
              act_input="tanh", act_gate="sigmoid", act_state="tanh"):
    """One LSTM step on pre-projected gates ``[B, 4H]`` with peephole
    diagonals ``[H]``; returns ``(out, state)``, both ``[B, H]``."""
    default = (act_input in ("tanh", "", None)
               and act_gate in ("sigmoid", "", None)
               and act_state in ("tanh", "", None))
    if _lstm_pallas_ok(gates, c_prev, (check_i, check_f, check_o), default):
        return _lstm_fused(gates, c_prev, check_i, check_f, check_o)
    return _lstm_math(gates, c_prev, check_i, check_f, check_o,
                      _act(act_input), _act(act_gate), _act(act_state))


# -------------------------------------------------------------------- GRU

def _gru_math(x, h, w_gate, w_state, act_in, act_gate):
    """The inline GruLayer/GruStepLayer step, verbatim (x already holds
    the input projection plus bias, ``[B, 3H]``)."""
    size = h.shape[-1]
    zr = x[:, :2 * size] + h @ w_gate
    z = act_gate(zr[:, :size])
    r = act_gate(zr[:, size:])
    c = act_in(x[:, 2 * size:] + (r * h) @ w_state)
    return h - z * h + z * c


def _gru_ref_default(x, h, w_gate, w_state):
    return _gru_math(x, h, w_gate, w_state, _act("tanh"), _act("sigmoid"))


def _gru_cell_kernel(xz_ref, xr_ref, xc_ref, h_ref, wz_ref, wr_ref,
                     wc_ref, out_ref):
    # f32 math whatever the storage dtype (ops/lstm.py:_lstm_kernel)
    f32 = jnp.float32
    h = h_ref[:]
    hf = h.astype(f32)
    z = jax.nn.sigmoid(xz_ref[:].astype(f32) + jnp.dot(
        h, wz_ref[:], preferred_element_type=f32))
    r = jax.nn.sigmoid(xr_ref[:].astype(f32) + jnp.dot(
        h, wr_ref[:], preferred_element_type=f32))
    c = jnp.tanh(xc_ref[:].astype(f32) + jnp.dot(
        (r * hf).astype(h.dtype), wc_ref[:], preferred_element_type=f32))
    out_ref[:] = (hf - z * hf + z * c).astype(out_ref.dtype)


def _gru_pallas(x, h, w_gate, w_state):
    from jax.experimental import pallas as pl
    B, H = h.shape
    Bp, Hp = _ceil_to(B, 8), _ceil_to(H, common.LANE)
    xs = [_pad2(x[:, :H], Bp, Hp), _pad2(x[:, H:2 * H], Bp, Hp),
          _pad2(x[:, 2 * H:], Bp, Hp)]
    ws = [_pad2(w_gate[:, :H], Hp, Hp), _pad2(w_gate[:, H:], Hp, Hp),
          _pad2(w_state, Hp, Hp)]
    full = common.resident_block
    out = pl.pallas_call(
        _gru_cell_kernel,
        grid=(1,),
        in_specs=[full(Bp, Hp)] * 4 + [full(Hp, Hp)] * 3,
        out_specs=full(Bp, Hp),
        out_shape=jax.ShapeDtypeStruct((Bp, Hp), h.dtype),
        interpret=common.interpret(),
    )(*xs, _pad2(h, Bp, Hp), *ws)
    return out[:B, :H]


@jax.custom_vjp
def _gru_fused(x, h, w_gate, w_state):
    return _gru_pallas(x, h, w_gate, w_state)


def _gru_fused_fwd(x, h, w_gate, w_state):
    return _gru_fused(x, h, w_gate, w_state), (x, h, w_gate, w_state)


def _gru_fused_bwd(res, ct):
    _, vjp = jax.vjp(_gru_ref_default, *res)
    return vjp(ct)


_gru_fused.defvjp(_gru_fused_fwd, _gru_fused_bwd)


def _gru_pallas_ok(x, h, default_acts):
    if not default_acts or x.ndim != 2 or h.ndim != 2:
        return False
    B, H = h.shape
    Bp, Hp = _ceil_to(B, 8), _ceil_to(H, common.LANE)
    itemsize = jnp.dtype(h.dtype).itemsize
    resident = (5 * Bp * Hp + 3 * Hp * Hp) * itemsize
    ok = common.use_pallas(resident)
    common.note("gru_cell", common.pallas_path() if ok else "ref")
    return ok


def gru_cell(x, h, w_gate, w_state, act_input="tanh", act_gate="sigmoid"):
    """One GRU step: ``x`` ``[B, 3H]`` (projection + bias pre-added),
    ``h`` ``[B, H]``, ``w_gate`` ``[H, 2H]``, ``w_state`` ``[H, H]``;
    returns the new hidden ``[B, H]``."""
    default = (act_input in ("tanh", "", None)
               and act_gate in ("sigmoid", "", None))
    if _gru_pallas_ok(x, h, default):
        return _gru_fused(x, h, w_gate, w_state)
    return _gru_math(x, h, w_gate, w_state,
                     _act(act_input), _act(act_gate))


# -------------------------------------------------- inference variants

def lstm_cell_infer(gates, c_prev, check_i, check_f, check_o,
                    act_input="tanh", act_gate="sigmoid",
                    act_state="tanh"):
    """``lstm_cell`` for the no-grad serving path: the PRIMAL spelling
    only. The training entry wraps the Pallas call in a ``custom_vjp``
    whose forward saves the full operand tuple as residuals and whose
    backward re-traces the reference math — plumbing a scoring/generate
    step never uses but still carries through tracing. This variant
    calls the Pallas primal directly: no residual tuple, no backward
    spelling in the program, and ``jax.grad`` through it fails loudly
    (``pallas_call`` has no AD rule), which PINS it to no-grad routing
    — layers select it only under ``train=False``. The fallback is the
    same verbatim inline math, so off-TPU routing stays bit-invisible
    (``docs/kernels.md``)."""
    default = (act_input in ("tanh", "", None)
               and act_gate in ("sigmoid", "", None)
               and act_state in ("tanh", "", None))
    if _lstm_pallas_ok(gates, c_prev, (check_i, check_f, check_o),
                       default):
        return _lstm_pallas(gates, c_prev, check_i, check_f, check_o)
    return _lstm_math(gates, c_prev, check_i, check_f, check_o,
                      _act(act_input), _act(act_gate), _act(act_state))


def gru_cell_infer(x, h, w_gate, w_state, act_input="tanh",
                   act_gate="sigmoid"):
    """``gru_cell`` for the no-grad serving path — primal-only, same
    contract as :func:`lstm_cell_infer` (no residuals, no backward
    spelling; ``jax.grad`` through the Pallas path fails loudly)."""
    default = (act_input in ("tanh", "", None)
               and act_gate in ("sigmoid", "", None))
    if _gru_pallas_ok(x, h, default):
        return _gru_pallas(x, h, w_gate, w_state)
    return _gru_math(x, h, w_gate, w_state,
                     _act(act_input), _act(act_gate))
