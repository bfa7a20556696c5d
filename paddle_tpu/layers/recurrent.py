"""Recurrent layers: lstmemory, gated_recurrent (GRU), recurrent (simple RNN).

Cell math matches the reference's fused kernels exactly:

- LSTM (``hl_lstm_ops.cuh:46-67``, layer ``LstmLayer.cpp``): the incoming
  projection supplies 4 gate blocks in order **[input, input_gate,
  forget_gate, output_gate]**; recurrent weight is [size, 4*size]; the bias
  parameter is 7*size = 4 gate biases + 3 peephole diagonals (checkI/F/O,
  ``LstmLayer.cpp:58-61``):

      in = actInput(in);  ig = actGate(ig + prevState*checkI)
      fg = actGate(fg + prevState*checkF)
      state = in*ig + prevState*fg
      og = actGate(og + state*checkO);  out = og * actState(state)

- GRU (``hl_gru_ops.cuh:28-81``, ``GruLayer.cpp``): gate blocks
  **[update z, reset r, frame state c]**; gate weight [size, 2*size], state
  weight [size, size] (stored as one [size, 3*size] parameter), bias 3*size:

      z = actGate(xz + h Wz);  r = actGate(xr + h Wr)
      c = actInput(xc + (r*h) Wc);  out = (1-z)*h + z*c

TPU design: time is a ``lax.scan``; the per-step [B,size]x[size,4size]
matmul rides the MXU. Padded steps hold the carried state (mask-guarded), so
ragged semantics survive the padded layout. The reference instead sorts
sequences and shrinks the active batch per step
(``RecurrentGradientMachine.cpp:294-346``) — on TPU static shapes win.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.argument import Argument
from paddle_tpu.core.registry import (LayerImpl, ParamSpec, ShapeInfo,
                                      register_layer)
from paddle_tpu.layers.activations import apply_activation


def _act(name):
    return lambda x: apply_activation(name or "tanh", x)


def _scan_time(step, carry0, xs_tbd, mask_tb, reverse: bool):
    """Scan over [T, B, ...] inputs with state carried through padded steps."""

    def body(carry, inp):
        x_t, m_t = inp
        new_carry, y_t = step(carry, x_t)
        m = m_t[:, None]
        guarded = jax.tree_util.tree_map(
            lambda n, o: jnp.where(m > 0, n, o), new_carry, carry)
        return guarded, y_t * m

    carry, ys = lax.scan(body, carry0, (xs_tbd, mask_tb), reverse=reverse)
    return carry, ys


@register_layer("lstmemory")
class LstmLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        assert in_infos[0].size % 4 == 0, "lstmemory input must be 4*size"
        return ShapeInfo(size=in_infos[0].size // 4, is_sequence=True)

    def params(self, cfg, in_infos):
        size = in_infos[0].size // 4
        # engine layout: one [H, 4H] block so the recurrent matmul is a
        # single MXU op; the WIRE records the reference's 3-dim fused-
        # gate layout (H, H, 4) verbatim (config_parser LstmLayer dims)
        specs = {"w0": ParamSpec(shape=(size, 4 * size),
                                 wire_dims=(size, size, 4))}
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(7 * size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        a = ins[0]
        size = ctx.out_info.size
        act_in_name = cfg.attrs.get("active_type", "tanh")
        act_gate_name = cfg.attrs.get("active_gate_type", "sigmoid")
        act_state_name = cfg.attrs.get("active_state_type", "tanh")
        reverse = bool(cfg.attrs.get("reversed", False))
        w = params["w0"]
        if "wbias" in params:
            b = params["wbias"]
            gate_bias = b[: 4 * size]
            check_i = b[4 * size: 5 * size]
            check_f = b[5 * size: 6 * size]
            check_o = b[6 * size: 7 * size]
        else:
            gate_bias = jnp.zeros((4 * size,), a.value.dtype)
            check_i = check_f = check_o = jnp.zeros((size,), a.value.dtype)

        B = a.value.shape[0]
        xs = jnp.swapaxes(a.value, 0, 1)  # [T, B, 4*size]
        mask = jnp.swapaxes(a.mask, 0, 1)  # [T, B]

        default_acts = (act_in_name in ("tanh", "")
                        and act_gate_name == "sigmoid"
                        and act_state_name in ("tanh", ""))
        carried = None if reverse else ctx.carried.get(cfg.name)
        if default_acts:
            # Fused path (ops/lstm.py): Pallas kernel on TPU, scan elsewhere.
            from paddle_tpu.ops import lstm_sequence
            z = jnp.zeros((B, size), a.value.dtype)
            h0, c0 = carried if carried is not None else (z, z)
            ys, hT, cT = lstm_sequence(xs, mask, w, gate_bias, check_i,
                                       check_f, check_o, h0, c0,
                                       reverse=reverse)
            return Argument(value=jnp.swapaxes(ys, 0, 1), mask=a.mask,
                            state=(hT, cT))

        act_in = _act(act_in_name)
        act_gate = _act(act_gate_name)
        act_state = _act(act_state_name)

        def step(carry, x_t):
            h, c = carry
            gates = x_t + h @ w + gate_bias
            g_in, g_ig, g_fg, g_og = jnp.split(gates, 4, axis=-1)
            g_in = act_in(g_in)
            g_ig = act_gate(g_ig + c * check_i)
            g_fg = act_gate(g_fg + c * check_f)
            state = g_in * g_ig + c * g_fg
            g_og = act_gate(g_og + state * check_o)
            out = g_og * act_state(state)
            return (out, state), out

        z = jnp.zeros((B, size), a.value.dtype)
        h0, c0 = carried if carried is not None else (z, z)
        (hT, cT), ys = _scan_time(step, (h0, c0), xs, mask, reverse)
        return Argument(value=jnp.swapaxes(ys, 0, 1), mask=a.mask,
                        state=(hT, cT))


@register_layer("gated_recurrent")
class GruLayer(LayerImpl):
    def infer(self, cfg, in_infos):
        assert in_infos[0].size % 3 == 0, "gated_recurrent input must be 3*size"
        return ShapeInfo(size=in_infos[0].size // 3, is_sequence=True)

    def params(self, cfg, in_infos):
        size = in_infos[0].size // 3
        specs = {"w0": ParamSpec(shape=(size, 3 * size))}
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(3 * size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        a = ins[0]
        size = ctx.out_info.size
        act_in_name = cfg.attrs.get("active_type", "tanh")
        act_gate_name = cfg.attrs.get("active_gate_type", "sigmoid")
        reverse = bool(cfg.attrs.get("reversed", False))
        w_gate = params["w0"][:, : 2 * size]   # [size, 2*size] for z, r
        w_state = params["w0"][:, 2 * size:]   # [size, size] for candidate
        bias = (params["wbias"] if "wbias" in params
                else jnp.zeros((3 * size,), a.value.dtype))

        B = a.value.shape[0]
        xs = jnp.swapaxes(a.value, 0, 1)
        mask = jnp.swapaxes(a.mask, 0, 1)

        default_acts = (act_in_name in ("tanh", "")
                        and act_gate_name == "sigmoid")
        carried = None if reverse else ctx.carried.get(cfg.name)
        if default_acts:
            from paddle_tpu.ops import gru_sequence
            h0 = carried if carried is not None \
                else jnp.zeros((B, size), a.value.dtype)
            ys, hT = gru_sequence(xs, mask, w_gate, w_state, bias, h0,
                                  reverse=reverse)
            return Argument(value=jnp.swapaxes(ys, 0, 1), mask=a.mask,
                            state=hT)

        act_in = _act(act_in_name)
        act_gate = _act(act_gate_name)

        def step(carry, x_t):
            (h,) = carry
            x_t = x_t + bias
            zr = x_t[:, : 2 * size] + h @ w_gate
            z = act_gate(zr[:, :size])
            r = act_gate(zr[:, size:])
            c = act_in(x_t[:, 2 * size:] + (r * h) @ w_state)
            out = h - z * h + z * c
            return (out,), out

        h0 = carried if carried is not None \
            else jnp.zeros((B, size), a.value.dtype)
        (hT,), ys = _scan_time(step, (h0,), xs, mask, reverse)
        return Argument(value=jnp.swapaxes(ys, 0, 1), mask=a.mask, state=hT)


@register_layer("recurrent")
class SimpleRecurrentLayer(LayerImpl):
    """Elman recurrence out_t = act(x_t + out_{t-1} W)
    (``RecurrentLayer.cpp``); activation applied *inside* the scan, so the
    layer declares act handling itself (executor's post-act is identity
    because cfg.act is consumed here)."""

    def infer(self, cfg, in_infos):
        return ShapeInfo(size=in_infos[0].size, is_sequence=True)

    def params(self, cfg, in_infos):
        size = in_infos[0].size
        specs = {"w0": ParamSpec(shape=(size, size))}
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        a = ins[0]
        act = _act(cfg.attrs.get("active_type", cfg.act or "tanh"))
        reverse = bool(cfg.attrs.get("reversed", False))
        w = params["w0"]
        b = params.get("wbias", 0.0)
        B, T, D = a.value.shape
        xs = jnp.swapaxes(a.value, 0, 1)
        mask = jnp.swapaxes(a.mask, 0, 1)

        def step(carry, x_t):
            (h,) = carry
            out = act(x_t + h @ w + b)
            return (out,), out

        carried = None if reverse else ctx.carried.get(cfg.name)
        h0 = carried if carried is not None \
            else jnp.zeros((B, D), a.value.dtype)
        (hT,), ys = _scan_time(step, (h0,), xs, mask, reverse)
        return Argument(value=jnp.swapaxes(ys, 0, 1), mask=a.mask, state=hT)


@register_layer("gru_step")
class GruStepLayer(LayerImpl):
    """Single GRU step for use inside recurrent groups
    (``GruStepLayer.cpp``): inputs = (gate projection x [B, 3*size],
    previous output [B, size]); the recurrent weight lives here."""

    def infer(self, cfg, in_infos):
        assert in_infos[0].size % 3 == 0
        return ShapeInfo(size=in_infos[0].size // 3)

    def params(self, cfg, in_infos):
        size = in_infos[0].size // 3
        specs = {"w0": ParamSpec(shape=(size, 3 * size))}
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(3 * size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        x, h = ins[0].value, ins[1].value
        size = ctx.out_info.size
        act_in = _act(cfg.attrs.get("active_type", "tanh"))
        act_gate = _act(cfg.attrs.get("active_gate_type", "sigmoid"))
        if "wbias" in params:
            x = x + params["wbias"]
        w_gate = params["w0"][:, : 2 * size]
        w_state = params["w0"][:, 2 * size:]
        zr = x[:, : 2 * size] + h @ w_gate
        z = act_gate(zr[:, :size])
        r = act_gate(zr[:, size:])
        c = act_in(x[:, 2 * size:] + (r * h) @ w_state)
        return Argument(value=h - z * h + z * c)


@register_layer("lstm_step")
class LstmStepLayer(LayerImpl):
    """Single LSTM step (``LstmStepLayer.cpp``): inputs = (combined gate
    input [B, 4*size] — the recurrent projection is a separate fc over the
    output memory — and previous cell state [B, size]). Outputs the hidden
    value; the new cell state is exposed via get_output(arg_name="state"),
    as in the reference."""

    def infer(self, cfg, in_infos):
        assert in_infos[0].size % 4 == 0
        return ShapeInfo(size=in_infos[0].size // 4)

    def params(self, cfg, in_infos):
        size = in_infos[0].size // 4
        if cfg.bias:
            # the reference lstm_step bias is ONLY the three peephole
            # check vectors (create_bias_parameter(bias, size * 3),
            # config_parser.py:3111; LstmStepLayer.cpp:84) — gate biases
            # belong to the input projection layer
            return {"wbias": ParamSpec(shape=(3 * size,), init="zeros",
                                       is_bias=True)}
        return {}

    def apply(self, cfg, params, ins, ctx):
        gates, c_prev = ins[0].value, ins[1].value
        size = ctx.out_info.size
        act_in = _act(cfg.attrs.get("active_type", "tanh"))
        act_gate = _act(cfg.attrs.get("active_gate_type", "sigmoid"))
        act_state = _act(cfg.attrs.get("active_state_type", "tanh"))
        if "wbias" in params:
            b = params["wbias"]
            check_i = b[:size]
            check_f = b[size: 2 * size]
            check_o = b[2 * size: 3 * size]
        else:
            z = jnp.zeros((size,), gates.dtype)
            check_i = check_f = check_o = z
        g_in, g_ig, g_fg, g_og = jnp.split(gates, 4, axis=-1)
        g_in = act_in(g_in)
        g_ig = act_gate(g_ig + c_prev * check_i)
        g_fg = act_gate(g_fg + c_prev * check_f)
        state = g_in * g_ig + c_prev * g_fg
        g_og = act_gate(g_og + state * check_o)
        out = g_og * act_state(state)
        return Argument(value=out, state={"state": state})


@register_layer("mdlstmemory")
class MDLstmLayer(LayerImpl):
    """2-D multi-dimensional LSTM (``MDLstmLayer.cpp``): cell (i,j) sees
    neighbours (i-1,j) and (i,j-1), with one forget gate per direction.
    Input: image-shaped sequence [B, H, W, 5*size] gate projections
    (in, ig, fg_h, fg_w, og). Scanned row-by-row (lax.scan over rows; the
    column recurrence is an inner scan), which XLA pipelines; the
    reference walks the grid cell-by-cell on the host."""

    def infer(self, cfg, in_infos):
        info = in_infos[0]
        assert info.channels % 5 == 0
        size = info.channels // 5
        return ShapeInfo(size=size * info.height * info.width, channels=size,
                         height=info.height, width=info.width)

    def params(self, cfg, in_infos):
        size = in_infos[0].channels // 5
        specs = {"w0": ParamSpec(shape=(2, size, 5 * size))}
        if cfg.bias:
            specs["wbias"] = ParamSpec(shape=(5 * size,), init="zeros",
                                       is_bias=True)
        return specs

    def apply(self, cfg, params, ins, ctx):
        from paddle_tpu.layers.conv import to_nhwc
        info = ctx.in_infos[0]
        x = to_nhwc(ins[0].value, info.channels, info.height, info.width)
        size = ctx.out_info.channels
        w_h, w_w = params["w0"][0], params["w0"][1]
        bias = params.get("wbias", jnp.zeros((5 * size,), x.dtype))
        act_in = _act(cfg.attrs.get("active_type", "tanh"))
        act_gate = _act(cfg.attrs.get("active_gate_type", "sigmoid"))
        act_state = _act(cfg.attrs.get("active_state_type", "tanh"))
        B, H, W, _ = x.shape

        def cell(gates, h_up, c_up, h_left, c_left):
            gates = gates + h_up @ w_h + h_left @ w_w + bias
            g_in, g_ig, g_fh, g_fw, g_og = jnp.split(gates, 5, axis=-1)
            state = (act_in(g_in) * act_gate(g_ig)
                     + c_up * act_gate(g_fh) + c_left * act_gate(g_fw))
            out = act_gate(g_og) * act_state(state)
            return out, state

        def row_step(carry, x_row):
            h_up_row, c_up_row = carry  # [B, W, size]

            def col_step(col_carry, inp):
                h_left, c_left = col_carry
                gates, h_up, c_up = inp
                out, state = cell(gates, h_up, c_up, h_left, c_left)
                return (out, state), (out, state)

            z = jnp.zeros((B, size), x.dtype)
            (_, _), (h_row, c_row) = lax.scan(
                col_step, (z, z),
                (jnp.swapaxes(x_row, 0, 1),
                 jnp.swapaxes(h_up_row, 0, 1),
                 jnp.swapaxes(c_up_row, 0, 1)))
            h_row = jnp.swapaxes(h_row, 0, 1)
            c_row = jnp.swapaxes(c_row, 0, 1)
            return (h_row, c_row), h_row

        z_row = jnp.zeros((B, W, size), x.dtype)
        _, hs = lax.scan(row_step, (z_row, z_row), jnp.swapaxes(x, 0, 1))
        return Argument(value=jnp.swapaxes(hs, 0, 1))  # [B, H, W, size]
