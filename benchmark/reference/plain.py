"""What the plain references share: weights from a seed, the two
optimizers as their papers write them, and the arithmetic modes.

Nothing here imports the program. A reference module
(``benchmark/reference/<config>.py``) gives

- ``leaves(cfg)``: ``{name: (shape, kind)}`` with kind ``normal`` (std
  1/sqrt(fan-in), fan-in = product of all axes but the last), ``ones``,
  ``const:<value>``, ``zeros`` or ``static`` (zeros, never trained:
  batch-norm's moving statistics). The names are the program's parameter names, so the
  weights made here can be handed to it.
- ``loss(params, batch, cfg, arith)``: the mean loss over the batch.

``arith`` says how the products are computed: ``Arith()`` is float32 at
``highest``. ``Arith(product="bfloat16")`` is a TPU's float32 product at
JAX's default precision, as a configuration may state it: every matrix
product, the backward pass's two as well, multiplies operands rounded to
bfloat16 and accumulates in float32. ``Arith(operand="bfloat16")`` rounds
both operands of every forward product and convolution to that type (per
tensor, scaled to the type's range for the 8-bit floats) and lets the
gradient pass straight through, and ``Arith(store="bfloat16")`` also
rounds what each layer hands on: the lower precisions that the controls
compute in.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def one_pass_matmul(name: str):
    """``a [..., K] @ b [K, N]`` as the MXU computes it in one pass of
    ``name``: both operands rounded, the sum in float32; the cotangent
    is rounded as an operand of the backward pass's two products too."""
    info = jnp.finfo(jnp.dtype(name))

    def r(x):
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)

    @jax.custom_vjp
    def mm(a, b):
        return jnp.matmul(r(a), r(b), precision=HIGHEST)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(saved, g):
        a, b = saved
        g = r(g)
        da = jnp.matmul(g, r(b).T, precision=HIGHEST)
        db = jnp.matmul(r(a).reshape(-1, a.shape[-1]).T,
                        g.reshape(-1, g.shape[-1]), precision=HIGHEST)
        return da, db

    mm.defvjp(fwd, bwd)
    return mm


@dataclasses.dataclass(frozen=True)
class Arith:
    product: Optional[str] = None   # dtype every product multiplies in
    operand: Optional[str] = None   # dtype forward operands round to
    store: Optional[str] = None     # dtype each layer's output rounds to
    carry: Optional[str] = None     # dtype a recurrence's state rounds to

    def _round(self, x, name):
        """``x`` rounded to the type's exponent and mantissa widths by
        ``lax.reduce_precision`` (a cast there and back is a pair that
        XLA may drop: it allows excess precision). The gradient passes
        straight through."""
        info = jnp.finfo(jnp.dtype(name))
        v = jax.lax.stop_gradient(x)
        scale = 1.0
        if info.bits == 8:
            # an 8-bit float holds a narrow range: scale by the tensor's
            # largest magnitude as an fp8 recipe does (to half the top,
            # so that rounding up cannot overflow)
            scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) \
                / (float(info.max) / 2)
        rounded = jax.lax.reduce_precision(
            v / scale, exponent_bits=info.nexp,
            mantissa_bits=info.nmant) * scale
        return x + (rounded - v)

    def op(self, x):
        return x if self.operand is None else self._round(x, self.operand)

    def out(self, x):
        return x if self.store is None else self._round(x, self.store)

    def keep(self, x):
        """What a recurrence carries to its next step."""
        name = self.carry or self.store
        return x if name is None else self._round(x, name)

    def mm(self, a, b):
        """A matrix product, before what ``store`` does to its result."""
        if self.product is not None:
            return one_pass_matmul(self.product)(self.op(a), self.op(b))
        return jnp.matmul(self.op(a), self.op(b), precision=HIGHEST)

    def dot(self, a, b):
        return self.out(self.mm(a, b))

    def conv(self, x, w, stride, pad):
        if self.product is not None:
            raise NotImplementedError("no one-pass convolution yet: the "
                                      "first configuration to state one "
                                      "brings it")
        return self.out(jax.lax.conv_general_dilated(
            self.op(x), self.op(w), window_strides=(stride, stride),
            padding=((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST))


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def make_weights(leaves: Dict[str, Tuple[tuple, str]], seed: int):
    """Every leaf on the device in one jitted call from the seed."""
    names = sorted(leaves)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = leaves[name]
            if kind == "normal":
                fan_in = max(1, math.prod(shape[:-1]))
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape,
                    jnp.float32) / math.sqrt(fan_in)
            elif kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind.startswith("const:"):
                out[name] = jnp.full(shape, float(kind[6:]), jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return jax.jit(build)(seed_key(seed))


def trained(leaves) -> list:
    return sorted(n for n, (_, kind) in leaves.items() if kind != "static")


# ------------------------------------------------------------ optimizers
# each: init(params) -> state; step(params, grads, state, hp, t) ->
# (params, state), t the step's number from 1; first_grad(slot_after_one
# _step, params0, hp) -> the gradient the optimizer was given at step 1,
# worked out from its state (less the L2 term; a clipping threshold that
# binds would show as a gap).

def adam_init(params):
    z = {n: jnp.zeros_like(p) for n, p in params.items()}
    return {"m": z, "v": dict(z)}


def _as_given(g, p, hp):
    """The gradient as the update takes it: clipped by value, then the
    L2 term added (``grad + value * decayRate``, as the source's
    optimizers apply it)."""
    if hp["clip"]:
        g = jnp.clip(g, -hp["clip"], hp["clip"])
    return g + hp["weight_decay"] * p


def adam_step(params, grads, state, hp, t):
    b1, b2, eps, lr = hp["beta1"], hp["beta2"], hp["epsilon"], hp["lr"]
    gs = {n: _as_given(g, params[n], hp) for n, g in grads.items()}
    m = {n: b1 * state["m"][n] + (1 - b1) * g for n, g in gs.items()}
    v = {n: b2 * state["v"][n] + (1 - b2) * g * g for n, g in gs.items()}
    alpha = lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new = {n: params[n] - alpha * m[n] / (jnp.sqrt(v[n]) + eps)
           for n in grads}
    return new, {"m": m, "v": v}


def adam_first_grad(first_moment, params0, hp):
    return {n: m / (1 - hp["beta1"]) - hp["weight_decay"] * params0[n]
            for n, m in first_moment.items()}


def momentum_init(params):
    return {"mom": {n: jnp.zeros_like(p) for n, p in params.items()}}


def momentum_step(params, grads, state, hp, t):
    mu, lr = hp["momentum"], hp["lr"]
    mom = {n: mu * state["mom"][n] - lr * _as_given(g, params[n], hp)
           for n, g in grads.items()}
    return {n: params[n] + mom[n] for n in grads}, {"mom": mom}


def momentum_first_grad(velocity, params0, hp):
    return {n: -v / hp["lr"] - hp["weight_decay"] * params0[n]
            for n, v in velocity.items()}


# kind -> (init, step, first_grad, the program's slot first_grad reads)
OPTIMIZERS = {
    "adam": (adam_init, adam_step, adam_first_grad, "mom"),
    "momentum": (momentum_init, momentum_step, momentum_first_grad, "mom"),
}


def norms(tree) -> Dict[str, jnp.ndarray]:
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}
