"""Linear-chain CRF partition function as a Pallas TPU kernel.

The reference computes the CRF forward-backward on the host, one sequence
at a time (`paddle/gserver/layers/LinearChainCRF.cpp:28-102`). The TPU
design keeps the whole batch on device and makes the time recursion MXU
work: in log space the alpha update is

    alpha_{t}[b, j] = logsumexp_i(alpha_{t-1}[b, i] + trans[i, j]) + x_t[b, j]

which, max-shifted, is an exp-space matrix product

    m[b]   = max_i alpha_{t-1}[b, i]
    S      = exp(alpha_{t-1} - m) @ exp(trans - tm)        # [B,C] x [C,C]
    alpha_t = log(S) + m + tm + x_t

so each step is one [B,C]x[C,C] matmul on the systolic array plus VPU
elementwise work — the same "keep the weight resident, fuse the step" shape
as the fused LSTM kernel (`ops/lstm.py`). The class axis is padded to the
128-lane width with -inf emissions/transitions, which round-trip through
the exp-space matmul as exact zeros.

Backward is the analytic beta recursion (marginals = d log Z), run as a
`lax.scan` over the alphas the forward kernel saved — no autodiff through
the time loop, mirroring the cuDNN-style "save activations" strategy used
by the other fused kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import common

NEG = common.NEG
LANE = common.LANE


def _pad_classes(x, trans, a, b):
    """Pad the class axis to a LANE multiple with -inf scores."""
    C = x.shape[-1]
    Cp = ((C + LANE - 1) // LANE) * LANE
    if Cp == C:
        return x, trans, a, b, C
    pc = Cp - C
    x = jnp.pad(x, ((0, 0), (0, 0), (0, pc)), constant_values=NEG)
    trans = jnp.pad(trans, ((0, pc), (0, pc)), constant_values=NEG)
    a = jnp.pad(a, (0, pc), constant_values=NEG)
    b = jnp.pad(b, (0, pc), constant_values=NEG)
    return x, trans, a, b, C


def _step(alpha, trans_shift, tm, x_t):
    """One max-shifted exp-space alpha update (shared by ref and bwd)."""
    m = jnp.max(alpha, axis=-1, keepdims=True)
    s = jnp.exp(alpha - m) @ trans_shift
    return jnp.log(jnp.maximum(s, 1e-37)) + m + tm + x_t


def crf_log_z_ref(x, mask, trans, a, b):
    """lax.scan reference. x [B,T,C], mask [B,T], trans [C,C], a/b [C].
    Returns log Z [B] (alpha frozen on padded steps)."""
    tm = jnp.max(trans)
    trans_shift = jnp.exp(trans - tm)
    alpha0 = a[None, :] + x[:, 0]

    def body(alpha, inp):
        x_t, m_t = inp
        nxt = _step(alpha, trans_shift, tm, x_t)
        return jnp.where(m_t[:, None] > 0, nxt, alpha), None

    xs = jnp.swapaxes(x, 0, 1)[1:]
    ms = jnp.swapaxes(mask, 0, 1)[1:]
    alpha, _ = lax.scan(body, alpha0, (xs, ms))
    m = jnp.max(alpha + b[None, :], axis=-1, keepdims=True)
    return jnp.squeeze(m, -1) + jnp.log(
        jnp.sum(jnp.exp(alpha + b[None, :] - m), axis=-1))


# ---------------------------------------------------------------- pallas fwd

def _crf_kernel(xs_ref, mask_ref, trans_ref, tm_ref, a_ref, x0_ref,
                alphas_ref, alpha_s):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        alpha_s[:] = a_ref[:] + x0_ref[:]

    alpha = alpha_s[:]
    tm = tm_ref[0, 0]
    m = jnp.max(alpha, axis=-1, keepdims=True)
    s = jnp.dot(jnp.exp(alpha - m), trans_ref[:],
                preferred_element_type=jnp.float32).astype(alpha.dtype)
    nxt = jnp.log(jnp.maximum(s, 1e-37)) + m + tm + xs_ref[0]
    alpha = jnp.where(mask_ref[0] > 0, nxt, alpha)
    alpha_s[:] = alpha
    alphas_ref[0] = alpha


def _crf_alphas_pallas(x, mask, trans, a):
    """All alphas [T,B,C] with the recursion fused in one kernel; the
    returned array includes alpha_0 at index 0 (computed in-kernel)."""
    B, T, C = x.shape
    dt = x.dtype
    tm = jnp.max(trans)
    trans_shift = jnp.exp(trans - tm)
    t_block, full = common.time_block, common.resident_block
    xs = jnp.swapaxes(x, 0, 1)  # [T,B,C]; step t consumes xs[t] (t>=1)
    ms = jnp.swapaxes(mask, 0, 1)[:, :, None]
    # grid step 0 writes alpha_0 (mask forced 0 so the update freezes),
    # steps 1..T-1 run the recursion
    ms = ms.at[0].set(0.0)
    alphas = pl.pallas_call(
        _crf_kernel,
        grid=(T,),
        in_specs=[
            t_block(B, C),                 # xs (consumed at step t)
            t_block(B, 1),                 # mask
            full(C, C),                    # exp(trans - tm), resident
            full(1, 1),                    # tm
            full(B, C),                    # a + broadcast (as [B,C])
            full(B, C),                    # x[:, 0]
        ],
        out_specs=t_block(B, C),
        out_shape=jax.ShapeDtypeStruct((T, B, C), dt),
        scratch_shapes=[pltpu.VMEM((B, C), dt)],
        interpret=common.interpret(),
    )(xs, ms, trans_shift, tm.reshape(1, 1),
      jnp.broadcast_to(a[None, :], (B, C)), x[:, 0])
    return jnp.swapaxes(alphas, 0, 1)  # [B,T,C]


# ------------------------------------------------------------- custom vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _crf_core(x, mask, trans, a, b):
    alphas = _crf_alphas_pallas(x, mask, trans, a)
    last = alphas[:, -1] + b[None, :]
    m = jnp.max(last, axis=-1, keepdims=True)
    return jnp.squeeze(m, -1) + jnp.log(jnp.sum(jnp.exp(last - m), axis=-1))


def _crf_fwd(x, mask, trans, a, b):
    alphas = _crf_alphas_pallas(x, mask, trans, a)
    last = alphas[:, -1] + b[None, :]
    m = jnp.max(last, axis=-1, keepdims=True)
    log_z = jnp.squeeze(m, -1) + jnp.log(
        jnp.sum(jnp.exp(last - m), axis=-1))
    return log_z, (x, mask, trans, a, b, alphas, log_z)


def _crf_bwd(res, g):
    """Marginals via the beta recursion over saved alphas.

    d log Z / d x_t[j]      = q_t[j]            (unary marginal, masked)
    d log Z / d trans[i,j]  = sum_t p_t[i,j]    (pairwise marginal)
    d log Z / d a[i]        = q_0[i];  d/d b[j] = q_T[j]
    """
    x, mask, trans, a, b, alphas, log_z = res
    B, T, C = x.shape
    tm = jnp.max(trans)
    trans_shift = jnp.exp(trans - tm)  # [prev, next]

    # beta_T = b; beta_{t-1}[i] = logsumexp_j(trans[i,j] + x_t[j] + beta_t[j])
    # (frozen where step t is padding). Scan produces betas for t=T-1..0.
    def body(beta, inp):
        x_t, m_t = inp  # step-t emission + mask, t in [1, T-1]
        y = x_t + beta  # [B, C]
        m = jnp.max(y, axis=-1, keepdims=True)
        prev = jnp.log(jnp.maximum(
            jnp.exp(y - m) @ trans_shift.T, 1e-37)) + m + tm
        prev = jnp.where(m_t[:, None] > 0, prev, beta)
        return prev, beta

    xs = jnp.swapaxes(x, 0, 1)[1:]      # [T-1,B,C]
    ms = jnp.swapaxes(mask, 0, 1)[1:]
    beta0, betas_rest = lax.scan(
        body, jnp.broadcast_to(b[None, :], (B, C)), (xs, ms), reverse=True)
    betas = jnp.concatenate(
        [beta0[None], betas_rest], axis=0)  # [T,B,C], betas[t] for step t
    betas = jnp.swapaxes(betas, 0, 1)       # [B,T,C]

    # unary marginals (alpha_t already includes x_t; q_0 IS the start
    # marginal since alpha_0 includes a)
    q = jnp.exp(alphas + betas - log_z[:, None, None])
    q = q * mask[:, :, None]
    dx = g[:, None, None] * q

    # pairwise marginals, accumulated exactly in probability space:
    # p_t[i,j] = exp(alpha_{t-1}[i] + trans[i,j] + x_t[j] + beta_t[j] - logZ)
    # The log-score is <= a small slack above 0 (it is a path posterior),
    # so exponentiating the SUMMED score never overflows — unlike any
    # outer-product factorization, whose per-factor scale blows up for
    # strongly forbidden transitions (trans[i,j] ~ -1e4). One [B,C,C]
    # block per step, scanned over time.
    a_prev = jnp.swapaxes(alphas[:, :-1], 0, 1)       # [T-1,B,C] (i axis)
    r_next = jnp.swapaxes(x[:, 1:] + betas[:, 1:], 0, 1)  # [T-1,B,C] (j)
    pair_m = jnp.swapaxes(mask[:, 1:] * mask[:, :-1], 0, 1)  # [T-1,B]

    def pair_body(acc, inp):
        a_t, r_t, m_t = inp
        s = (a_t[:, :, None] + trans[None] + r_t[:, None, :]
             - log_z[:, None, None])
        p = jnp.exp(jnp.minimum(s, 30.0)) * (m_t * g)[:, None, None]
        return acc + jnp.sum(p, axis=0), None

    dtrans, _ = lax.scan(pair_body, jnp.zeros_like(trans),
                         (a_prev, r_next, pair_m))

    da = jnp.sum(g[:, None] * q[:, 0], axis=0)
    # end marginal: probability mass of the state at the last real step.
    # With frozen alphas, alpha_{T-1} holds the final state, so
    # q_end = exp(alpha_last + b - logZ)
    last = alphas[:, -1] + b[None, :]
    q_end = jnp.exp(last - log_z[:, None])
    db = jnp.sum(g[:, None] * q_end, axis=0)
    return dx, None, dtrans, da, db


_crf_core.defvjp(_crf_fwd, _crf_bwd)


# ---------------------------------------------------------------- public

def crf_log_z(x, mask, trans, a, b):
    """log Z [B] for a batch of linear-chain CRFs. Pallas on TPU (class
    axis padded to the 128-lane width), lax.scan elsewhere. Traced into a
    step partitioned over a mesh whose batch axes divide B, each device
    runs the kernel on its own rows (``common.batch_local``)."""
    B, T, C = x.shape
    split = common.batch_split(B)
    itemsize = jnp.dtype(x.dtype).itemsize
    Cp = ((C + LANE - 1) // LANE) * LANE
    resident = itemsize * (Cp * Cp + 4 * (B // max(split, 1)) * Cp)
    if split == 0 or not common.use_pallas(resident):
        common.note("crf", "ref")
        return crf_log_z_ref(x, mask, trans, a, b)
    common.note("crf", common.pallas_path())
    xp, transp, ap, bp, _ = _pad_classes(x, trans, a, b)
    core = common.batch_local(_crf_core, split,
                              in_dims=(0, 0, None, None, None), out_dims=0)
    return core(xp, mask, transp, ap, bp)
