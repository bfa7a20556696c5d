"""CTC alpha-beta recursion as a Pallas TPU kernel.

The reference computes CTC forward-backward per sequence on the host
(`paddle/gserver/layers/LinearChainCTC.cpp:55-150`). Here the whole batch
runs on device over the padded extended label sequence (S = 2L+1,
blank-interleaved, `chain.py` builds it): the kernel fuses the three-way
shifted logsumexp + emission add per time step, carrying alpha [B, S] in
VMEM across the sequentially-executed grid; the S axis pads to the
128-lane width.

The op consumes *pre-gathered* emissions ``emit[b, t, s] =
log_probs[b, t, ext[b, s]]`` — the gather (and its scatter-add transpose
back into the [B, T, C] log-prob tensor) stays outside in XLA autodiff
land, so the hand-written VJP only handles the DP itself: the beta
recursion over the alphas saved by the forward kernel, with
d ll / d emit_t[s] = exp(alpha_t[s] + beta_t[s] - ll)
(the state posterior; beta excludes its own step's emission, so emit_t is
counted exactly once, inside alpha).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import common

NEG = common.NEG
LANE = common.LANE


def _lse3(a, b, c):
    m = jnp.maximum(jnp.maximum(a, b), c)
    m_safe = jnp.maximum(m, NEG)  # all-NEG columns stay NEG, no nan
    return m_safe + jnp.log(jnp.exp(a - m_safe) + jnp.exp(b - m_safe)
                            + jnp.exp(c - m_safe))


def _shift1(x):
    return jnp.concatenate([jnp.full_like(x[:, :1], NEG), x[:, :-1]], axis=1)


def _shift2(x):
    return jnp.concatenate([jnp.full_like(x[:, :2], NEG), x[:, :-2]], axis=1)


def _step(alpha, emit_t, can_skip, valid_s):
    a1 = _shift1(alpha)
    a2 = jnp.where(can_skip > 0, _shift2(alpha), NEG)
    nxt = _lse3(alpha, a1, a2) + emit_t
    return jnp.where(valid_s > 0, nxt, NEG)


def ctc_ll_ref(emit, in_mask, valid_s, can_skip, ext_lens):
    """lax.scan reference. emit [B,T,S] gathered log-probs; in_mask [B,T];
    valid_s/can_skip [B,S] floats; ext_lens [B] ints. Returns ll [B]."""
    B, T, S = emit.shape
    s_idx = jnp.arange(S)[None, :]
    alpha = jnp.where((s_idx <= 1) & (valid_s > 0), emit[:, 0], NEG)

    def body(alpha, inp):
        e_t, m_t = inp
        nxt = _step(alpha, e_t, can_skip, valid_s)
        return jnp.where(m_t[:, None] > 0, nxt, alpha), None

    es = jnp.swapaxes(emit, 0, 1)[1:]
    ms = jnp.swapaxes(in_mask, 0, 1)[1:]
    alpha, _ = lax.scan(body, alpha, (es, ms))
    return _final_ll(alpha, ext_lens)


def _final_ll(alpha, ext_lens):
    last = jnp.take_along_axis(
        alpha, jnp.maximum(ext_lens - 1, 0)[:, None], axis=1)[:, 0]
    last2 = jnp.take_along_axis(
        alpha, jnp.maximum(ext_lens - 2, 0)[:, None], axis=1)[:, 0]
    last2 = jnp.where(ext_lens >= 2, last2, NEG)
    m = jnp.maximum(last, last2)
    return m + jnp.log(jnp.exp(last - m) + jnp.exp(last2 - m))


# ---------------------------------------------------------------- pallas

def _ctc_kernel(emit_ref, mask_ref, skip_ref, valid_ref, a0_ref,
                alphas_ref, alpha_s):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        alpha_s[:] = a0_ref[:]

    alpha = alpha_s[:]
    nxt = _step(alpha, emit_ref[0], skip_ref[:], valid_ref[:])
    alpha = jnp.where(mask_ref[0] > 0, nxt, alpha)
    alpha_s[:] = alpha
    alphas_ref[0] = alpha


def _ctc_alphas_pallas(emit, in_mask, valid_s, can_skip):
    B, T, S = emit.shape
    dt = emit.dtype
    s_idx = jnp.arange(S)[None, :]
    alpha0 = jnp.where((s_idx <= 1) & (valid_s > 0), emit[:, 0], NEG)
    t_block, full = common.time_block, common.resident_block
    es = jnp.swapaxes(emit, 0, 1)
    ms = jnp.swapaxes(in_mask, 0, 1)[:, :, None]
    ms = ms.at[0].set(0.0)  # step 0 only records alpha_0
    alphas = pl.pallas_call(
        _ctc_kernel,
        grid=(T,),
        in_specs=[t_block(B, S), t_block(B, 1), full(B, S), full(B, S),
                  full(B, S)],
        out_specs=t_block(B, S),
        out_shape=jax.ShapeDtypeStruct((T, B, S), dt),
        scratch_shapes=[pltpu.VMEM((B, S), dt)],
        interpret=common.interpret(),
    )(es, ms, can_skip, valid_s, alpha0)
    return jnp.swapaxes(alphas, 0, 1)  # [B,T,S]


@jax.custom_vjp
def _ctc_core(emit, in_mask, valid_s, can_skip, ext_lens):
    alphas = _ctc_alphas_pallas(emit, in_mask, valid_s, can_skip)
    return _final_ll(alphas[:, -1], ext_lens)


def _ctc_fwd(emit, in_mask, valid_s, can_skip, ext_lens):
    alphas = _ctc_alphas_pallas(emit, in_mask, valid_s, can_skip)
    ll = _final_ll(alphas[:, -1], ext_lens)
    return ll, (emit, in_mask, valid_s, can_skip, ext_lens, alphas, ll)


def _ctc_bwd(res, g):
    """Beta recursion (suffix scores EXCLUDING the step-t emission):
    beta_{T-1}[s] = 0 at s in {len-1, len-2}, else -inf; going backwards
    beta_t[s] = lse3(beta_{t+1}[s], beta_{t+1}[s+1],
                     beta_{t+1}[s+2] if skippable) + emit_{t+1}[.] folded
    as forward-shifted terms. Frozen where step t+1 is padding."""
    emit, in_mask, valid_s, can_skip, ext_lens, alphas, ll = res
    B, T, S = emit.shape
    s_idx = jnp.arange(S)[None, :]
    beta_last = jnp.where(
        (s_idx == jnp.maximum(ext_lens - 1, 0)[:, None])
        | ((s_idx == jnp.maximum(ext_lens - 2, 0)[:, None])
           & (ext_lens[:, None] >= 2)),
        0.0, NEG)

    def shift_m1(x):  # x[s+1]
        return jnp.concatenate(
            [x[:, 1:], jnp.full_like(x[:, :1], NEG)], axis=1)

    def shift_m2(x):  # x[s+2]
        return jnp.concatenate(
            [x[:, 2:], jnp.full_like(x[:, :2], NEG)], axis=1)

    # can_skip[s] gates the s-2 -> s jump; from state s the jump to s+2 is
    # allowed iff can_skip[s+2]
    skip_fwd = shift_m2(jnp.where(can_skip > 0, 0.0, NEG))

    def body(beta, inp):
        e_next, m_next = inp  # emission + mask of step t+1
        y = beta + e_next  # beta'_{t+1}[s] including its own emission
        stay = y
        up1 = shift_m1(y)
        up2 = shift_m2(y) + skip_fwd
        prev = _lse3(stay, up1, up2)
        prev = jnp.where(valid_s > 0, prev, NEG)
        return jnp.where(m_next[:, None] > 0, prev, beta), beta

    es = jnp.swapaxes(emit, 0, 1)[1:]
    ms = jnp.swapaxes(in_mask, 0, 1)[1:]
    beta0, betas_rest = lax.scan(body, beta_last, (es, ms), reverse=True)
    betas = jnp.concatenate([beta0[None], betas_rest], axis=0)  # [T,B,S]
    betas = jnp.swapaxes(betas, 0, 1)

    # d ll / d emit_t[s] = P(state s at step t) = exp(alpha_t + beta_t - ll)
    # (alpha covers emissions <= t, beta covers > t, so emit_t is counted
    # exactly once, inside alpha)
    post = jnp.exp(jnp.minimum(alphas + betas - ll[:, None, None], 30.0))
    demit = g[:, None, None] * post * in_mask[:, :, None]
    return demit, None, None, None, None


_ctc_core.defvjp(_ctc_fwd, _ctc_bwd)


# ---------------------------------------------------------------- public

def ctc_ll(emit, in_mask, valid_s, can_skip, ext_lens):
    """Log-likelihood [B] of the CTC paths. Pallas on TPU (S padded to the
    128-lane width by the caller or here), lax.scan elsewhere. Traced into a
    step partitioned over a mesh whose batch axes divide B, each device
    runs the kernel on its own rows (``common.batch_local``)."""
    B, T, S = emit.shape
    split = common.batch_split(B)
    Sp = ((S + LANE - 1) // LANE) * LANE
    itemsize = jnp.dtype(emit.dtype).itemsize
    resident = itemsize * 6 * (B // max(split, 1)) * Sp
    if split == 0 or not common.use_pallas(resident):
        common.note("ctc", "ref")
        return ctc_ll_ref(emit, in_mask, valid_s, can_skip, ext_lens)
    common.note("ctc", common.pallas_path())
    if Sp != S:
        pc = Sp - S
        emit = jnp.pad(emit, ((0, 0), (0, 0), (0, pc)), constant_values=NEG)
        valid_s = jnp.pad(valid_s, ((0, 0), (0, pc)))
        can_skip = jnp.pad(can_skip, ((0, 0), (0, pc)))
    core = common.batch_local(_ctc_core, split,
                              in_dims=(0, 0, 0, 0, 0), out_dims=0)
    return core(emit, in_mask, valid_s, can_skip, ext_lens)
