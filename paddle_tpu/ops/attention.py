"""Attention kernels: Pallas flash attention + blockwise-scan reference.

The 2017 reference has no fused attention (its only attention is the
composite `simple_attention` in `trainer_config_helpers/networks.py`);
this module is where the TPU build exceeds it, and it is the per-device
compute block of ring attention (parallel/ring.py): sequence parallelism
needs an attention that consumes KV in blocks with online-softmax running
state, which is exactly the flash decomposition.

Three tiers:
- ``mha_reference`` — plain softmax attention, ground truth for tests.
- ``blockwise_attention`` — pure-JAX ``lax.scan`` over KV blocks with
  online softmax (max/sum running stats). Memory O(T_q·block) instead of
  O(T_q·T_k); differentiable by autodiff; runs anywhere.
- ``flash_attention`` — Pallas kernel: grid (batch·heads, q-blocks,
  kv-blocks), kv innermost so the accumulator lives in VMEM scratch across
  the kv sweep. Backward = recompute via ``jax.vjp`` of
  ``blockwise_attention`` (flash-bwd recompute strategy).

All take [B, N, T, D] and an optional kv validity mask [B, T_k] plus a
``causal`` flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import common

_NEG = -1e9


def mha_reference(q, k, v, kv_mask=None, causal=False, scale=None):
    """Plain attention. q [B,N,Tq,D], k/v [B,N,Tk,D], kv_mask [B,Tk]."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, _NEG)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        qi = jnp.arange(Tq)[:, None] + (Tk - Tq)
        kj = jnp.arange(Tk)[None, :]
        s = jnp.where(kj <= qi, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnqk,bnkd->bnqd", p, v)


def blockwise_attention(q, k, v, kv_mask=None, causal=False, scale=None,
                        block_k=512):
    """Memory-efficient attention: lax.scan over KV blocks with online
    softmax. Differentiable; the ground-truth backward for flash."""
    B, N, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, Tk)
    pad = (-Tk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        base = (kv_mask if kv_mask is not None
                else jnp.ones((B, Tk), q.dtype))
        kv_mask = jnp.pad(base, ((0, 0), (0, pad)))
    nk = k.shape[2] // block_k
    kb = k.reshape(B, N, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, N, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    mb = (kv_mask.reshape(B, nk, block_k).transpose(1, 0, 2)
          if kv_mask is not None else None)
    qi = jnp.arange(Tq)[:, None] + (Tk - Tq)

    def body(carry, inp):
        acc, m_run, l_run = carry
        idx, k_t, v_t, msk = inp
        s = jnp.einsum("bnqd,bnkd->bnqk", q, k_t) * scale
        if msk is not None:
            s = jnp.where(msk[:, None, None, :] > 0, s, _NEG)
        if causal:
            kj = idx * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(kj <= qi, s, _NEG)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bnqk,bnkd->bnqd", p, v_t)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((B, N, Tq, D), jnp.float32)
    m0 = jnp.full((B, N, Tq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, N, Tq), jnp.float32)
    if mb is None:
        (acc, m_run, l_run), _ = lax.scan(
            lambda c, i: body(c, (i[0], i[1], i[2], None)), (acc0, m0, l0),
            (jnp.arange(nk), kb, vb))
    else:
        (acc, m_run, l_run), _ = lax.scan(body, (acc0, m0, l0),
                                          (jnp.arange(nk), kb, vb, mb))
    return (acc / l_run[..., None]).astype(q.dtype)


# ---------------------------------------------------------------- pallas

def _flash_kernel(tq_orig, tk_orig, scale, causal,
                  q_ref, k_ref, v_ref, mask_ref,
                  o_ref, acc_s, m_s, l_s):
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        acc_s[:] = jnp.zeros_like(acc_s)
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)

    q = q_ref[0]          # [Bq, D]
    k = k_ref[0]          # [Bk, D]
    v = v_ref[0]
    Bq, Bk = q.shape[0], k.shape[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    msk = mask_ref[0]     # [1, Bk] validity of this kv block
    s = jnp.where(msk > 0, s, _NEG)
    if causal:
        qb = pl.program_id(1)
        qi = (qb * Bq + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 0)
              + (tk_orig - tq_orig))
        kj = kb * Bk + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 1)
        s = jnp.where(kj <= qi, s, _NEG)
    m_prev = m_s[:, 0:1]                                     # [Bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)                          # [Bq, 1]
    l_s[:, 0:1] = l_s[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[:] = (acc_s[:] * alpha
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32))
    m_s[:, 0:1] = m_new

    @pl.when(kb == nk - 1)
    def _():
        o_ref[0] = (acc_s[:] / l_s[:, 0:1]).astype(o_ref.dtype)


def _flash_forward(q, k, v, kv_mask, causal, scale, block_q, block_k):
    B, N, Tq, D = q.shape
    Tk = k.shape[2]
    tk_orig = Tk
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    pad_q = (-Tq) % block_q
    pad_k = (-Tk) % block_k
    if kv_mask is None:
        kv_mask = jnp.ones((B, Tk), jnp.float32)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad_k)))
    Tqp, Tkp = q.shape[2], k.shape[2]
    qf = q.reshape(B * N, Tqp, D)
    kf = k.reshape(B * N, Tkp, D)
    vf = v.reshape(B * N, Tkp, D)
    nq, nk = Tqp // block_q, Tkp // block_k
    kernel = functools.partial(_flash_kernel, Tq, tk_orig, scale, causal)
    out = pl.pallas_call(
        kernel,
        grid=(B * N, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bn, qb, kb: (bn, qb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda bn, qb, kb: (bn, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda bn, qb, kb: (bn, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda bn, qb, kb: (bn // N, 0, kb),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bn, qb, kb: (bn, qb, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * N, Tqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=common.interpret(),
    )(qf, kf, vf, kv_mask[:, None, :])
    return out.reshape(B, N, Tqp, D)[:, :, :Tq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_core(q, k, v, kv_mask, causal, scale, block_q, block_k):
    return _flash_forward(q, k, v, kv_mask, causal, scale, block_q, block_k)


def _flash_fwd(q, k, v, kv_mask, causal, scale, block_q, block_k):
    out = _flash_forward(q, k, v, kv_mask, causal, scale, block_q, block_k)
    return out, (q, k, v, kv_mask)


def _flash_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, kv_mask = res
    # Flash-style recompute backward: autodiff the blockwise formulation.
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, kv_mask, causal=causal, scale=scale,
            block_k=block_k), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, kv_mask=None, causal=False, scale=None,
                    block_q=256, block_k=256):
    """Flash attention. Pallas on TPU, blockwise-scan elsewhere. Traced
    into a step partitioned over a mesh whose batch axes divide B, each
    device runs the kernel on its own rows (``common.batch_local``)."""
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    resident = jnp.dtype(q.dtype).itemsize * (
        3 * min(block_k, k.shape[2]) * D + 2 * min(block_q, q.shape[2]) * D)
    split = common.batch_split(q.shape[0])
    if split == 0 or not common.use_pallas(resident):
        common.note("flash_attention", "ref")
        return blockwise_attention(q, k, v, kv_mask, causal=causal,
                                   scale=scale, block_k=block_k)
    common.note("flash_attention", common.pallas_path())
    if split > 1:
        if kv_mask is None:
            kv_mask = jnp.ones((k.shape[0], k.shape[2]), jnp.float32)
        core = common.batch_local(
            lambda q_, k_, v_, m_: _flash_core(
                q_, k_, v_, m_, causal, scale, block_q, block_k),
            split, in_dims=(0, 0, 0, 0), out_dims=0)
        return core(q, k, v, kv_mask)
    return _flash_core(q, k, v, kv_mask, causal, scale, block_q, block_k)
