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
- ``flash_attention`` — Pallas kernels: forward on a grid (batch·heads,
  q-blocks, kv-blocks), kv innermost so the accumulator lives in VMEM
  scratch across the kv sweep; it also hands back each row's
  log-sum-exp. Backward = two kernels that recompute the scores block by
  block from that log-sum-exp (dK/dV with the q sweep innermost, dQ with
  the kv sweep innermost): memory linear in T, nothing of size T_q·T_k
  is ever held. Under ``causal`` the blocks that lie wholly above the
  diagonal are skipped, in all three (their index maps repeat the last
  needed block, so nothing is fetched for them either). The forward rule
  names its output and the log-sum-exp ``common.KEPT_RESIDUAL``: a
  layer under the executor's checkpoint keeps those two (T·Dv and T a
  head) and recomputes what led to q, k and v, so the forward kernel
  runs once a step, not again in the backward pass.

All take q, k of [B, N, T, Dqk] and v of [B, N, T_k, Dv] (the two head
sizes may differ: latent attention has 192 and 128), an optional kv
validity mask [B, T_k] and a ``causal`` flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import common

_NEG = -1e9


def mha_reference(q, k, v, kv_mask=None, causal=False, scale=None):
    """Plain attention. q [B,N,Tq,Dqk], k [B,N,Tk,Dqk], v [B,N,Tk,Dv],
    kv_mask [B,Tk]."""
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
    Tk, Dv = k.shape[2], v.shape[-1]
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
    vb = v.reshape(B, N, nk, block_k, Dv).transpose(2, 0, 1, 3, 4)
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

    acc0 = jnp.zeros((B, N, Tq, Dv), jnp.float32)
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

_TRANS_B = (((1,), (1,)), ((), ()))     # a [M,K] x b [N,K] -> [M,N]
_STAT_LANES = 128     # a row statistic is kept broadcast over one lane tile


def _scores(off, scale, causal, q, k, msk, qb, kb):
    """The masked, scaled score tile [Bq, Bk] in float32. ``off`` is
    T_k - T_q: query row i sees keys up to i + off."""
    Bq, Bk = q.shape[0], k.shape[0]
    s = lax.dot_general(q, k, _TRANS_B,
                        preferred_element_type=jnp.float32) * scale
    s = jnp.where(msk > 0, s, _NEG)
    if causal:
        qi = qb * Bq + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 0) + off
        kj = kb * Bk + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 1)
        s = jnp.where(kj <= qi, s, _NEG)
    return s


def _visible(off, causal, Bq, Bk, qb, kb):
    """Does q block ``qb`` see any key of kv block ``kb``?"""
    if not causal:
        return None
    return kb * Bk <= qb * Bq + Bq - 1 + off


def _when(cond):
    """``pl.when`` that is no condition at all for ``None``."""
    return (lambda f: f()) if cond is None else pl.when(cond)


def _flash_kernel(off, scale, causal, q_ref, k_ref, v_ref, mask_ref,
                  o_ref, lse_ref, acc_s, m_s, l_s):
    qb, kb = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        acc_s[:] = jnp.zeros_like(acc_s)
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)

    Bq, Bk = q_ref.shape[1], k_ref.shape[1]

    @_when(_visible(off, causal, Bq, Bk, qb, kb))
    def _():
        v = v_ref[0]
        s = _scores(off, scale, causal, q_ref[0], k_ref[0], mask_ref[0],
                    qb, kb)
        m_prev = m_s[:, 0:1]                                     # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                          # [Bq, 1]
        l_s[:, 0:1] = l_s[:, 0:1] * alpha + jnp.sum(p, axis=-1,
                                                    keepdims=True)
        acc_s[:] = (acc_s[:] * alpha
                    + jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
        m_s[:, 0:1] = m_new

    @pl.when(kb == nk - 1)
    def _():
        o_ref[0] = (acc_s[:] / l_s[:, 0:1]).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_s[:, 0:1] + jnp.log(l_s[:, 0:1]),
                                      lse_ref.shape[1:])


def _tile_terms(off, scale, causal, q_ref, k_ref, v_ref, mask_ref, do_ref,
                st_ref, qb, kb):
    """What both backward kernels recompute for one tile: the
    probabilities from the saved log-sum-exp (lane 0 of ``st``) and the
    scores' gradient, with delta = rowsum(dO * O) in lane 1."""
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    st = st_ref[0]
    s = _scores(off, scale, causal, q, k, mask_ref[0], qb, kb)
    p = jnp.exp(s - st[:, 0:1])
    dp = lax.dot_general(do, v, _TRANS_B,
                         preferred_element_type=jnp.float32)
    ds = p * (dp - st[:, 1:2]) * scale
    return q, k, do, p, ds


def _flash_dkv_kernel(off, scale, causal, q_ref, k_ref, v_ref, mask_ref,
                      do_ref, st_ref, dk_ref, dv_ref, dk_s, dv_s):
    kb, qb = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    Bq, Bk = q_ref.shape[1], k_ref.shape[1]

    @_when(_visible(off, causal, Bq, Bk, qb, kb))
    def _():
        q, _k, do, p, ds = _tile_terms(off, scale, causal, q_ref, k_ref,
                                       v_ref, mask_ref, do_ref, st_ref,
                                       qb, kb)
        dv_s[:] += jnp.dot(p.T.astype(do.dtype), do,
                           preferred_element_type=jnp.float32)
        dk_s[:] += jnp.dot(ds.T.astype(q.dtype), q,
                           preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _flash_dq_kernel(off, scale, causal, q_ref, k_ref, v_ref, mask_ref,
                     do_ref, st_ref, dq_ref, dq_s):
    qb, kb = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        dq_s[:] = jnp.zeros_like(dq_s)

    Bq, Bk = q_ref.shape[1], k_ref.shape[1]

    @_when(_visible(off, causal, Bq, Bk, qb, kb))
    def _():
        _q, k, _do, _p, ds = _tile_terms(off, scale, causal, q_ref, k_ref,
                                         v_ref, mask_ref, do_ref, st_ref,
                                         qb, kb)
        dq_s[:] += jnp.dot(ds.astype(k.dtype), k,
                           preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


class _Tiles:
    """The block specs the three kernels share, for q-major grids
    (bn, qb, kb) and the kv-major one (bn, kb, qb). Under ``causal`` a
    step that is skipped names the block of the last step that was not,
    so the pipeline fetches nothing for it."""

    def __init__(self, heads, off, causal, block_q, block_k, nq, nk):
        self.N, self.off, self.causal = heads, off, causal
        self.bq, self.bk, self.nq, self.nk = block_q, block_k, nq, nk

    def _kv(self, qb, kb):      # q-major: the last kv block qb sees
        if not self.causal:
            return kb
        last = (qb * self.bq + self.bq - 1 + self.off) // self.bk
        return jnp.minimum(kb, jnp.clip(last, 0, self.nk - 1))

    def _q(self, kb, qb):       # kv-major: the first q block that sees kb
        if not self.causal:
            return qb
        first = (kb * self.bk - self.off) // self.bq
        return jnp.maximum(qb, jnp.clip(first, 0, self.nq - 1))

    def specs(self, kv_major):
        """``(q-like(d), kv-like(d), mask)`` block-spec makers."""
        if kv_major:
            qi = lambda bn, kb, qb: (bn, self._q(kb, qb), 0)
            ki = lambda bn, kb, qb: (bn, kb, 0)
            mi = lambda bn, kb, qb: (bn // self.N, 0, kb)
        else:
            qi = lambda bn, qb, kb: (bn, qb, 0)
            ki = lambda bn, qb, kb: (bn, self._kv(qb, kb), 0)
            mi = lambda bn, qb, kb: (bn // self.N, 0, self._kv(qb, kb))
        vmem = pltpu.VMEM
        return (lambda d: pl.BlockSpec((1, self.bq, d), qi,
                                       memory_space=vmem),
                lambda d: pl.BlockSpec((1, self.bk, d), ki,
                                       memory_space=vmem),
                pl.BlockSpec((1, 1, self.bk), mi, memory_space=vmem))


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _flash_forward(cfg, qf, kf, vf, mask):
    """qf [BN,Tq,Dqk], kf [BN,Tk,Dqk], vf [BN,Tk,Dv] (lengths already
    multiples of the blocks), mask [B,1,Tk] -> (out [BN,Tq,Dv], the rows'
    log-sum-exp [BN,Tq])."""
    heads, off, scale, causal, block_q, block_k = cfg
    BN, Tq, Dqk = qf.shape
    Tk, Dv = vf.shape[1], vf.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    q_like, kv_like, mask_spec = _Tiles(
        heads, off, causal, block_q, block_k, nq, nk).specs(False)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, off, scale, causal),
        grid=(BN, nq, nk),
        in_specs=[q_like(Dqk), kv_like(Dqk), kv_like(Dv), mask_spec],
        out_specs=[q_like(Dv), q_like(_STAT_LANES)],
        out_shape=[jax.ShapeDtypeStruct((BN, Tq, Dv), qf.dtype),
                   jax.ShapeDtypeStruct((BN, Tq, _STAT_LANES),
                                        jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=common.interpret(),
    )(qf, kf, vf, mask)
    return out, lse[..., 0]


def _flash_backward(cfg, qf, kf, vf, mask, out, lse, do):
    heads, off, scale, causal, block_q, block_k = cfg
    BN, Tq, Dqk = qf.shape
    Tk, Dv = vf.shape[1], vf.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    # lane 0 the log-sum-exp, lane 1 delta: one operand, one fetch a tile
    stats = jnp.pad(jnp.stack([lse, delta], axis=-1),
                    ((0, 0), (0, 0), (0, _STAT_LANES - 2)))
    tiles = _Tiles(heads, off, causal, block_q, block_k, nq, nk)
    q_like, kv_like, mask_spec = tiles.specs(True)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, off, scale, causal),
        grid=(BN, nk, nq),
        in_specs=[q_like(Dqk), kv_like(Dqk), kv_like(Dv), mask_spec,
                  q_like(Dv), q_like(_STAT_LANES)],
        out_specs=[kv_like(Dqk), kv_like(Dv)],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, Dqk), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=common.interpret(),
    )(qf, kf, vf, mask, do, stats)
    q_like, kv_like, mask_spec = tiles.specs(False)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, off, scale, causal),
        grid=(BN, nq, nk),
        in_specs=[q_like(Dqk), kv_like(Dqk), kv_like(Dv), mask_spec,
                  q_like(Dv), q_like(_STAT_LANES)],
        out_specs=q_like(Dqk),
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, Dqk), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=common.interpret(),
    )(qf, kf, vf, mask, do, stats)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg, qf, kf, vf, mask):
    return _flash_forward(cfg, qf, kf, vf, mask)[0]


def _flash_fwd(cfg, qf, kf, vf, mask):
    # named as they leave the kernel, so that the layer's own use of `out`
    # (the output projection) reads the kept array too
    out, lse = checkpoint_name(_flash_forward(cfg, qf, kf, vf, mask),
                               common.KEPT_RESIDUAL)
    return out, (qf, kf, vf, mask, out, lse)


def _flash_bwd(cfg, res, g):
    dq, dk, dv = _flash_backward(cfg, *res, g)
    return dq, dk, dv, None


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _flash_padded(q, k, v, kv_mask, causal, scale, block_q, block_k):
    """Pad the lengths to whole blocks (padded keys are masked, padded
    query rows cut off again), fold batch and heads, call the kernels."""
    B, N, Tq, Dqk = q.shape
    Tk, Dv = k.shape[2], v.shape[-1]
    block_q, block_k = min(block_q, Tq), min(block_k, Tk)
    pad_q, pad_k = (-Tq) % block_q, (-Tk) % block_k
    if kv_mask is None:
        kv_mask = jnp.ones((B, Tk), jnp.float32)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad_k)))
    cfg = (N, Tk - Tq, float(scale), bool(causal), block_q, block_k)
    out = _flash_core(cfg, q.reshape(B * N, Tq + pad_q, Dqk),
                      k.reshape(B * N, Tk + pad_k, Dqk),
                      v.reshape(B * N, Tk + pad_k, Dv),
                      kv_mask.astype(jnp.float32)[:, None, :])
    return out.reshape(B, N, Tq + pad_q, Dv)[:, :, :Tq]


def flash_attention(q, k, v, kv_mask=None, causal=False, scale=None,
                    block_q=256, block_k=256):
    """Flash attention. Pallas on TPU, blockwise-scan elsewhere. Traced
    into a step partitioned over a mesh whose batch axes divide B, each
    device runs the kernels on its own rows (``common.batch_local``)."""
    Dqk, Dv = q.shape[-1], v.shape[-1]
    scale = scale if scale is not None else Dqk ** -0.5
    bq, bk = min(block_q, q.shape[2]), min(block_k, k.shape[2])
    # the backward's dK/dV kernel holds the most: every operand block
    # twice (the pipeline's two buffers), the f32 accumulators and the
    # score-sized temporaries once
    item = jnp.dtype(q.dtype).itemsize
    resident = (2 * item * (bq * (Dqk + Dv) + bk * (Dqk + Dv))
                + 2 * 4 * bq * _STAT_LANES + 4 * bk * (Dqk + Dv)
                + 4 * 4 * bq * bk)
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
            lambda q_, k_, v_, m_: _flash_padded(
                q_, k_, v_, m_, causal, scale, block_q, block_k),
            split, in_dims=(0, 0, 0, 0), out_dims=0)
        return core(q, k, v, kv_mask)
    return _flash_padded(q, k, v, kv_mask, causal, scale, block_q, block_k)
