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
  steps), a step one (q block, kv block) tile and a q block's kv tiles
  consecutive, so the accumulator lives in VMEM scratch across that
  sweep; it also hands back each row's log-sum-exp. The backward
  recomputes the scores tile by tile from that log-sum-exp: memory
  linear in T, nothing of size T_q·T_k is ever held. It is ONE kernel
  over dK/dV's walk (a kv block's q tiles) that computes a tile's
  scores, probabilities and score gradient once and feeds dK, dV and dQ
  from them, where dQ's float32 sums for the q blocks that are open at
  once fit VMEM beside the rest (``_flash_backward`` reckons the bytes
  against ``common.VMEM_BUDGET_BYTES``; ``flash_backward`` in a tally
  says ``fused`` or ``split``), and two kernels that each recompute the
  tile (dK/dV; dQ walking a q block's kv tiles) where they do not: many
  query heads a key-value head over a long row with no window. The grid
  holds the tiles in which a query sees a key and no others (``_Tiles``): under
  ``causal`` the pairs wholly above the diagonal are not skipped steps
  but no steps, named by a table of steps in SMEM; a grid step costs
  about 0.4 us on a v5e even when it computes nothing, and a sweep
  that starts right after skipped steps waits for its first blocks. A
  tile's own vector work (scale, both masks, the exponential) is done
  on every tile alike: on the chip it hides under the matrix products
  (``tools/flash_tile_times.py``; PERF.md §5). The forward rule names
  its output and the log-sum-exp ``common.KEPT_RESIDUAL``: a layer
  under the executor's checkpoint keeps those two (T·Dv and T a head)
  and recomputes what led to q, k and v, so the forward kernel runs
  once a step, not again in the backward pass.

All take q of [B, N, T, Dqk], k of [B, N_kv, T_k, Dqk] and v of [B, N_kv,
T_k, Dv] (the two head sizes may differ: latent attention has 192 and
128), an optional kv validity mask [B, T_k], a ``causal`` flag and a
``window``.

- **Grouped queries.** ``N_kv`` divides ``N``: query head ``n`` attends
  key-value head ``n // (N / N_kv)``. The kernels never see K and V
  repeated to the query heads: the forward and dQ kernels walk a query
  head's tiles and their index maps name its group's K and V blocks;
  the dK/dV kernel and the fused backward walk a KEY-VALUE head's tiles
  and, inside one kv block's sweep, the group's query heads in turn, so
  the sums over the group stay in the VMEM accumulators and dK, dV
  leave at ``N_kv`` heads
  (per-query-head results summed outside cost a write and a read of
  ``N / N_kv`` times the bytes: PERF.md §5 has both timings).
- **A window.** With ``causal``, ``window=W`` lets query ``i`` see key
  ``j`` iff ``j <= i + off`` and ``i + off - j < W`` (``off = T_k -
  T_q``): a band. ``_Tiles`` leaves out the pairs wholly below the band
  as it leaves out those above the diagonal, and a tile on either edge
  is masked inside; ``walked_pairs`` says how many pairs the forward
  grid's tiles hold against how many the mask lets see.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import common

_NEG = -1e9


def _group(q, k):
    """Query heads a key-value head: ``k`` has a divisor of ``q``'s."""
    N, Nkv = q.shape[1], k.shape[1]
    if N % Nkv:
        raise ValueError(f"{Nkv} key-value heads do not divide {N} "
                         "query heads")
    return N // Nkv


def _check_window(causal, window):
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is a causal band of at least one key")


def mha_reference(q, k, v, kv_mask=None, causal=False, scale=None,
                  window=None):
    """Plain attention. q [B,N,Tq,Dqk], k [B,Nkv,Tk,Dqk], v
    [B,Nkv,Tk,Dv], kv_mask [B,Tk]."""
    _check_window(causal, window)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    group = _group(q, k)
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, _NEG)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        qi = jnp.arange(Tq)[:, None] + (Tk - Tq)
        kj = jnp.arange(Tk)[None, :]
        s = jnp.where(kj <= qi, s, _NEG)
        if window is not None:
            s = jnp.where(qi - kj < window, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnqk,bnkd->bnqd", p, v)


def blockwise_attention(q, k, v, kv_mask=None, causal=False, scale=None,
                        block_k=512, window=None):
    """Memory-efficient attention: lax.scan over KV blocks with online
    softmax. Differentiable; the ground-truth backward for flash. A
    group's query heads ride one more axis of q (``g``), so K and V
    keep their own heads; every kv block is visited, whatever the
    window."""
    _check_window(causal, window)
    group = _group(q, k)
    B, N, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    q = q.reshape(B, N // group, group, Tq, D)
    block_k = min(block_k, Tk)
    pad = (-Tk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        base = (kv_mask if kv_mask is not None
                else jnp.ones((B, Tk), q.dtype))
        kv_mask = jnp.pad(base, ((0, 0), (0, pad)))
    nk = k.shape[2] // block_k
    kb = k.reshape(B, N // group, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, N // group, nk, block_k, Dv).transpose(2, 0, 1, 3, 4)
    mb = (kv_mask.reshape(B, nk, block_k).transpose(1, 0, 2)
          if kv_mask is not None else None)
    qi = jnp.arange(Tq)[:, None] + (Tk - Tq)

    def body(carry, inp):
        acc, m_run, l_run = carry
        idx, k_t, v_t, msk = inp
        s = jnp.einsum("bngqd,bnkd->bngqk", q, k_t) * scale
        if msk is not None:
            s = jnp.where(msk[:, None, None, None, :] > 0, s, _NEG)
        if causal:
            kj = idx * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(kj <= qi, s, _NEG)
            if window is not None:
                s = jnp.where(qi - kj < window, s, _NEG)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bngqk,bnkd->bngqd", p,
                                                  v_t)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros(q.shape[:-1] + (Dv,), jnp.float32)
    m0 = jnp.full(q.shape[:-1], _NEG, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    if mb is None:
        (acc, m_run, l_run), _ = lax.scan(
            lambda c, i: body(c, (i[0], i[1], i[2], None)), (acc0, m0, l0),
            (jnp.arange(nk), kb, vb))
    else:
        (acc, m_run, l_run), _ = lax.scan(body, (acc0, m0, l0),
                                          (jnp.arange(nk), kb, vb, mb))
    return (acc / l_run[..., None]).astype(q.dtype).reshape(B, N, Tq, Dv)


# ---------------------------------------------------------------- pallas

_TRANS_B = (((1,), (1,)), ((), ()))     # a [M,K] x b [N,K] -> [M,N]
_STAT_LANES = 128     # a row statistic is kept broadcast over one lane tile
_WALK_TABLE_BYTES = 512 * 1024      # of SMEM, for a walk's table of steps
_FIRST, _LAST = 1, 2      # a step's place in its sweep, in the walk's table
_OPENS, _CLOSES = 4, 8    # and in its q block's steps (the fused backward)


def _scores(tiles, scale, q, k, msk, qb, kb):
    """The masked, scaled score tile [Bq, Bk] in float32. ``tiles.off``
    is T_k - T_q: query row i sees keys up to i + off, and under a
    window no key more than ``window - 1`` before that."""
    Bq, Bk = q.shape[0], k.shape[0]
    s = lax.dot_general(q, k, _TRANS_B,
                        preferred_element_type=jnp.float32) * scale
    s = jnp.where(msk > 0, s, _NEG)
    if tiles.causal:
        qi = (qb * Bq + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 0)
              + tiles.off)
        kj = kb * Bk + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 1)
        s = jnp.where(kj <= qi, s, _NEG)
        if tiles.window is not None:
            s = jnp.where(qi - kj < tiles.window, s, _NEG)
    return s


def _flash_kernel(tiles, scale, walk_ref, q_ref, k_ref, v_ref, mask_ref,
                  o_ref, lse_ref, acc_s, m_s, l_s):
    qb, kb, first, last = tiles.step(walk_ref)

    @pl.when(first)
    def _():
        acc_s[:] = jnp.zeros_like(acc_s)
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)

    v = v_ref[0]
    s = _scores(tiles, scale, q_ref[0], k_ref[0], mask_ref[0], qb, kb)
    m_prev = m_s[:, 0:1]                                         # [Bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)                              # [Bq, 1]
    l_s[:, 0:1] = l_s[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[:] = (acc_s[:] * alpha
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32))
    m_s[:, 0:1] = m_new

    @pl.when(last)
    def _():
        o_ref[0] = (acc_s[:] / l_s[:, 0:1]).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_s[:, 0:1] + jnp.log(l_s[:, 0:1]),
                                      lse_ref.shape[1:])


def _tile_terms(tiles, scale, q_ref, k_ref, v_ref, mask_ref, do_ref,
                st_ref, qb, kb):
    """What both backward kernels recompute for one tile: the
    probabilities from the saved log-sum-exp (lane 0 of ``st``) and the
    scores' gradient, with delta = rowsum(dO * O) in lane 1."""
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    st = st_ref[0]
    s = _scores(tiles, scale, q, k, mask_ref[0], qb, kb)
    p = jnp.exp(s - st[:, 0:1])
    dp = lax.dot_general(do, v, _TRANS_B,
                         preferred_element_type=jnp.float32)
    ds = p * (dp - st[:, 1:2]) * scale
    return q, k, do, p, ds


def _flash_dkv_kernel(tiles, scale, walk_ref, q_ref, k_ref, v_ref, mask_ref,
                      do_ref, st_ref, dk_ref, dv_ref, dk_s, dv_s):
    qb, kb, first, last = tiles.step(walk_ref)

    @pl.when(first)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    q, _k, do, p, ds = _tile_terms(tiles, scale, q_ref, k_ref, v_ref,
                                   mask_ref, do_ref, st_ref, qb, kb)
    dv_s[:] += jnp.dot(p.T.astype(do.dtype), do,
                       preferred_element_type=jnp.float32)
    dk_s[:] += jnp.dot(ds.T.astype(q.dtype), q,
                       preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _flash_dq_kernel(tiles, scale, walk_ref, q_ref, k_ref, v_ref, mask_ref,
                     do_ref, st_ref, dq_ref, dq_s):
    qb, kb, first, last = tiles.step(walk_ref)

    @pl.when(first)
    def _():
        dq_s[:] = jnp.zeros_like(dq_s)

    _q, k, _do, _p, ds = _tile_terms(tiles, scale, q_ref, k_ref, v_ref,
                                     mask_ref, do_ref, st_ref, qb, kb)
    dq_s[:] += jnp.dot(ds.astype(k.dtype), k,
                       preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _flash_bwd_kernel(tiles, scale, walk_ref, q_ref, k_ref, v_ref, mask_ref,
                      do_ref, st_ref, dk_ref, dv_ref, dq_ref, dk_s, dv_s,
                      dq_s):
    """dK/dV's walk with dQ beside it: a tile's terms once, three sums.
    ``dq_s`` holds a slot for every q block that is open (some of its
    kv blocks walked, not all); a q block's kv blocks come in ascending
    order here as in dQ's own walk, so each sum adds what the two
    kernels add, in their order."""
    qb, kb, first, last = tiles.step(walk_ref)
    row, opens, closes = tiles.dq_step(walk_ref)
    rows = pl.ds(pl.multiple_of(row, tiles.bq), tiles.bq)

    @pl.when(first)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(opens)
    def _():
        dq_s[rows, :] = jnp.zeros((tiles.bq, dq_s.shape[1]), dq_s.dtype)

    q, k, do, p, ds = _tile_terms(tiles, scale, q_ref, k_ref, v_ref,
                                  mask_ref, do_ref, st_ref, qb, kb)
    dv_s[:] += jnp.dot(p.T.astype(do.dtype), do,
                       preferred_element_type=jnp.float32)
    dk_s[:] += jnp.dot(ds.T.astype(q.dtype), q,
                       preferred_element_type=jnp.float32)
    dq_s[rows, :] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)

    @pl.when(closes)
    def _():
        dq_ref[0] = dq_s[rows, :].astype(dq_ref.dtype)


def _seen(off, causal, window, block_q, block_k, nq, nk):
    """``[nq, nk]``: does some query of q block ``qb`` see some key of kv
    block ``kb``? A block is a rectangle and what the mask lets see a
    band between two diagonals, so it is enough that the rectangle lies
    neither wholly above the upper one nor wholly below the lower."""
    qb, kb = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    seen = np.ones((nq, nk), bool)
    if causal:
        seen = kb * block_k <= qb * block_q + block_q - 1 + off
        if window is not None:
            seen &= kb * block_k + block_k - 1 > qb * block_q + off - window
    return qb, kb, seen


class _Tiles:
    """The tiles of one head that a kernel's grid walks, and the block
    specs the three kernels share over that walk. The grid is (rows,
    steps): one step for every (q block, kv block) pair in which a query
    sees a key, q-major (a row is a query head, a sweep one q block's kv
    blocks: the forward and dQ kernels) or kv-major (a row is a
    KEY-VALUE head, a sweep one kv block's q blocks over each of the
    ``group`` query heads that share it in turn: dK/dV). Under
    ``causal`` the pairs wholly above the diagonal are no steps at all,
    and under a ``window`` neither are those wholly below the band:
    nothing is fetched, tested or waited for on their behalf, and a
    sweep's first blocks arrive while the sweep before it still
    computes. A sweep that sees nothing keeps one tile, all of it
    masked, so that its output block is still written. The steps' tiles
    are a table in SMEM (the calls' scalar prefetch, read by the index
    maps and the kernels); where every pair is a step there is no
    table: the grid is (rows, sweeps, steps of a sweep) and a step's
    tile is its two indices."""

    def __init__(self, heads, off, causal, block_q, block_k, nq, nk,
                 kv_major, window=None, group=1, dq_too=False):
        self.N, self.off, self.causal = heads, off, causal
        self.window, self.group, self.nq = window, group, nq
        self.bq, self.bk, self.kv_major = block_q, block_k, kv_major
        qb, kb, seen = _seen(off, causal, window, block_q, block_k, nq, nk)
        band = seen.copy()
        if kv_major:
            seen[nq - 1, ~seen.any(axis=0)] = True
        if dq_too or not kv_major:
            seen[~seen.any(axis=1), 0] = True
        if dq_too:
            # dQ's slots: a q block is open from its first kv block's
            # sweep to its last one's, so the q blocks open at once are
            # those that see one kv block, consecutive ones, and ``ring``
            # slots taken in turn hold them (all of them where a tile
            # outside the band was added: its q block is open out of turn)
            self.ring = int(seen.sum(axis=0).max()) \
                if (seen == band).all() else nq
        # the group's query heads share a kv block's sweep (dK/dV alone)
        share = group if kv_major else 1
        # every pair a step: the grid is (rows, sweeps, steps of a sweep)
        # and needs no table
        self.whole = ((nk, share * nq) if kv_major else (nq, nk)) \
            if seen.all() else None
        if self.whole:
            return
        qs, ks = np.tile(qb[seen], share), np.tile(kb[seen], share)
        head = np.repeat(np.arange(share), seen.sum())
        order = np.lexsort((qs, head, ks) if kv_major else (ks, qs))
        sweep = (ks if kv_major else qs)[order]
        edge = np.flatnonzero(np.diff(sweep)) + 1     # where a sweep starts
        ends = np.zeros(len(sweep), np.int32)
        ends[np.r_[0, edge]] |= _FIRST
        ends[np.r_[edge - 1, len(sweep) - 1]] |= _LAST
        self.steps = len(sweep)
        cols = [qs[order], ks[order], ends] \
            + ([head[order]] if share > 1 else [])
        if dq_too:
            # a (query head, q block)'s first and last step, and for every
            # step the one that closes next: dQ's output block stays that
            # one's until it is written
            ident = (head * nq + qs)[order]
            opens = np.unique(ident, return_index=True)[1]
            closes = np.sort(len(ident) - 1
                             - np.unique(ident[::-1], return_index=True)[1])
            ends[opens] |= _OPENS
            ends[closes] |= _CLOSES
            self.out_at = len(cols) * self.steps
            cols.append(ident[closes[np.searchsorted(
                closes, np.arange(len(ident)))]])
        # [qb of every step | kb of every step | its place in its sweep
        #  | under a group, the query head of it the step is for
        #  | with dQ, the query head and q block its output block is]
        self.walk = jnp.asarray(np.concatenate(cols), jnp.int32)

    def _qb(self, *at):
        """The q block of the grid step ``at``: (sweep, step of it) where
        every pair is a step, else (step, the table)."""
        if self.whole:
            if not self.kv_major:
                return at[0]
            return at[1] % self.nq if self.group > 1 else at[1]
        t, walk = at
        return walk[t]

    def _kb(self, *at):
        if self.whole:
            return at[0] if self.kv_major else at[1]
        t, walk = at
        return walk[self.steps + t]

    def _head(self, *at):
        """Which of its group's query heads a kv-major step is for."""
        if self.whole:
            return at[1] // self.nq
        t, walk = at
        return walk[3 * self.steps + t]

    def step(self, walk):
        """``(qb, kb, first of its sweep?, last of it?)`` of the grid
        step a kernel is in."""
        if self.whole:
            at = pl.program_id(1), pl.program_id(2)
            first, last = at[1] == 0, at[1] == self.whole[1] - 1
        else:
            at = pl.program_id(1), walk
            ends = walk[2 * self.steps + at[0]]
            first, last = (ends & _FIRST) != 0, (ends & _LAST) != 0
        return self._qb(*at), self._kb(*at), first, last

    def dq_step(self, walk):
        """``(its q block's first row in the dQ slots, is this the q
        block's first step?, its last?)`` of the grid step the fused
        backward kernel is in."""
        if self.whole:  # a slot a (query head, q block), in a sweep's order
            sweep, t = pl.program_id(1), pl.program_id(2)
            return t * self.bq, sweep == 0, sweep == self.whole[0] - 1
        t = pl.program_id(1)
        ends = walk[2 * self.steps + t]
        slot = walk[t] % self.ring
        if self.group > 1:
            slot += self._head(t, walk) * self.ring
        return slot * self.bq, (ends & _OPENS) != 0, (ends & _CLOSES) != 0

    def dq_spec(self, d):
        """dQ's block in the fused backward: the (query head, q block)
        that closes next, so that a block is held from the step after
        the one before it closed to its own last step, and written
        there."""
        def index(r, *at):
            if self.whole:
                nxt = jnp.where(at[0] == self.whole[0] - 1, at[1], 0)
            else:
                t, walk = at
                nxt = walk[self.out_at + t]
            return r * self.group + nxt // self.nq, nxt % self.nq, 0
        return pl.BlockSpec((1, self.bq, d), index, memory_space=pltpu.VMEM)

    def specs(self):
        """``(q-like(d), kv-like(d), mask)`` block-spec makers. A grid
        row is a query head (q-major) or a key-value head (kv-major);
        the other operand's row follows from the group."""
        vmem = pltpu.VMEM
        G = self.group
        if G == 1:
            q_row = kv_row = lambda r, *at: r
        elif self.kv_major:
            q_row = lambda r, *at: r * G + self._head(*at)
            kv_row = lambda r, *at: r
        else:
            q_row = lambda r, *at: r
            kv_row = lambda r, *at: r // G
        rows = self.N // G if self.kv_major else self.N     # a batch row's
        qi = lambda r, *at: (q_row(r, *at), self._qb(*at), 0)
        ki = lambda r, *at: (kv_row(r, *at), self._kb(*at), 0)
        mi = lambda r, *at: (r // rows, 0, self._kb(*at))
        return (lambda d: pl.BlockSpec((1, self.bq, d), qi,
                                       memory_space=vmem),
                lambda d: pl.BlockSpec((1, self.bk, d), ki,
                                       memory_space=vmem),
                pl.BlockSpec((1, 1, self.bk), mi, memory_space=vmem))

    def call(self, kernel, scale, rows, in_specs, out_specs, out_shape,
             scratch, *operands):
        """``kernel(self, scale, the table or None, *references)`` over
        the walk, ``rows`` (batch x the heads a row stands for) times."""
        kernel = functools.partial(kernel, self, scale)
        if self.whole:              # no table among its references
            table, grid = (), (rows,) + self.whole
            kernel = functools.partial(kernel, None)
        else:
            table, grid = (self.walk,), (rows, self.steps)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(table), grid=grid,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * (len(grid) - 1)
                + ("arbitrary",)),
            interpret=common.interpret(),
        )(*table, *operands)


def walked_pairs(Tq, Tk, causal=False, window=None, block_q=256,
                 block_k=256):
    """``(visited, visible)`` of one head: the query-key pairs inside
    the tiles the forward kernel's grid walks at these blocks, and the
    pairs the mask lets see. Their ratio is what the tiling wastes on
    the band's two edges (2.0 for a window of 512 in 512 x 512 tiles:
    every q block walks the diagonal tile and the one before it, each
    half visible)."""
    _check_window(causal, window)
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    _, _, seen = _seen(Tk - Tq, causal, window, bq, bk, -(-Tq // bq),
                       -(-Tk // bk))
    seen[~seen.any(axis=1), 0] = True       # the tile a blind sweep keeps
    # query i sees the keys in (i + off - window, i + off], cut to [0, Tk)
    qi = np.arange(Tq) + (Tk - Tq)
    upper = np.minimum(qi, Tk - 1) if causal else np.full(Tq, Tk - 1)
    lower = np.maximum(qi - window + 1, 0) if window is not None \
        else np.zeros(Tq, np.int64)
    visible = int(np.maximum(upper - lower + 1, 0).sum())
    return int(seen.sum()) * bq * bk, visible


def _flash_forward(cfg, qf, kf, vf, mask):
    """qf [B*N,Tq,Dqk], kf [B*Nkv,Tk,Dqk], vf [B*Nkv,Tk,Dv] (lengths
    already multiples of the blocks), mask [B,1,Tk] -> (out [B*N,Tq,Dv],
    the rows' log-sum-exp [B*N,Tq])."""
    heads, off, scale, causal, block_q, block_k, window, group = cfg
    BN, Tq, Dqk = qf.shape
    Tk, Dv = vf.shape[1], vf.shape[2]
    tiles = _Tiles(heads, off, causal, block_q, block_k, Tq // block_q,
                   Tk // block_k, kv_major=False, window=window,
                   group=group)
    q_like, kv_like, mask_spec = tiles.specs()
    out, lse = tiles.call(
        _flash_kernel, scale, BN,
        [q_like(Dqk), kv_like(Dqk), kv_like(Dv), mask_spec],
        [q_like(Dv), q_like(_STAT_LANES)],
        [jax.ShapeDtypeStruct((BN, Tq, Dv), qf.dtype),
         jax.ShapeDtypeStruct((BN, Tq, _STAT_LANES), jnp.float32)],
        [pltpu.VMEM((block_q, Dv), jnp.float32),
         pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
         pltpu.VMEM((block_q, _STAT_LANES), jnp.float32)],
        qf, kf, vf, mask)
    return out, lse[..., 0]


def _vmem_bytes(item, bq, bk, Dqk, Dv, dq_rows=0):
    """What the backward holds in VMEM, as ``common.VMEM_BUDGET_BYTES``
    counts: every operand block twice (the pipeline's two buffers), the
    float32 accumulators and the score-sized temporaries once. dK/dV's
    kernel holds the most of the three; the fused backward adds dQ's
    block and ``dq_rows`` rows of dQ's float32 slots, whole lane tiles
    wide."""
    held = (2 * item * (bq * (Dqk + Dv) + bk * (Dqk + Dv))
            + 2 * 4 * bq * _STAT_LANES + 4 * bk * (Dqk + Dv)
            + 4 * 4 * bq * bk)
    if dq_rows:
        held += 2 * item * bq * Dqk \
            + 4 * dq_rows * -(-Dqk // common.LANE) * common.LANE
    return held


def _backward_stats(out, lse, do):
    """The backward kernels' row statistics, one operand and one fetch a
    tile: lane 0 the log-sum-exp, lane 1 delta = rowsum(dO * O)."""
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    return jnp.pad(jnp.stack([lse, delta], axis=-1),
                   ((0, 0), (0, 0), (0, _STAT_LANES - 2)))


def _fused_walk(cfg, qf, kf, vf):
    """``(the fused backward's walk, does it fit?)``: dQ's slots and
    block beside what the two kernels hold within the VMEM budget, the
    longer table within SMEM's."""
    heads, off, _, causal, block_q, block_k, window, group = cfg
    tiles = _Tiles(heads, off, causal, block_q, block_k,
                   qf.shape[1] // block_q, kf.shape[1] // block_k,
                   kv_major=True, window=window, group=group, dq_too=True)
    held = _vmem_bytes(qf.dtype.itemsize, block_q, block_k, qf.shape[2],
                       vf.shape[2], dq_rows=group * tiles.ring * block_q)
    return tiles, held <= common.VMEM_BUDGET_BYTES and bool(
        tiles.whole or 4 * tiles.walk.size <= _WALK_TABLE_BYTES)


def _flash_backward(cfg, qf, kf, vf, mask, out, lse, do):
    """dq, dk, dv. One kernel over dK/dV's walk where dQ's open q blocks
    fit VMEM beside it (a group of 1 at the cells' 4,096 positions; any
    group under a window, whose band keeps few q blocks open), else the
    two kernels: the reckoned bytes decide, and ``flash_backward`` in a
    tally says which."""
    operands = (qf, kf, vf, mask, do, _backward_stats(out, lse, do))
    tiles, fits = _fused_walk(cfg, qf, kf, vf)
    if common.note("flash_backward", "fused" if fits else "split") == "fused":
        return _backward_fused(tiles, cfg[2], *operands)
    return _backward_split(cfg, *operands)


def _backward_fused(tiles, scale, qf, kf, vf, mask, do, stats):
    """A row of the grid is a key-value head, as in dK/dV's kernel; dq
    leaves a (query head, q block) at a time, when its last tile is
    done."""
    Dqk, Dv = qf.shape[2], vf.shape[2]
    q_like, kv_like, mask_spec = tiles.specs()
    dk, dv, dq = tiles.call(
        _flash_bwd_kernel, scale, kf.shape[0],
        [q_like(Dqk), kv_like(Dqk), kv_like(Dv), mask_spec,
         q_like(Dv), q_like(_STAT_LANES)],
        [kv_like(Dqk), kv_like(Dv), tiles.dq_spec(Dqk)],
        [jax.ShapeDtypeStruct(kf.shape, kf.dtype),
         jax.ShapeDtypeStruct(vf.shape, vf.dtype),
         jax.ShapeDtypeStruct(qf.shape, qf.dtype)],
        [pltpu.VMEM((tiles.bk, Dqk), jnp.float32),
         pltpu.VMEM((tiles.bk, Dv), jnp.float32),
         pltpu.VMEM((tiles.group * tiles.ring * tiles.bq, Dqk),
                    jnp.float32)],
        qf, kf, vf, mask, do, stats)
    return dq, dk, dv


def _backward_split(cfg, *operands):
    heads, off, scale, causal, block_q, block_k, window, group = cfg
    qf, kf, vf = operands[:3]
    BN, Tq, Dqk = qf.shape
    Tk, Dv = vf.shape[1], vf.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    # a row of dK/dV's grid is a key-value head: its group's query heads
    # are swept inside, so dk and dv leave at kf's and vf's own heads
    tiles = _Tiles(heads, off, causal, block_q, block_k, nq, nk,
                   kv_major=True, window=window, group=group)
    q_like, kv_like, mask_spec = tiles.specs()
    dk, dv = tiles.call(
        _flash_dkv_kernel, scale, BN // group,
        [q_like(Dqk), kv_like(Dqk), kv_like(Dv), mask_spec,
         q_like(Dv), q_like(_STAT_LANES)],
        [kv_like(Dqk), kv_like(Dv)],
        [jax.ShapeDtypeStruct(kf.shape, kf.dtype),
         jax.ShapeDtypeStruct(vf.shape, vf.dtype)],
        [pltpu.VMEM((block_k, Dqk), jnp.float32),
         pltpu.VMEM((block_k, Dv), jnp.float32)],
        *operands)
    tiles = _Tiles(heads, off, causal, block_q, block_k, nq, nk,
                   kv_major=False, window=window, group=group)
    q_like, kv_like, mask_spec = tiles.specs()
    dq = tiles.call(
        _flash_dq_kernel, scale, BN,
        [q_like(Dqk), kv_like(Dqk), kv_like(Dv), mask_spec,
         q_like(Dv), q_like(_STAT_LANES)],
        q_like(Dqk),
        jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        [pltpu.VMEM((block_q, Dqk), jnp.float32)],
        *operands)
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


def _flash_padded(q, k, v, kv_mask, causal, scale, block_q, block_k,
                  window):
    """Pad the lengths to whole blocks (padded keys are masked, padded
    query rows cut off again), fold batch and heads, call the kernels."""
    B, N, Tq, Dqk = q.shape
    Nkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[-1]
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
    cfg = (N, Tk - Tq, float(scale), bool(causal), block_q, block_k,
           window, N // Nkv)
    out = _flash_core(cfg, q.reshape(B * N, Tq + pad_q, Dqk),
                      k.reshape(B * Nkv, Tk + pad_k, Dqk),
                      v.reshape(B * Nkv, Tk + pad_k, Dv),
                      kv_mask.astype(jnp.float32)[:, None, :])
    return out.reshape(B, N, Tq + pad_q, Dv)[:, :, :Tq]


def flash_attention(q, k, v, kv_mask=None, causal=False, scale=None,
                    block_q=256, block_k=256, window=None):
    """Flash attention. Pallas on TPU, blockwise-scan elsewhere. ``k``
    and ``v`` may have fewer heads than ``q`` (a divisor: grouped
    queries); ``window`` makes ``causal`` a band. Traced into a step
    partitioned over a mesh whose batch axes divide B, each device runs
    the kernels on its own rows (``common.batch_local``)."""
    _check_window(causal, window)
    group = _group(q, k)
    Dqk, Dv = q.shape[-1], v.shape[-1]
    scale = scale if scale is not None else Dqk ** -0.5
    bq, bk = min(block_q, q.shape[2]), min(block_k, k.shape[2])
    # what the two-kernel backward holds; whether the one-kernel backward
    # fits beside dQ's slots is `_flash_backward`'s to reckon
    resident = _vmem_bytes(jnp.dtype(q.dtype).itemsize, bq, bk, Dqk, Dv)
    # the causal walk's table of steps lives in SMEM (1 MiB on a v5e),
    # 12 bytes a (q block, kv block) pair at most, and 16 for each of a
    # group's query heads in dK/dV's
    pairs = -(-q.shape[2] // bq) * -(-k.shape[2] // bk)
    table = (12 if group == 1 else 16 * group) * pairs
    split = common.batch_split(q.shape[0])
    if split == 0 or not common.use_pallas(resident) \
            or (causal and table > _WALK_TABLE_BYTES):
        common.note("flash_attention", "ref")
        return blockwise_attention(q, k, v, kv_mask, causal=causal,
                                   scale=scale, block_k=block_k,
                                   window=window)
    common.note("flash_attention", common.pallas_path())
    if split > 1:
        if kv_mask is None:
            kv_mask = jnp.ones((k.shape[0], k.shape[2]), jnp.float32)
        core = common.batch_local(
            lambda q_, k_, v_, m_: _flash_padded(
                q_, k_, v_, m_, causal, scale, block_q, block_k, window),
            split, in_dims=(0, 0, 0, 0), out_dims=0)
        return core(q, k, v, kv_mask)
    return _flash_padded(q, k, v, kv_mask, causal, scale, block_q, block_k,
                         window)
