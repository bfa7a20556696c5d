"""The row kernel of the expert path: ``sum_rows`` adds each token's
rows of the dispatch buffer back, and is the transpose of the buffer's
gather (``gather_rows``). It moves only the rows a chunk holds: the
buffer is sized for twice what a uniform router sends
(``parallel/moe.py:_chunk_rows``), and XLA's scatter-add pays for every
row of it. XLA's gather is cheap on the TPU, and stays.

A chunk's plan is ``(pairs, runs, slot, first)``: ``pairs [R]``, the
sorted (token, choice) pairs the chunk's rows stand for (indices into
the flattened ``[T * K]`` choices); ``runs [H]``, the rows each held
expert has in the chunk, one run after another, each in token order;
``slot [T * K]``, where in the sorted order each pair lies (``slots``,
the inverse of the sort); and the chunk's first sorted position. The
chunk holds ``n``, the sum of ``runs``: the rows ``0 .. n`` of the
buffer; the rest hold no expert's row.

- ``gather_rows(src [T, d], plan) -> [R, d]``: row ``r < n`` is
  ``src[pairs[r] // K]``, the rest zero: XLA's gather and a select.
  Under the kernel its transpose is ``sum_rows`` with weights 1, summed
  in float32 and rounded once, in place of XLA's scatter-add at the
  source's type.
- ``sum_rows(rows [R, d], gates [T, K], plan) -> [T, d]`` float32: for
  each token the sum of its pairs' rows that the chunk holds (slot in
  ``first .. first + n``), weighted by their gates, in float32. Each
  held row is read once and each token's row written once; a slot
  outside the chunk is skipped. Its transpose is XLA's gather of the
  cotangent.

On the TPU a row is moved by one DMA; a block of tokens finds its rows
in each run by a binary search over the chunk's rows. Mosaic copies
whole tiles, and a bfloat16 matrix packs two rows into every 32-bit
word of its (8, 128) tiles, so the rows are first written one to a
tile of 32-bit words (``_words``: ``[N, 1, W]``, a bfloat16 row's two
halves in the low and high halves of each word, a float32 row bit for
bit), a Pallas pass over the held rows; the kernel unpacks them in
VMEM. The entry
functions are module-level ``jax.jit`` functions with their sizes
static, so that a step's lowering emits each kernel once for each shape
and type however many layers, chunks, recomputations and transposes
call it (a ``pallas_call`` made at a call site is lowered again at
every site).

``kernels(slots, rows)`` is the one policy's answer for both (the
reference is XLA's gather and scatter-add, which JAX differentiates):
the kernel where ``common.use_pallas()``, the step is not partitioned
and the layer's first buffer is large enough against its slots
(``_SLOTS_A_ROW``); the expert path then builds ``slot`` and the
entries take it. With ``slot`` None they take the reference. Each
entry notes its path (``moe_gather_rows``: what its transpose takes;
``moe_sum_rows``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import common

_WORD_ROWS = 256        # rows a grid step of the packing pass writes
_SUM_SLOTS = 256        # (token, choice) slots a grid step of sum_rows reads
# the index tables a kernel holds in SMEM (1 MiB on a v5e): an int32 a
# buffer row, 128 KiB at the Mellum2 cell's 32,768
_TABLE_BYTES = 512 * 1024
# The kernel reads every (token, choice) slot of the batch, where XLA's
# scatter-add pays for every row of the buffer: it takes the buffer only
# where the first buffer has a row for at most this many slots. Measured
# on a v5e: faster at 4 slots a row (the Mellum2 and LFM2 cells' expert
# layers), slower at 16 (JoyAI's and Laguna's).
_SLOTS_A_ROW = 8


def kernels(slots: int, rows: int) -> bool:
    """Does the row kernel move the buffers of an expert layer of
    ``slots`` (token, choice) pairs whose first buffer holds ``rows``,
    in the step being traced? Where it does, the expert path hands the
    entries a ``slot`` table."""
    return (common.use_pallas() and not common.partitioned()
            and slots <= _SLOTS_A_ROW * rows)


def slots(order, pairs: int):
    """``slot [pairs]``: where each (token, choice) pair lies among the
    sorted ``order`` (its first ``pairs`` entries; padding follows)."""
    return jnp.zeros((pairs,), jnp.int32).at[order[:pairs]].set(
        jnp.arange(pairs, dtype=jnp.int32))


def _fits(rows: int, a) -> bool:
    """The kernel's shape gate: a bfloat16 or float32 row of even width,
    and an index table a buffer row that SMEM holds."""
    return (a.dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and a.shape[1] % 2 == 0 and 4 * rows <= _TABLE_BYTES)


def gather_rows(src, pairs, choices: int, runs, slot=None, first=0):
    """``[R, d]``: row ``r < n`` is ``src[pairs[r] // choices]``, the
    rest zero, where ``n`` is the sum of ``runs`` (XLA's gather and a
    select; under the kernel, its transpose is ``sum_rows``' kernel)."""
    R = pairs.shape[0]
    token = pairs // choices
    valid = jnp.arange(R) < jnp.sum(runs)
    if slot is None or not _fits(R, src):
        common.note("moe_gather_rows", "ref")
        return jnp.where(valid[:, None], src[token], 0)
    common.note("moe_gather_rows", common.pallas_path())
    return _gather(src, valid, pairs, runs, slot.reshape(-1, choices),
                   _bounds(first, runs), common.interpret())


def sum_rows(rows, gates, pairs, runs, slot=None, first=0, into=None):
    """``into`` ([T, d] float32) plus, for each token, its held rows
    weighted by ``gates [T, K]`` (the reference: XLA's float32
    scatter-add of every row of the buffer, rows past ``n`` zero)."""
    T, K = gates.shape
    if slot is None or not _fits(rows.shape[0], rows):
        common.note("moe_sum_rows", "ref")
        g = gates.reshape(-1)[pairs]
        return into.at[pairs // K].add(rows.astype(jnp.float32)
                                       * g[:, None])
    common.note("moe_sum_rows", common.pallas_path())
    return into + _sum(rows, gates, pairs, runs, slot.reshape(T, K),
                       _bounds(first, runs), common.interpret())


def _bounds(first, runs):
    """``[first, n]``: the chunk's first sorted position and its rows."""
    return jnp.stack([jnp.asarray(first, jnp.int32),
                      jnp.sum(runs).astype(jnp.int32)])


# the rows as 32-bit words, one row a tile ---------------------------------

def _pack(a):
    """``[rows, d]`` bfloat16 or float32 -> ``uint32 [rows, W]``: float32
    bit for bit (``W = d``); bfloat16 with columns ``c`` and ``c + d/2``
    in the low and high halves of word ``c`` (``W = d / 2``)."""
    if a.dtype.itemsize == 4:
        return lax.bitcast_convert_type(a, jnp.uint32)
    u = lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)
    h = a.shape[1] // 2
    return u[:, :h] | (u[:, h:] << 16)


def _words_kernel(B, live, a, out):
    del live
    w = _pack(a[...])
    out[...] = w.reshape(B, 1, w.shape[1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _words(a, live, interpret):
    """``_pack``'s words of the rows ``0 .. live`` of ``a [N, d]``, one
    row a tile: ``uint32 [N, 1, W]`` (XLA's own relayout takes two
    passes over the rows and packs them in a third). Blocks past
    ``live`` are neither read nor written: what lies there is no
    row's."""
    pl, pltpu = _pallas()
    (N, d), B = a.shape, _block(a.shape[0], _WORD_ROWS)
    W = d * a.dtype.itemsize // 4

    def block(i, live):         # the last block holding a row, from there
        return jnp.minimum(i, lax.div(jnp.maximum(live[0] - 1, 0), B))

    return pl.pallas_call(
        functools.partial(_words_kernel, B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // B,),
            in_specs=[pl.BlockSpec((B, d), lambda i, n: (block(i, n), 0))],
            out_specs=pl.BlockSpec((B, 1, W),
                                   lambda i, n: (block(i, n), 0, 0))),
        out_shape=jax.ShapeDtypeStruct((N, 1, W), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(live, (1,)).astype(jnp.int32), a)


def _unwords(w, dtype):
    """``_words``' rows back, ``[rows, W]`` -> ``[rows, d]`` float32
    (a bfloat16 value is the high half of its float32)."""
    if jnp.dtype(dtype).itemsize == 4:
        return lax.bitcast_convert_type(w, jnp.float32)
    lo = lax.bitcast_convert_type(w << 16, jnp.float32)
    hi = lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), jnp.float32)
    return jnp.concatenate([lo, hi], axis=1)


def _block(total: int, most: int) -> int:
    """The most rows a grid step takes: a multiple of 8 up to ``most``
    that divides ``total``, or ``total`` itself."""
    return next((b for b in range(min(most, total), 7, -1)
                 if total % b == 0 and b % 8 == 0), total)


def _pallas():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


# sum_rows ------------------------------------------------------------------

def _each(lo, hi, body):
    """``body(j)`` for ``j`` in ``lo .. hi``, eight to a turn of the loop
    (a DMA's start costs the scalar unit a few cycles; the loop's own
    test and branch as many again)."""
    eights = lax.div(hi - lo, 8)

    def eight(i, c):
        for u in range(8):
            body(lo + 8 * i + u)
        return c

    lax.fori_loop(0, eights, eight, 0)
    lax.fori_loop(lo + 8 * eights, hi, lambda j, c: (body(j), c)[1], 0)


def _sum_kernel(Bt, K, H, nb, dtype, weighted, bounds, spans, pairs, slot,
                *refs):
    """One block of ``Bt`` tokens: a DMA a held row of theirs, each
    expert's rows of the block one span of the buffer (``spans``), into
    ``held`` at its choice's slice and its token's place there (choice-
    major, so that each choice's rows are one aligned slice); then each
    token's rows summed in float32, a skipped slot's by a select
    (``held`` keeps what an earlier block left there)."""
    pl, pltpu = _pallas()
    gates, (words, out, held, sem) = ((refs[0], refs[1:]) if weighted
                                      else (None, refs))
    b = pl.program_id(0)

    def copy(r, p):
        return pltpu.make_async_copy(
            words.at[pl.ds(r, 1)],
            held.at[pl.ds(lax.rem(p, K) * Bt + lax.rem(lax.div(p, K), Bt),
                          1)], sem)

    def run(e, moved):      # a loop, not unrolled: the step traces less
        lo, hi = spans[e * nb + b], spans[e * nb + b + 1]
        _each(lo, hi, lambda r: copy(r, pairs[r]).start())
        return moved + hi - lo

    _each(0, lax.fori_loop(0, H, run, 0), lambda r: copy(0, 0).wait())
    s = slot[...] - bounds[0]
    ok = (s >= 0) & (s < bounds[1])                         # [Bt, K]
    W = held.shape[-1]
    acc = jnp.zeros(out.shape, jnp.float32)
    for k in range(K):
        rows = _unwords(held[k * Bt:(k + 1) * Bt].reshape(Bt, W), dtype)
        if weighted:
            rows = rows * gates[:, k:k + 1]
        acc = acc + jnp.where(ok[:, k:k + 1], rows, 0)
    out[...] = acc.astype(out.dtype)


def _spans(pairs, runs, T: int, K: int, Bt: int):
    """``[H * (T / Bt + 1)]``: where each block of ``Bt`` tokens begins
    in each expert's rows of the buffer. A chunk holds its experts' rows
    one run after another (``runs [H]``), each run in token order (the
    sort is stable), so the rows ordered by (run, block) are the rows in
    buffer order, and a binary search finds every start."""
    R, H, nb = pairs.shape[0], runs.shape[0], T // Bt + 1
    ends = jnp.cumsum(runs)
    r = jnp.arange(R, dtype=jnp.int32)
    run = jnp.sum(r[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    key = run * nb + lax.div(pairs, Bt * K)     # past n: run H, past all
    return jnp.searchsorted(key, jnp.arange(H * nb, dtype=jnp.int32)
                            ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _sum_call(rows, gates, pairs, runs, slot, bounds, dtype, interpret):
    """``[T, d]`` in ``dtype``: the sums in float32, rounded once."""
    pl, pltpu = _pallas()
    (T, K), d, H = slot.shape, rows.shape[1], runs.shape[0]
    words = _words(rows, bounds[1], interpret)
    Bt = _block(T, max(_SUM_SLOTS // K, 8))
    block = pl.BlockSpec((Bt, K), lambda i, *_: (i, 0))
    weighted = gates is not None
    return pl.pallas_call(
        functools.partial(_sum_kernel, Bt, K, H, T // Bt + 1, rows.dtype,
                          weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(T // Bt,),
            in_specs=[block] + [block] * weighted
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((Bt, d), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((K * Bt, 1, words.shape[-1]), jnp.uint32),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((T, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(bounds, _spans(pairs, runs, T, K, Bt), pairs, slot,
      *([gates] * weighted), words)


# the gather's transpose and the sum's -------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gather(src, valid, pairs, runs, slot, bounds, interpret):
    return jnp.where(valid[:, None], src[pairs // slot.shape[1]], 0)


def _gather_fwd(src, valid, pairs, runs, slot, bounds, interpret):
    return (_gather(src, valid, pairs, runs, slot, bounds, interpret),
            (pairs, runs, slot, bounds))


def _gather_bwd(interpret, saved, g):
    """The gathered rows' cotangents summed back to their tokens in
    float32 and rounded once."""
    pairs, runs, slot, bounds = saved
    return (_sum_call(g, None, pairs, runs, slot, bounds, g.dtype,
                      interpret),) + (None,) * 5


_gather.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _sum(rows, gates, pairs, runs, slot, bounds, interpret):
    return _sum_call(rows, gates, pairs, runs, slot, bounds, jnp.float32,
                     interpret)


def _sum_fwd(rows, gates, pairs, runs, slot, bounds, interpret):
    return (_sum(rows, gates, pairs, runs, slot, bounds, interpret),
            (rows, gates, pairs, slot, bounds))


def _sum_bwd(interpret, saved, dy):
    """``dy`` gathered to the held rows at the rows' type (the layer's
    output is cast to it, so its cotangent already is one): a row's
    cotangent is its gate times that, a gate's the row's product with
    it, summed over the width in float32."""
    rows, gates, pairs, slot, bounds = saved
    (T, K), R = gates.shape, rows.shape[0]
    valid = jnp.arange(R) < bounds[1]
    dyg = jnp.where(valid[:, None], dy.astype(rows.dtype)[pairs // K],
                    0).astype(jnp.float32)
    gate = gates.reshape(-1)[pairs]
    drows = (dyg * gate[:, None]).astype(rows.dtype)
    # a gate's cotangent from the row its slot names; rows past n are
    # never read (what lies there may be no number)
    s = slot - bounds[0]
    ok = (s >= 0) & (s < bounds[1])                        # [T, K]
    dgate = jnp.sum(rows.astype(jnp.float32) * dyg, axis=1)
    return (drows, jnp.where(ok, dgate[jnp.clip(s, 0, R - 1)], 0), None,
            None, None, None)


_sum.defvjp(_sum_fwd, _sum_bwd)
