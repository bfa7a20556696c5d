"""DataFeeder: python samples -> host Arguments.

Replaces ``py_paddle.DataProviderConverter`` (``paddle/py_paddle/
dataprovider_converter.py``) + the SWIG ``Arguments`` assembly: given input
type declarations, converts a minibatch (list of tuples) into a feed dict of
padded Arguments. Sequence inputs are padded to ``pad_multiple`` to bound
XLA recompilation (bucketed static shapes) — the TPU answer to ragged
offset batches. ``length_buckets`` tightens that bound to a fixed menu of
padded lengths, and ``batch_buckets`` pads short (e.g. final partial)
batches up to a bucketed row count with all-masked rows plus a
``ROW_MASK_KEY`` feed entry the trainer uses to ignore them exactly
(zero loss, zero grad — see ``trainer/trainer.py:_total_cost``).

Every leaf of the feed is a ``numpy.ndarray``: the feeder builds on the
host and places nothing. Placement belongs to whoever times it: the
prefetch worker's ``prefetch.h2d`` (``data/prefetch.py``), the trainer's
``train.h2d`` on the synchronous path, or the jitted function a feed is
handed to, which takes numpy leaves as they are.
"""

from __future__ import annotations

import collections
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.core.argument import Argument
from paddle_tpu.data import types as T

# feed-dict entry carrying the [B] f32 row-validity mask emitted when
# batch_buckets pads the batch dim. Not a data layer: Network.apply only
# reads data-layer names, so the entry flows untouched to the trainer.
# Like every mask it is f32 COUNT data (never cast to bf16); the trainer
# reads it from the *uncast* feed.
ROW_MASK_KEY = "__row_mask__"


def _ceil_to(n: int, m: int) -> int:
    return ((max(n, 1) + m - 1) // m) * m


def _zero_sample(itype: T.InputType):
    """An all-padding sample for one input slot: empty for sequences
    (rows pad to an all-zero mask), zeros otherwise."""
    if itype.seq_type != T.NO_SEQUENCE:
        return []
    if itype.type == T.INDEX:
        return 0
    if itype.type in (T.SPARSE_BINARY, T.SPARSE_FLOAT):
        return []
    return np.zeros(itype.dim, dtype=np.float32)


class _Staging:
    """Host memory for the dense batches of one feeder, handed out again
    once nothing refers to the batch that had it.

    A 154 MB batch in fresh memory costs ten times its copy: the
    allocator maps new pages for every array of that size and the first
    write faults each one in (PERF.md, PR 26: 167 ms fresh against 15 ms
    in memory written before, on the chip's host). So ``empty`` hands
    out arrays over blocks it keeps, and takes a block back at the moment
    numpy would have freed it: when the array and every view of it are
    gone. A batch therefore never changes under anyone who can still
    read it, on any backend and from any thread. That covers a transfer
    in flight: the runtime keeps the host array it reads from alive until
    the copy is done (or, where a CPU device array aliases it, for that
    array's life), exactly as it must for memory numpy frees.

    No lock: the release runs wherever the last reference drops, maybe
    inside another call of this class on the same thread. Every step is
    one atomic deque operation; two callers racing for one block cost a
    fresh allocation, never a shared block."""

    KEEP = 4        # free blocks kept; one more is freed as numpy would

    def __init__(self):
        self._free = collections.deque(maxlen=self.KEEP)
        self.allocated = 0      # blocks ever made: a steady stream stops

    def empty(self, shape: Tuple[int, ...]) -> np.ndarray:
        """An uninitialised float32 array of ``shape``."""
        block = self._take(4 * int(np.prod(shape, dtype=np.int64)))
        # the array over a block is no view of another array (its base is
        # the block's memoryview), so every view of it keeps *it* alive
        # and its end is the end of all of them
        root = np.frombuffer(block, dtype=np.float32)
        weakref.finalize(root, self._free.append, block).atexit = False
        return root.reshape(shape)

    def _take(self, nbytes: int) -> memoryview:
        for _ in range(len(self._free)):
            try:
                block = self._free.popleft()
            except IndexError:
                break
            if block.nbytes == nbytes:
                return block
            self._free.append(block)    # another shape's: back in line
        self.allocated += 1
        return memoryview(np.empty(nbytes, dtype=np.uint8))


class DataFeeder:
    def __init__(self, feeding: Dict[str, T.InputType],
                 pad_multiple: int = 32,
                 length_buckets: Optional[Sequence[int]] = None,
                 batch_buckets: Optional[Sequence[int]] = None,
                 validate_ids: Optional[bool] = None,
                 shared_length_bucket: bool = False):
        """feeding: data-layer name -> InputType, in feed order if the
        reader yields tuples. ``length_buckets``: fixed menu of padded
        sequence lengths (``data/prefetch.py:LengthBuckets``) overriding
        the pad_multiple ceiling. ``batch_buckets``: menu of batch sizes;
        short batches pad up with dead rows + a ROW_MASK_KEY entry.

        ``shared_length_bucket``: pad EVERY single-level sequence slot of
        a batch to ONE bucket (of the max raw length across all such
        slots) instead of bucketing each slot independently. Serving
        turns this on so its warmed shape menu is the bucket LIST, not
        the cross-product of per-slot buckets — a multi-sequence-input
        model otherwise has unwarmed legal shape combinations.

        ``validate_ids`` (debug mode; default from the
        ``PADDLE_TPU_VALIDATE_IDS`` env var) checks every INDEX input
        against its declared range on the host and raises with the
        offending id and input/layer name. The device-side table lookup
        cannot raise (jit shapes are static): it maps out-of-range ids to
        zero rows (``layers/common.py:_table_lookup``), so this check is
        the loud counterpart of the reference's CHECK-fail
        (``TableProjection.cpp``)."""
        import os
        self.feeding = feeding
        self.names = list(feeding)
        self.pad_multiple = pad_multiple
        if validate_ids is None:
            validate_ids = os.environ.get(
                "PADDLE_TPU_VALIDATE_IDS", "").lower() in ("1", "true", "yes")
        self.validate_ids = bool(validate_ids)
        self.length_buckets = None
        if length_buckets is not None:
            from paddle_tpu.data.prefetch import LengthBuckets
            self.length_buckets = (
                length_buckets if isinstance(length_buckets, LengthBuckets)
                else LengthBuckets(length_buckets))
        self.batch_buckets = (sorted(int(b) for b in batch_buckets)
                              if batch_buckets else None)
        self.shared_length_bucket = bool(shared_length_bucket)
        self._staging = _Staging()

    def _pad_len(self, raw_max: int) -> int:
        if self.length_buckets is not None:
            return self.length_buckets.pad_len(raw_max)
        return _ceil_to(raw_max, self.pad_multiple)

    def convert(self, batch: List[Tuple]) -> Dict[str, Argument]:
        n_real = len(batch)
        row_mask = None
        if self.batch_buckets:
            import bisect
            # batch sizes are a CLOSED menu (unlike lengths, there is no
            # overflow rule): a batch beyond the largest bucket is a
            # reader/config mismatch, not something to pad around
            i = bisect.bisect_left(self.batch_buckets, n_real)
            if i == len(self.batch_buckets):
                raise ValueError(
                    f"batch of {n_real} exceeds the largest batch bucket "
                    f"{self.batch_buckets[-1]}; include the reader's "
                    "batch size in batch_buckets")
            target = self.batch_buckets[i]
            pad_row = tuple(_zero_sample(self.feeding[n])
                            for n in self.names)
            batch = list(batch) + [pad_row] * (target - n_real)
            # emitted whenever bucketing is on (even unpadded batches) so
            # the feed's pytree structure is step-invariant — a structure
            # flip would itself force a jit recompile
            row_mask = np.zeros(target, dtype=np.float32)
            row_mask[:n_real] = 1.0
        cols = list(zip(*batch))
        if len(cols) != len(self.names):
            raise ValueError(
                f"batch has {len(cols)} columns, feeder expects "
                f"{len(self.names)} ({self.names})")
        pad_to = None
        if self.shared_length_bucket:
            # one padded length for every single-level sequence slot:
            # bucket of the global raw max across those slots
            raw = [len(s) for name, col in zip(self.names, cols)
                   if self.feeding[name].seq_type == T.SEQUENCE
                   for s in col]
            if raw:
                pad_to = self._pad_len(max(raw))
        feed = {}
        for name, col in zip(self.names, cols):
            feed[name] = self._convert_one(self.feeding[name], col, name,
                                           pad_to=pad_to)
        if row_mask is not None:
            feed[ROW_MASK_KEY] = Argument(value=row_mask)
        return feed

    __call__ = convert

    def _check_ids(self, name, itype: T.InputType, value: np.ndarray,
                   mask: Optional[np.ndarray] = None):
        """Debug-mode host-side range check for INDEX inputs: raises with
        the offending id and the input (data-layer) name. -1 stays legal
        (the OOV ignore sentinel); padding positions (mask 0) are
        exempt."""
        if not self.validate_ids:
            return
        bad = (value >= itype.dim) | (value < -1)
        if mask is not None:
            bad &= mask > 0
        if bad.any():
            pos = tuple(int(i) for i in np.argwhere(bad)[0])
            raise ValueError(
                f"input {name!r}: id {int(value[pos])} at position {pos} "
                f"is outside the declared range [-1, {itype.dim}). The "
                "reference CHECK-fails here (TableProjection.cpp); the "
                "jitted table lookup maps such ids to zero rows instead "
                "of raising — fix the data or the declared dimension.")

    def _stack_dense(self, col: Sequence, name: str) -> np.ndarray:
        """Rows -> one float32 array, a copy a row. Array assignment
        runs without the GIL and writes into memory the feeder has used
        before; ``np.asarray(col)`` holds neither promise. Values, shape
        and dtype are what ``np.asarray(col, dtype=np.float32)`` gives."""
        shape = np.shape(col[0])
        out = self._staging.empty((len(col),) + shape)
        for i, row in enumerate(col):
            if not isinstance(row, np.ndarray):
                row = np.asarray(row)
            if row.shape != shape:
                # assignment would broadcast a short row in silence
                raise ValueError(
                    f"input {name!r}: row {i} has shape {row.shape}, "
                    f"row 0 has {shape}; a dense_vector batch needs "
                    "rows of one shape")
            out[i] = row
        return out

    def _convert_one(self, itype: T.InputType, col: Sequence,
                     name: str = "?",
                     pad_to: Optional[int] = None) -> Argument:
        if itype.seq_type == T.NO_SEQUENCE:
            if itype.type == T.INDEX:
                arr = np.asarray(col, dtype=np.int32)
                self._check_ids(name, itype, arr)
                return Argument(value=arr)
            if itype.type == T.DENSE:
                return Argument(value=self._stack_dense(col, name))
            if itype.type in (T.SPARSE_BINARY, T.SPARSE_FLOAT):
                dense = np.zeros((len(col), itype.dim), dtype=np.float32)
                for i, idxs in enumerate(col):
                    if itype.type == T.SPARSE_BINARY:
                        dense[i, np.asarray(idxs, dtype=np.int64)] = 1.0
                    else:
                        for j, v in idxs:
                            dense[i, j] = v
                return Argument(value=dense)
            raise KeyError(itype.type)
        if itype.seq_type == T.SUB_SEQUENCE:
            # nested: sample = list of sub-sequences -> [B, S, T(, D)]
            # with a [B, S, T] mask (the 2-level padded layout the
            # nested recurrent groups consume, layers/group.py)
            B = len(col)
            S = max(len(s) for s in col)
            Tm = self._pad_len(max((len(ss) for s in col for ss in s),
                                   default=1))
            mask = np.zeros((B, S, Tm), dtype=np.float32)
            if itype.type == T.INDEX:
                value = np.zeros((B, S, Tm), dtype=np.int32)
                for i, s in enumerate(col):
                    for j, ss in enumerate(s):
                        value[i, j, : len(ss)] = np.asarray(ss,
                                                            dtype=np.int32)
                        mask[i, j, : len(ss)] = 1.0
                self._check_ids(name, itype, value, mask)
            elif itype.type == T.DENSE:
                value = np.zeros((B, S, Tm, itype.dim), dtype=np.float32)
                for i, s in enumerate(col):
                    for j, ss in enumerate(s):
                        arr = np.asarray(ss, dtype=np.float32).reshape(
                            len(ss), itype.dim)
                        value[i, j, : len(ss)] = arr
                        mask[i, j, : len(ss)] = 1.0
            else:
                value = np.zeros((B, S, Tm, itype.dim), dtype=np.float32)
                for i, s in enumerate(col):
                    for j, ss in enumerate(s):
                        for t, idxs in enumerate(ss):
                            if itype.type == T.SPARSE_BINARY:
                                value[i, j, t, np.asarray(
                                    idxs, dtype=np.int64)] = 1.0
                            else:
                                for k, v in idxs:
                                    value[i, j, t, k] = v
                            mask[i, j, t] = 1.0
            return Argument(value=value, mask=mask)
        # sequences: pad to multiple / bucket edge for shape bucketing
        # (pad_to = the batch-wide shared bucket, shared_length_bucket)
        max_len = pad_to or self._pad_len(max(len(s) for s in col))
        bsz = len(col)
        mask = np.zeros((bsz, max_len), dtype=np.float32)
        if itype.type == T.INDEX:
            value = np.zeros((bsz, max_len), dtype=np.int32)
            for i, s in enumerate(col):
                value[i, : len(s)] = np.asarray(s, dtype=np.int32)
                mask[i, : len(s)] = 1.0
            self._check_ids(name, itype, value, mask)
        elif itype.type in (T.SPARSE_BINARY, T.SPARSE_FLOAT):
            # per-timestep index lists (sparse_binary_vector_sequence,
            # e.g. the sequence-tagging demo's feature slot) densify to
            # the padded [B, T, dim] layout like every sequence input
            value = np.zeros((bsz, max_len, itype.dim), dtype=np.float32)
            for i, s in enumerate(col):
                for t, idxs in enumerate(s):
                    if itype.type == T.SPARSE_BINARY:
                        value[i, t, np.asarray(idxs, dtype=np.int64)] = 1.0
                    else:
                        for k, v in idxs:
                            value[i, t, k] = v
                    mask[i, t] = 1.0
        else:
            value = np.zeros((bsz, max_len, itype.dim), dtype=np.float32)
            for i, s in enumerate(col):
                arr = np.asarray(s, dtype=np.float32).reshape(len(s), itype.dim)
                value[i, : len(s)] = arr
                mask[i, : len(s)] = 1.0
        return Argument(value=value, mask=mask)
