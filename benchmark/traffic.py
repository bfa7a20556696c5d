"""The one general generator of training traffic.

A traffic mix is a data file ``traffic/<name>.json`` beside the
configurations' directory: kind, global batch, sequence length, pool
size and mesh. What a row holds comes from the configuration's ``inputs``:
``integer_value_sequence`` draws ``seq_len`` ids uniformly below ``dim``,
``integer_value`` one label, ``dense_vector`` ``dim`` standard normals.

Every batch is a function of ``(seed, index)`` alone, so the reference
can draw the first steps' batches again after the window. A batch under
a mebibyte is drawn fresh at every step (a model cannot memorise it); a
larger one, which the host cannot draw in a step's time, comes from a
pool of ``pool`` batches made in set-up and cycled, every seed with the
same sizes.
"""

from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
POOLED_FROM_BYTES = 1 << 20


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("kind") != "train":
        raise ValueError(f"traffic {name!r}: this generator makes "
                         f"'train' traffic, not {mix.get('kind')!r}")
    return mix


def _draw(rng, spec: dict, batch: int, seq_len: int) -> np.ndarray:
    kind, dim = spec["type"], int(spec["dim"])
    if kind == "integer_value_sequence":
        return rng.integers(0, dim, size=(batch, seq_len), dtype=np.int32)
    if kind == "integer_value":
        return rng.integers(0, dim, size=(batch,), dtype=np.int32)
    if kind == "dense_vector":
        return rng.standard_normal((batch, dim), dtype=np.float32)
    raise ValueError(f"no generator for input type {kind!r}")


class Batches:
    """``at(i)``: the i-th batch of the run, ``{input: array}``."""

    def __init__(self, inputs: Dict[str, dict], mix: dict, seed: int):
        self.inputs, self.mix, self.seed = inputs, mix, int(seed)
        self.batch = int(mix["batch"])
        self.seq_len = int(mix.get("seq_len", 0))
        probe = self._make(0)
        pooled = sum(a.nbytes for a in probe.values()) >= POOLED_FROM_BYTES
        self.pool = ([probe] + [self._make(k)
                                for k in range(1, int(mix["pool"]))]
                     if pooled else None)

    def _make(self, index: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, index])
        return {name: _draw(rng, spec, self.batch, self.seq_len)
                for name, spec in self.inputs.items()}

    def at(self, index: int) -> Dict[str, np.ndarray]:
        if self.pool is not None:
            return self.pool[index % len(self.pool)]
        return self._make(index)
