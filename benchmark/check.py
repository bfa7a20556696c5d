"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (driven by set-up through the window's
own call and feed) against the plain reference's three, from the same
weights and batches:

- ``loss``: the widest |program - reference| over the three steps;
- ``grad``: the first gradient as the optimizer got it, by the worst
  leaf: | ||g_prog|| - ||g_ref|| | over max(||g_ref|| of the leaf, of
  the median leaf);
- ``change``: the same measure of ||P3 - P0||, over the leaves whose
  reference gradient is at least a thousandth of the median leaf's (the
  others move under Adam by round-off alone);
- ``grad_diff``: the norm of the difference of the two first gradients,
  leaf by leaf, against the same denominators. A gap of norms is of
  second order in an elementwise error (rounding noise is all but
  orthogonal to the gradient), so it cannot tell bfloat16 from fp8; the
  difference is of first order and can (PERF.md, Probe P).

``readings`` come from the program through ``Recorder``; ``follow`` runs
the reference, in the arithmetic the configuration states
(``stated_arith``), or a control in its place. ``limits`` are the
cell's, from ``cells/<cell>.json``.
"""

from __future__ import annotations

import importlib
import json
import os
import statistics
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference import plain

HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 3
NEGLIGIBLE = 1e-3     # of the median leaf's reference gradient norm


def reference_module(config_name: str):
    return importlib.import_module(f"benchmark.reference.{config_name}")


def hyper(cfg: dict) -> dict:
    a = cfg["optimizer"]["args"]
    return {"lr": a["learning_rate"], "beta1": a.get("beta1"),
            "beta2": a.get("beta2"), "epsilon": a.get("epsilon"),
            "momentum": a.get("momentum"),
            "weight_decay": a.get("l2_rate", 0.0),
            "clip": a.get("gradient_clipping_threshold", 0.0)}


def stated_arith(cfg: dict) -> plain.Arith:
    """The arithmetic the reference computes in: what the configuration
    states (``precision.reference``), float32 at ``highest`` where it
    states none."""
    return plain.Arith(**cfg["precision"].get("reference", {}))


def _floats(tree) -> Dict[str, float]:
    return {n: float(v) for n, v in jax.device_get(tree).items()}


class Recorder:
    """Takes the program's readings at its first three steps. ``weights``
    makes the initial leaves again (the program's were donated to its
    first step); ``slot`` and ``params`` read the program's state."""

    def __init__(self, cfg: dict, names: List[str], weights: Callable,
                 slot: Callable, params: Callable):
        self.cfg, self.names = cfg, names
        self.weights, self.slot, self.params = weights, slot, params
        self.losses: List[float] = []
        self.grad_norms = self.change_norms = self.first_grad = None

    def after_step(self, index: int, loss: float) -> None:
        if index >= STEPS:
            return
        self.losses.append(float(loss))
        kind = self.cfg["optimizer"]["kind"]
        if index == 0:
            _, _, first, name = plain.OPTIMIZERS[kind]
            hp = hyper(self.cfg)
            grads = jax.jit(lambda s, p0: first(s, p0, hp))(
                {n: v for n, v in self.slot(name).items()
                 if n in self.names},
                {n: v for n, v in self.weights().items()
                 if n in self.names})
            self.grad_norms = _floats(jax.jit(plain.norms)(grads))
            # kept on the host until the reference has its own: the
            # difference of the two is what tells precisions apart
            self.first_grad = jax.device_get(grads)
        if index == STEPS - 1:
            now = {n: v for n, v in self.params().items()
                   if n in self.names}
            got = jax.jit(lambda p, p0: plain.norms(
                {n: p[n] - p0[n] for n in p}))(
                now, {n: v for n, v in self.weights().items()
                      if n in self.names})
            self.change_norms = _floats(got)

    def readings(self) -> dict:
        return {"loss": list(self.losses), "grad": self.grad_norms,
                "change": self.change_norms, "first_grad": self.first_grad}


def follow(config_name: str, cfg: dict, weights: dict, batches: Callable,
           arith: plain.Arith = plain.Arith(), *, against: dict = None,
           keep_first_grad: bool = False, steps: int = STEPS) -> dict:
    """The reference's three steps: ``{"loss": [..], "grad": {leaf: norm},
    "change": {leaf: norm}}``. ``batches(i)`` gives step i's arrays.
    ``against`` is another run's first gradient (host arrays): the norm
    of its difference from this one's comes back as ``grad_diff``.
    ``keep_first_grad`` hands this run's own back on the host (a control
    put in the program's place needs it)."""
    ref = reference_module(config_name)
    names = plain.trained(ref.leaves(cfg))
    init, step = plain.OPTIMIZERS[cfg["optimizer"]["kind"]][:2]
    hp = hyper(cfg)
    fixed = {n: v for n, v in weights.items() if n not in names}

    @jax.jit
    def one(params, batch):
        def f(p):
            return ref.loss({**fixed, **p}, batch, cfg, arith)
        return jax.value_and_grad(f)(params)

    params0 = {n: weights[n] for n in names}
    params, state = params0, init(params0)
    losses, grad_norms, grad_diff, first_grad = [], None, None, None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            batch = {k: jnp.asarray(v) for k, v in batches(i).items()}
            loss, grads = one(params, batch)
            losses.append(float(loss))
            if i == 0:
                grad_norms = _floats(plain.norms(grads))
                if against is not None:
                    grad_diff = _floats(jax.jit(lambda g, o: plain.norms(
                        {n: o[n] - g[n] for n in g}))(
                        grads, {n: against[n] for n in names}))
                if keep_first_grad:
                    first_grad = jax.device_get(grads)
            params, state = jax.jit(
                lambda p, g, s, t=i + 1: step(p, g, s, hp, t))(
                params, grads, state)
            del grads
        change = _floats(plain.norms(
            {n: params[n] - params0[n] for n in names}))
    return {"loss": losses, "grad": grad_norms, "change": change,
            "grad_diff": grad_diff, "first_grad": first_grad}


def _leaf_gaps(got: Dict[str, float], ref: Dict[str, float],
               names: List[str]) -> Dict[str, float]:
    """Per leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    median = statistics.median(ref.values())
    return {n: abs(got[n] - ref[n]) / max(ref[n], median) for n in names}


def compare(got: dict, ref: dict) -> dict:
    """Every number a cell may compare: ``loss_first`` (step 1 alone)
    and ``loss`` (the widest of the three steps); ``grad`` and ``change``
    by the worst leaf (``*_at`` names it), ``grad_median`` and
    ``change_median`` by the median leaf, which is steady from seed to
    seed where the worst leaf is one small leaf's noise (PERF.md);
    ``grad_diff`` and ``grad_diff_median`` where the reference was
    followed ``against`` the program's first gradient."""
    finite = len(got["loss"]) == STEPS and all(
        v == v and abs(v) != float("inf") for v in got["loss"])
    loss_gaps = [abs(a - b) for a, b in zip(got["loss"], ref["loss"])] \
        if finite else [float("inf")] * STEPS
    median = statistics.median(ref["grad"].values())
    moved = [n for n in ref["grad"] if ref["grad"][n] >= NEGLIGIBLE * median]
    grad = _leaf_gaps(got["grad"], ref["grad"], list(ref["grad"]))
    change = _leaf_gaps(got["change"], ref["change"], moved)
    out = {}
    if ref.get("grad_diff") is not None:
        diff = {n: ref["grad_diff"][n] / max(ref["grad"][n], median)
                for n in ref["grad"]}
        out = {"grad_diff": max(diff.values()),
               "grad_diff_at": max(diff, key=diff.get),
               "grad_diff_median": statistics.median(diff.values())}
    return {**out, "loss_first": loss_gaps[0], "loss": max(loss_gaps),
            "loss_at": loss_gaps.index(max(loss_gaps)),
            "grad": max(grad.values()), "grad_at": max(grad, key=grad.get),
            "grad_median": statistics.median(grad.values()),
            "change": max(change.values()),
            "change_at": max(change, key=change.get),
            "change_median": statistics.median(change.values()),
            "left_out": sorted(set(ref["grad"]) - set(moved))}


def limits(cell: str, root: str = HERE) -> Dict[str, float]:
    """``cells/<cell>.json`` beside the configurations' directory: the
    cell's limits with the readings they were set from, a file of its
    own so that a later cell brings its own."""
    with open(os.path.join(root, "cells", f"{cell}.json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def verdict(numbers: dict, lim: Dict[str, float]) -> dict:
    """``{"correct": bool, "compared": {name: [number, limit]}}``; a
    number that is not finite fails."""
    compared = {k: [float(numbers[k]), lim[k]] for k in lim}
    ok = all(v == v and v <= limit for v, limit in compared.values())
    return {"correct": bool(ok), "compared": compared}
