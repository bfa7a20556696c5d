"""``benchmark.control`` for a cell whose batch is one row, where its
``half`` (half of every batch's ROWS left out) would leave the step
nothing: the same controls with one more fault, ``half_tokens``, under
which the step sees only the first half of every row's positions and
takes its mean over those.

    python3 -m benchmark.probes.half_tokens --workload <name> --seeds 1,2 --what half_tokens

It has to come out as not correct. The benchmark's own runs never call
this.
"""

from __future__ import annotations

import sys

from benchmark import control


def positions_left_out(keep: float):
    """The step sees the first ``keep`` share of every row's positions
    (values and masks alike: every leaf of a sequence feed is [rows,
    positions, ...])."""
    def tamper(prog) -> None:
        import jax
        step = prog.trainer._train_step

        def faulty(params, opt_state, feed, *rest):
            part = jax.tree_util.tree_map(
                lambda x: x[:, :int(x.shape[1] * keep)], feed)
            return step(params, opt_state, part, *rest)

        prog.trainer._train_step = faulty
    return tamper


control.FAULTS["half_tokens"] = positions_left_out(0.5)

if __name__ == "__main__":
    sys.exit(control.main())
