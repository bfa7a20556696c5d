"""The controls and the faults of "how ``correct`` is decided", at the
cell's own size on the chip, several seeds in one process:

    python3 -m benchmark.control --workload <name> --seeds 1,2,3 [--what control,half,unchanged,sound]

``control`` runs the configuration's lower-precision control in the
program's place; ``half`` leaves half of every batch out of the step
(the mean taken over the rest); ``unchanged`` has the step return its
state unchanged; ``sound`` is the program as configured. Each prints the
numbers compared beside the cell's limits; everything but ``sound`` has
to come out as not correct. The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import sys


def _copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, tree)


def unchanged(prog) -> None:
    """The step returns the state it was given."""
    step = prog.trainer._train_step

    def faulty(params, opt_state, *rest):
        _p, _o, metrics = step(_copy(params), _copy(opt_state), *rest)
        return params, opt_state, metrics

    prog.trainer._train_step = faulty


def rows_left_out(keep: float):
    """The step sees only the first ``keep`` share of every batch's rows
    and takes the mean over those: ``keep=0.5`` is half of the batch
    left out."""
    def tamper(prog) -> None:
        import jax
        step = prog.trainer._train_step

        def faulty(params, opt_state, feed, *rest):
            n = int(jax.tree_util.tree_leaves(feed)[0].shape[0] * keep)
            part = jax.tree_util.tree_map(lambda x: x[:n], feed)
            return step(params, opt_state, part, *rest)

        prog.trainer._train_step = faulty
    return tamper


FAULTS = {"unchanged": unchanged, "half": rows_left_out(0.5)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="control")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    from benchmark import run
    for what in args.what.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            result = run.run_cell(
                args.workload, seed, args.seconds, False,
                control=(what == "control"), tamper=FAULTS.get(what))
            print(json.dumps({"what": what, "seed": seed,
                              "correct": result["correct"],
                              "compared": result["compared"],
                              "numbers": result["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
