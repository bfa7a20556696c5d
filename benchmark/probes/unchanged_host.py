"""``benchmark.control``'s ``unchanged`` for a cell whose state fills the
chip: there the step is given COPIES of its state on the device (it
donates what it is given), and at 436M parameters a second 5.24 GB of
masters and moments beside the step's 6.0 GB of temporaries does not fit
16.9 GB (``RESOURCE_EXHAUSTED``, my chip run, PR 33). The same fault with
the copy kept on the host: ``unchanged_host`` runs the real step, drops
what it returns and hands back the state as it was at the start, placed
again from the host's copy.

    python3 -m benchmark.probes.unchanged_host --workload <name> --seeds 1 --what unchanged_host

It has to come out as not correct (no moment to read a gradient from, no
change: those numbers read 1). The benchmark's own runs never call this.
"""

from __future__ import annotations

import sys

from benchmark import control


def unchanged_host(prog) -> None:
    """The step returns the state the trainer started from."""
    import jax
    step = prog.trainer._train_step
    start = jax.device_get((prog.trainer.params, prog.trainer.opt_state))

    def faulty(params, opt_state, *rest):
        _p, _o, metrics = step(params, opt_state, *rest)
        del _p, _o
        params, opt_state = jax.device_put(start)
        return params, opt_state, metrics

    prog.trainer._train_step = faulty


control.FAULTS["unchanged_host"] = unchanged_host

if __name__ == "__main__":
    sys.exit(control.main())
