"""The pass at which a position exits on average under the exit
distribution, ``sum_t t p_t`` (1 .. R), over the window: the program's
own counter ``loop_exit_step_mean`` (``StepBreakdown.totals``, a step's
mean over its positions), the window's end less its start over the
steps between. A reading pinned at 1 or at R is a gate that has
collapsed: the weighted loss is then one pass's loss and the other
heads dead weight. A program without the counter gives nothing to
read."""


def read(ctx):
    win = ctx["window"]
    a, b = win.at_open or {}, win.at_close or {}
    if "loop_exit_step_mean" not in b:
        return None
    steps = b["steps"] - a.get("steps", 0)
    if steps <= 0:
        return None
    return (b["loop_exit_step_mean"]
            - a.get("loop_exit_step_mean", 0.0)) / steps
