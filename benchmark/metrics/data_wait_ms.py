"""Milliseconds a step of the window waited for its batch: the
trainer's own ``StepBreakdown`` ``data_wait``, window's end less its
start, over the steps between."""


def read(ctx):
    return ctx["window"].host_ms_per_step("data_wait")
