"""Milliseconds a step of the window spent on the trainer's thread
outside the device step: ``StepBreakdown`` ``h2d`` + ``callback`` (the
benchmark's own event handler is inside ``callback``)."""


def read(ctx):
    return ctx["window"].host_ms_per_step("h2d", "callback")
