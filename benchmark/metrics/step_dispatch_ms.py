"""Milliseconds a step of the window spent from the feed in hand to the
jitted step's return, on the trainer's thread: the rng split, the step's
scalars, the jit call's argument handling and enqueue (``StepBreakdown``
``dispatch``, span ``train.dispatch``)."""


def read(ctx):
    try:
        return ctx["window"].host_ms_per_step("dispatch")
    except KeyError:        # a program whose breakdown has no such key
        return None
