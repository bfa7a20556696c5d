"""Milliseconds a step of the window the prefetch thread spent in the
``device_put`` call, which returns before the copy ends
(``StepBreakdown`` ``prefetch_h2d``, span ``prefetch.h2d``)."""


def read(ctx):
    try:
        return ctx["window"].host_ms_per_step("prefetch_h2d")
    except KeyError:        # a program whose breakdown has no such key
        return None
