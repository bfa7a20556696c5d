"""Milliseconds a step of the window the prefetch thread spent in the
feeder, rows to arrays (``StepBreakdown`` ``prefetch_decode``, span
``prefetch.decode``); concurrent with the trainer's thread."""


def read(ctx):
    try:
        return ctx["window"].host_ms_per_step("prefetch_decode")
    except KeyError:        # a program whose breakdown has no such key
        return None
