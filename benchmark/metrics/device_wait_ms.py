"""Milliseconds a step of the window the trainer's thread was blocked on
the device: the host read of the cost alone (``StepBreakdown``
``device_wait``, span ``train.device_wait``)."""


def read(ctx):
    try:
        return ctx["window"].host_ms_per_step("device_wait")
    except KeyError:        # a program whose breakdown has no such key
        return None
