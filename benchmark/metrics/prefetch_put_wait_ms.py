"""Milliseconds a step of the window the prefetch thread was blocked on
its full queue: the room the pipeline has over the trainer. About 0
with ``data_wait_ms`` above 0 means the step is bound by its input
(``StepBreakdown`` ``prefetch_put_wait``, span ``prefetch.put_wait``)."""


def read(ctx):
    try:
        return ctx["window"].host_ms_per_step("prefetch_put_wait")
    except KeyError:        # a program whose breakdown has no such key
        return None
