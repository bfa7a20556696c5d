"""Peak device memory on the fullest chip, GiB: the allocator's
``peak_bytes_in_use`` plus ``peak_bytes_reserved`` (Probe M, PERF.md: on
this runtime the compiled step's temporaries are the reserved bytes).
What is left of the chip is batch a later PR can add."""


def read(ctx):
    if ctx["memory_peak_bytes"] is None or ctx["peak"] is None:
        return None
    return ctx["memory_peak_bytes"] / 2 ** 30
