"""The whole step's share of the chip's bf16 peak: the FLOPs the forward
and backward passes need per sample (``benchmark/counts/<config>.py``,
from shapes; recomputed or padded work does not count) times the samples
finished in the window, over the window's seconds, the chips and the
peak."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    win = ctx["window"]
    flops = ctx["counts"].step_flops_per_sample(ctx["cfg"], ctx["mix"])
    samples = win.steps * int(ctx["mix"]["batch"])
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops * samples / (win.window_s * peak)
