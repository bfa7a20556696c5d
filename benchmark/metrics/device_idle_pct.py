"""The share of the traced window in which no operation ran on the
device: 1 - the union of the device's operation intervals over the
window, on the worst device."""


def read(ctx):
    if ctx["trace"] is None or ctx["peak"] is None:
        return None
    return 100.0 * ctx["trace"].idle_share()
