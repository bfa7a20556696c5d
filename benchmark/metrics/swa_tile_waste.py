"""What the sliding layers' tiling computes over what their mask lets
see: the program's own counters ``swa_pairs_visited`` (query-key pairs
inside the tiles the forward kernel's grid walks, a head) over
``swa_pairs_visible`` (``StepBreakdown.totals``, each a step's mean over
the sliding layers), the window's end less its start. 1 would be a
tiling that computes no masked pair; a window of 512 in 512 x 512 tiles
reads 2."""


def read(ctx):
    win = ctx["window"]
    a, b = win.at_open or {}, win.at_close or {}
    if "swa_pairs_visible" not in b:
        return None
    visible = b["swa_pairs_visible"] - a.get("swa_pairs_visible", 0.0)
    if visible <= 0:
        return None
    return (b["swa_pairs_visited"] - a.get("swa_pairs_visited", 0.0)) \
        / visible
