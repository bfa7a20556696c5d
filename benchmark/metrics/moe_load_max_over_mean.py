"""The fullest held expert's rows over the mean held expert's, over the
window: the program's own counters ``moe_rows_max`` and ``moe_rows_mean``
(``StepBreakdown.totals``, each a step's mean over the expert layers),
the window's end less its start. 1 is a perfectly even load; the grouped
products wait for the fullest expert."""


def read(ctx):
    win = ctx["window"]
    a, b = win.at_open or {}, win.at_close or {}
    if "moe_rows_mean" not in b:
        return None
    mean = b["moe_rows_mean"] - a.get("moe_rows_mean", 0.0)
    if mean <= 0:
        return None
    return (b["moe_rows_max"] - a.get("moe_rows_max", 0.0)) / mean
