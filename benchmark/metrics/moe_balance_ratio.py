"""The load-balancing term over its value at a balanced router, a step's
mean over the window: the program's own counter ``moe_balance``
(``StepBreakdown.totals``, the window's end less its start). 1 is a
router that spreads its probability and its choices evenly over the
experts; ``E / k`` (8 for 64 experts, 8 a token) one that sends every
token to one expert. Read beside ``moe_load_max_over_mean``: it says
whether an uneven load on the held experts is the router's doing."""


def read(ctx):
    win = ctx["window"]
    a, b = win.at_open or {}, win.at_close or {}
    steps = b.get("steps", 0) - a.get("steps", 0)
    if "moe_balance" not in b or steps <= 0:
        return None
    return (b["moe_balance"] - a.get("moe_balance", 0.0)) / steps
