"""The routed experts' grouped products' share of their roofline: the
least time the chip could take for the three grouped matrix products of
every ``*_moe`` layer, forward and backward, of one step
(``counts.moe_experts``) over the device time of every operation under
those layers' inner ``moe_experts`` scope, the forward's recomputation
in the backward pass included. The rows, and how many held experts got
any, are the program's own counts over the window (``moe_rows_mean``
times the experts held, ``moe_experts_active``: a router is not uniform,
and a count at a uniform router's rows read over 100% where few tokens
chose a held expert). Only the experts that got rows are charged their
weights, so a collapsed router cannot push the share over 100% either.
The yardstick rests on the program's counters: a program that miscounts
its rows moves it. The bound is printed on standard error."""

from benchmark.metrics import mla_core_roofline

SCOPE = r"_moe\).*moe_experts"


def read(ctx):
    win = ctx["window"]
    a, b = win.at_open or {}, win.at_close or {}
    steps = b.get("steps", 0) - a.get("steps", 0)
    if "moe_rows_mean" not in b or steps <= 0:
        return None
    m = ctx["cfg"]["model"]["args"]
    held = m.get("experts_held") or m["n_routed_experts"]

    def a_step(key):
        return (b[key] - a.get(key, 0.0)) / steps

    active = a_step("moe_experts_active") if "moe_experts_active" in b \
        else None
    return mla_core_roofline.read(ctx, SCOPE, "moe_experts",
                                  "moe_experts_roofline",
                                  rows=a_step("moe_rows_mean") * held,
                                  active=active)
