"""Device time a step of a kept trace by part of a layer and direction.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds 30 \\
        --trace 1 --keep-trace FILE
    python3 tools/trace_layer_split.py FILE

For each kind of layer that opens inner scopes (``*_attn``, ``*_swa``,
``*_sconv``, ``*_moe``: ``docs/observability.md``) one JSON object a part: the
milliseconds a step under the part's scope, ``fwd | again | bwd``
(``again``: the forward run a second time in the backward pass, which
``jax.checkpoint`` names ``rematted_computation``), then the layer
whole and ``no part``: the time under the layer's scope that no part
covers (a sum of cotangents that autodiff names by the layer alone, and
what a ``while`` spends between its body's operations: the event of the
expert layer's loop over chunks encloses them). Every figure is a union
of intervals on device 0, as the benchmark's readers take them
(``benchmark/metrics/scope_ms.py``), so the parts and ``no part`` add
up to the layer. A step is what most instructions ran: their count of
events. ``tools/trace_scope_ops.py FILE
<part> shapes`` lists one part by operation.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ATTENTION = ("attn_qkv", "attn_qk_norm", "attn_rope", "mla_core",
             "attn_core", "attn_out")
KINDS = {"attn": ATTENTION, "swa": ATTENTION,
         "sconv": ("sconv_in", "sconv_core", "sconv_out"),
         "moe": ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
                 "moe_shared", "moe_balance")}
AGAIN = "rematted_computation"


def way(scope: str) -> str:
    if AGAIN in scope:
        return "again"
    return "bwd" if "transpose(jvp(" in scope else "fwd"


def rows(device, steps: int):
    """The table's rows for one device's operations
    (``trace_reduce.Device``) over ``steps`` steps."""
    from benchmark import trace_reduce

    def ms(intervals) -> float:
        return 1e3 * trace_reduce.total(intervals) / steps

    for kind, parts in KINDS.items():
        layer = re.compile(rf"jvp\(\w+_{kind}\)")
        ops = [o for o in device.ops if layer.search(o.scope)]
        if not ops:
            continue

        def row(part, chosen):
            return {"layer": kind, "part": part,
                    **{d: ms((o.start, o.end) for o in chosen
                             if way(o.scope) == d)
                       for d in ("fwd", "again", "bwd")},
                    "ms_a_step": ms((o.start, o.end) for o in chosen)}

        covered = []
        for part in parts:
            rx = re.compile(rf"\b{part}\b")
            chosen = [o for o in ops if rx.search(o.scope)]
            if chosen:
                yield row(part, chosen)
                covered += [(o.start, o.end) for o in chosen]
        yield row("the layer", ops)
        yield {"layer": kind, "part": "no part", "ms_a_step": ms(
            trace_reduce.subtract([(o.start, o.end) for o in ops], covered))}


def main(argv) -> int:
    from benchmark import trace_reduce

    path = argv[0]
    with open(path + ".scopes.json") as f:
        scopes = json.load(f)
    device = trace_reduce.reduce_file(path, chips=1, scopes=scopes).devices[0]
    runs = collections.Counter(o.name for o in device.ops)
    steps = collections.Counter(runs.values()).most_common(1)[0][0]
    print(json.dumps({"steps": steps}))
    for row in rows(device, steps):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
