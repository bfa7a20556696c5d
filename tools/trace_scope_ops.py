r"""Device time a step under one scope of a kept trace, by operation.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds 30 \\
        --trace 1 --keep-trace FILE
    python3 tools/trace_scope_ops.py FILE mla_core
    python3 tools/trace_scope_ops.py FILE '^(?!.*jvp\()' shapes

``FILE`` is the run's ``.xplane.pb`` and ``FILE.scopes.json`` the
compiled step's ``{instruction: op_name}`` beside it. Every operation
whose ``op_name`` path matches the pattern is summed under its
direction (``fwd`` / ``bwd``, from ``jvp`` / ``transpose(jvp``; ``again``
for a ``recompute`` layer's forward run a second time in the backward
pass, ``rematted_computation``), its
opcode and, for a custom call, its result's type: that tells the
attention core's kernels apart (forward ``(bf16[..,Dv],
f32[..,128])``; the one backward kernel ``(bf16[..,Dqk], bf16[..,Dv],
bf16[..,Dqk])``, or where that does not fit VMEM dK/dV ``(bf16[..,Dqk],
bf16[..,Dv])`` and dQ ``bf16[..,Dqk]``). A third word, ``shapes``,
keys every operation by its result's type, and the second pattern above takes what carries no
layer (the optimizer's update, casts, metrics): that is how PR 32 found
the update's relayouts. A step is what most instructions ran: their
count of events. One JSON object a line, the dearest first.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EVENT = re.compile(r"^%?([\w.\-]+) = (.*?) ([\w\-]+)\(")


def main(argv) -> int:
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    path, pattern = argv[0], re.compile(argv[1])
    shapes = argv[2:] == ["shapes"]
    with open(path + ".scopes.json") as f:
        scopes = json.load(f)
    plane = min((p for p in ProfileData.from_file(path).planes
                 if trace_reduce.DEVICE_PLANE.match(p.name)),
                key=lambda p: p.name)
    runs = collections.Counter()        # instruction -> events
    ns = collections.Counter()          # key -> nanoseconds
    for line in plane.lines:
        if line.name != trace_reduce.OPS_LINE:
            continue
        for ev in line.events:
            m = EVENT.match(ev.name)
            if m is None:
                continue
            name, result, opcode = m.groups()
            scope = scopes.get(name, "")
            runs[name] += 1
            if opcode == "while" or not pattern.search(scope):
                continue
            way = ("again" if "rematted_computation" in scope else
                   trace_reduce.scope_label(scope).rsplit(".", 1)[-1])
            kind = (f"{opcode} -> {re.sub(r'{[^{}]*}', '', result)}"
                    if shapes or opcode == "custom-call" else opcode)
            ns[f"{way} {kind}"] += ev.duration_ns
    steps = collections.Counter(runs.values()).most_common(1)[0][0]
    for key, total in ns.most_common():
        print(json.dumps({"op": key, "ms_a_step": 1e-6 * total / steps}))
    print(json.dumps({"op": "all under the pattern", "steps": steps,
                      "ms_a_step": 1e-6 * sum(ns.values()) / steps}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
