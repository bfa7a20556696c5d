"""What the grouped products of an expert cell cost on the chip, by call and
by tiling (on a TPU only: `python3 tools/gmm_tile_times.py [CELL ...]
[--sweep]`, a CELL one of mellum2, lfm2, joyai, laguna).

A routed expert layer runs three grouped products (`parallel/moe.py`:
`wg` and `wu` from the model width `d` to the experts' `hidden`, `wd`
back), each as three megablox calls: the forward `gmm`, the backward's
`gmm` with the right operand transposed for the left operand's gradient
(`dlhs`), and `tgmm` for the weights' (`drhs`). At a cell's shape (a
buffer of `rows` rows, `groups` held experts of `group_rows` rows each,
the rest of the buffer past the last group) this times the nine calls
alone (`wu`'s are `wg`'s shapes and take its timings), each jitted by
itself on the same operands, at two tilings:

- `old`: one `(tm, tk, tn)` for all three calls, the forward's by
  divisibility from (1024, 512, 256, 128), as megablox's own custom VJP
  hands it on to its transposes;
- `new`: each call's own, `moe.gmm_tiles` from the call's shape.

`--sweep` adds every tiling at the rule's row tile that `moe._vmem_bytes`
fits into the budget with tiles of 256 or more (or a whole dimension),
for each distinct call. One JSON object a line on standard output and in
`chiprun_out/gmm_tile_times.jsonl`: `ms` is the median of `REPEATS`
host-clock timings of a jitted loop of `CALLS` calls, over `CALLS`; `tflops` the call's 2 x rows x k x n
over the groups' rows (the work the kernel cannot skip) over `ms`. The
last line sums, for each tiling, what a step runs: four expert layers,
each product's forward twice (the expert rule's backward recomputes it)
and its two backward calls once.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from paddle_tpu.parallel import moe  # noqa: E402

# cell -> (buffer rows R, model width d, expert width, held experts,
# rows a held expert under a uniform router); R as `moe._chunk_rows`
CELLS = {
    "mellum2": (32768, 2304, 896, 8, 2048),
    "lfm2": (8192, 2048, 1536, 8, 512),
    "joyai": (4096, 2048, 768, 8, 256),
    "laguna": (4096, 2048, 512, 8, 256),
}
LAYERS, CALLS, REPEATS = 4, 10, 5


def _time(fn, a, b, sizes):
    """ms a call: ``CALLS`` calls in one jitted loop, so that the host's
    dispatch (about 0.2 ms a call) is paid once; each call's group sizes
    depend on the output before it, so that none is hoisted or merged."""
    shape = jax.eval_shape(fn, a, b, sizes)

    @jax.jit
    def loop(a, b, sizes):
        def body(i, prev):
            bump = jnp.isnan(prev.reshape(-1)[0]).astype(sizes.dtype)
            return fn(a, b, sizes + bump)
        return lax.fori_loop(0, CALLS, body,
                             jnp.zeros(shape.shape, shape.dtype))

    jax.block_until_ready(loop(a, b, sizes))           # compile, warm
    out = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(a, b, sizes))
        out.append(1e3 * (time.perf_counter() - t0) / CALLS)
    return statistics.median(out)


def old_tiles(m, k, n):
    """The baseline: the forward's tiling by divisibility, which megablox's
    own custom VJP handed to both backward calls (no longer in
    ``moe``)."""
    def tile(size, cap):
        return next((t for t in (cap, 512, 256, 128)
                     if t <= cap and size % t == 0), size)
    return tile(m, moe._ROW_TILE), tile(k, 1024), tile(n, 1024)


def call_fn(call, tiles, dtype=jnp.bfloat16, interpret=False):
    """The megablox call as `moe._kernel` makes it, at ``tiles``: ``fwd``
    ``(x [m,k], w [G,k,n])``, ``dlhs`` ``(g [m,n], w)``, ``drhs`` ``(x,
    g)``, each with the group sizes last."""
    kernels = moe._megablox()
    if call == "drhs":
        return jax.jit(lambda x, g, sizes: kernels.tgmm(
            x.swapaxes(0, 1), g, sizes, dtype, tiles, interpret=interpret))
    return jax.jit(lambda a, w, sizes: kernels.gmm(
        a, w, sizes, dtype, tiles, transpose_rhs=call == "dlhs",
        interpret=interpret))


def tilings(call, m, k, n, item=2):
    """``{label: (tm, tk, tn)}`` for one product's call: the old tiling as
    the forward's hands it on, the rule's, and with ``sweep`` every one
    that fits."""
    fwd = old_tiles(m, k, n)
    if call == "fwd":
        own = ("gmm", m, k, n)
    elif call == "dlhs":
        own = ("gmm", m, n, k)
    else:
        own = ("tgmm", m, k, n)
    return {"old": fwd, "new": moe.gmm_tiles(*own, item)}, own


def sweep(own, item=2):
    """Every tiling at the rule's ``tm`` whose widths are 256 or more (or
    a whole dimension) and that ``moe._vmem_bytes`` fits the budget."""
    kind, m, k, n = own
    tm = moe.gmm_tiles(kind, m, k, n, item)[0]

    def widths(size):
        return [t for t in moe._widths(size) if t >= 256 or t == size]

    return [(tm, a, b) for a in widths(k) for b in widths(n)
            if moe._vmem_bytes(kind, tm, a, b, item)
            <= moe.common.VMEM_BUDGET_BYTES]


def operands(R, d, h, G, rows):
    keys = jax.random.split(jax.random.PRNGKey(0), 5)

    def rnd(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(
            jnp.bfloat16)

    sizes = jnp.full((G,), rows, jnp.int32)
    return {"x": rnd(keys[0], R, d), "a": rnd(keys[1], R, h),
            "w_up": rnd(keys[2], G, d, h), "w_down": rnd(keys[3], G, h, d),
            "g_up": rnd(keys[4], R, h), "g_down": rnd(keys[0], R, d),
            "sizes": sizes}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*", default=["mellum2"],
                    choices=sorted(CELLS))
    ap.add_argument("--sweep", action="store_true")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a chip measurement: JAX has {device}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gmm_tile_times.jsonl"),
              "a") as f:
        def say(row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for cell in args.cells:
            measure(cell, args.sweep, device, say)
    return 0


def measure(cell, with_sweep, device, say):
    R, d, h, G, rows = CELLS[cell]
    ops = operands(R, d, h, G, rows)
    # product -> (its input, weight, output's cotangent, k, n)
    products = {"wg": ("x", "w_up", "g_up", d, h),
                "wu": ("x", "w_up", "g_up", d, h),
                "wd": ("a", "w_down", "g_down", h, d)}
    step, timed = {}, {}
    say({"device": device.device_kind, "cell": cell, "rows": R, "d": d,
         "hidden": h, "groups": G, "group_rows": rows})
    for name, (lhs, w, g, k, n) in products.items():
        for call in ("fwd", "dlhs", "drhs"):
            labelled, own = tilings(call, R, k, n)
            if with_sweep:
                for t in sweep(own):
                    labelled.setdefault("x".join(map(str, t)), t)
            a, b = {"fwd": (lhs, w), "dlhs": (g, w), "drhs": (lhs, g)}[call]
            flops = 2 * G * rows * k * n
            for label, tiles in labelled.items():
                key = (call, own, tiles)
                if key not in timed:
                    try:
                        timed[key] = _time(call_fn(call, tiles), ops[a],
                                           ops[b], ops["sizes"])
                    except Exception as e:  # noqa: BLE001 - Mosaic's refusal
                        say({"cell": cell, "product": name, "call": call,
                             "label": label, "tiles": tiles,
                             "error": str(e)[-400:]})
                        continue
                ms = timed[key]
                say({"cell": cell, "product": name, "call": call,
                     "label": label, "tiles": tiles, "ms": ms,
                     "tflops": flops / ms / 1e9})
                if label in ("old", "new"):
                    step[label] = step.get(label, 0.0) + LAYERS * ms * (
                        2 if call == "fwd" else 1)
    say({"cell": cell, "step_ms": step, "layers": LAYERS})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
