"""What a pass of the flash attention kernels costs on the chip, by kernel
and by what the score tile does (TPU only; `chiprun -- python3
tools/flash_tile_times.py [--window W] [--kv-heads N] [--batch B --heads
H --seq T --dqk D --dv D] [variant ...]`).

At the JoyAI cell's shape by default (batch x heads 2 x 32, 4,096
positions, q/k 192, v 128, bf16, 512 x 512 tiles, causal, a key mask of
ones) it times the forward, the dK/dV and the dQ kernel and the fused
backward kernel (dK, dV and dQ from one visit of a tile) alone, each
jitted by itself on the same operands, as `ops/attention.py` has them;
`fused_fits` says whether `_flash_backward` would take the fused kernel
at this shape, `fused_equals_split` whether its three results are the
two kernels' bit for bit. `--window` makes the causal pass a band, `--kv-heads` gives K and V fewer heads than q (the
Laguna cell's sliding layers: `--batch 1 --heads 64 --kv-heads 8 --seq
8192 --dqk 128 --window 512`; its full layers: `--heads 48` and no
window). A variant is one of

- a name of `VARIANTS`: parts of the tile's vector work taken out by
  patching the module's tile functions. These compute WRONG outputs:
  ceilings that say what a part costs, not candidates (PR 30: none of
  them moves a pass);
- `dkv_outside`: dK/dV per QUERY head (K and V repeated to the query
  heads beforehand, outside the timing; the kernel reads the same bytes)
  and summed over each group outside the kernel, against `as_is`'s sweep
  over the group inside it (PR 31's choice: PERF.md section 5);
- `shape:<block_q>x<block_k>`: the kernels as they are at other blocks;
  `shape:<bq>x<bk>:full` without `causal`, every pair of the grid a tile
  (a causal pass over a whole pass says what the grid's walk costs
  beside its tiles).

No variant: all of `VARIANTS`. One JSON object a line on standard output
and in `chiprun_out/flash_tile_times.jsonl`; `ms` is the median of
`REPEATS` host-clock timings of `CALLS` calls (XLA's copies of the
operands into the kernels' layout, about 0.7 ms, are in it; the
backward kernels take their statistics operand ready-made, `beside_ms`
is what making it costs a backward pass), `layers6_ms` six layers of
it, `us_a_tile` over the tiles the forward grid walks at 512 x 512
(2,304 at the default shape).
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

from paddle_tpu.ops import attention as A  # noqa: E402

B, N, T, DQK, DV, BLOCK = 2, 32, 4096, 192, 128, 512
NKV, WINDOW = N, None       # --kv-heads, --window
CALLS, REPEATS = 10, 5


def _product(q, k):
    return lax.dot_general(q, k, A._TRANS_B,
                           preferred_element_type=jnp.float32)


def _iotas(q, k, qb, kb, off):
    Bq, Bk = q.shape[0], k.shape[0]
    qi = qb * Bq + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 0) + off
    kj = kb * Bk + lax.broadcasted_iota(jnp.int32, (Bq, Bk), 1)
    return qi, kj


def _scores_variant(scale_on, key_on, causal_on):
    def scores(tiles, scale, q, k, msk, qb, kb):
        s = _product(q, k)
        if scale_on:
            s = s * scale
        if key_on:
            s = jnp.where(msk > 0, s, A._NEG)
        if causal_on and tiles.causal:
            qi, kj = _iotas(q, k, qb, kb, tiles.off)
            s = jnp.where(kj <= qi, s, A._NEG)
            if tiles.window is not None:
                s = jnp.where(qi - kj < tiles.window, s, A._NEG)
        return s
    return scores


def _terms_variant(ds_scale, exp_on):
    def terms(tiles, scale, q_ref, k_ref, v_ref, mask_ref, do_ref, st_ref,
              qb, kb):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = st_ref[0]
        s = A._scores(tiles, scale, q, k, mask_ref[0], qb, kb)
        p = s - st[:, 0:1]
        if exp_on:
            p = jnp.exp(p)
        dp = _product(do, v)
        ds = p * (dp - st[:, 1:2])
        if ds_scale:
            ds = ds * scale
        return q, k, do, p, ds
    return terms


# name -> (scores patch, tile-terms patch); None leaves the module's own
VARIANTS = {
    "as_is": (None, None),
    "bare": (_scores_variant(False, False, False),
             _terms_variant(False, True)),
    "no_key_mask": (_scores_variant(True, False, True), None),
    "no_causal_mask": (_scores_variant(True, True, False), None),
    "no_masks": (_scores_variant(True, False, False), None),
    "no_scale": (_scores_variant(False, True, True),
                 _terms_variant(False, True)),
    "bare_no_exp": (_scores_variant(False, False, False),
                    _terms_variant(False, False)),
    "dkv_outside": (None, None),
}


def _operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 5)

    def rnd(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(
            jnp.bfloat16)

    q = rnd(keys[0], B * N, T, DQK)
    k = rnd(keys[1], B * NKV, T, DQK)
    v = rnd(keys[2], B * NKV, T, DV)
    do = rnd(keys[3], B * N, T, DV)
    mask = jnp.ones((B, 1, T), jnp.float32)
    return q, k, v, do, mask


def _time(fn, *args):
    jax.block_until_ready(fn(*args))           # compile, warm
    out = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            res = fn(*args)
        jax.block_until_ready(res)
        out.append(1e3 * (time.perf_counter() - t0) / CALLS)
    return statistics.median(out)


def measure(name, bq=BLOCK, bk=BLOCK, causal=True):
    scores, terms = VARIANTS[name]
    own = A._scores, A._tile_terms
    if scores is not None:
        A._scores = scores
    if terms is not None:
        A._tile_terms = terms
    try:
        group = N // NKV
        window = WINDOW if causal else None
        cfg = (N, 0, DQK ** -0.5, causal, bq, bk, window, group)
        q, k, v, do, mask = _operands()
        fwd = jax.jit(lambda *a: A._flash_forward(cfg, *a))
        out, lse = jax.block_until_ready(fwd(q, k, v, mask))
        if name == "dkv_outside":
            # a query head a grid row, its group's K and V beside it;
            # the group's dK and dV summed by XLA afterwards
            one = cfg[:-1] + (1,)
            rep = [jnp.repeat(x.reshape(B, NKV, T, -1), group, axis=1)
                   .reshape(B * N, T, -1) for x in (k, v)]

            def outside(q, k, v, *rest):
                return [g.reshape(B, NKV, group, T, -1).sum(axis=2)
                        for g in A._backward_split(one, q, k, v, *rest)[1:]]

            return {"dkv": _time(jax.jit(outside), q, *rep, mask, do,
                                 jax.jit(A._backward_stats)(out, lse, do))}
        # one of the two kernels each: the other's call is dead code
        dkv = jax.jit(lambda *a: A._backward_split(cfg, *a)[1:])
        dq = jax.jit(lambda *a: A._backward_split(cfg, *a)[0])
        args = (q, k, v, mask, do, jax.jit(A._backward_stats)(out, lse, do))
        times = {"fwd": _time(fwd, q, k, v, mask),
                 "dkv": _time(dkv, *args), "dq": _time(dq, *args)}
        # the one kernel that makes all three, whether or not the entry
        # would take it at this shape (`fused_fits`; Mosaic may refuse)
        walk, times["fused_fits"] = A._fused_walk(cfg, q, k, v)
        fused = jax.jit(lambda *a: A._backward_fused(walk, cfg[2], *a))
        try:
            times["fused"] = _time(fused, *args)
            same = [bool(jnp.array_equal(a, b)) for a, b in zip(
                fused(*args), jax.jit(
                    lambda *a: A._backward_split(cfg, *a))(*args))]
            times["fused_equals_split"] = all(same)
        except Exception as e:  # noqa: BLE001 - Mosaic's refusal
            times["fused_error"] = str(e)[-400:]
    finally:
        A._scores, A._tile_terms = own
    return times


def beside():
    """The backward's work beside its kernels (delta and the statistics'
    128-lane buffer): once a backward pass, in none of the timings
    above."""
    q, k, v, do, mask = _operands()
    lse = jnp.zeros((B * N, T), jnp.float32)
    return _time(jax.jit(A._backward_stats), do, lse, do)


def main(argv) -> int:
    global B, N, NKV, T, DQK, DV, WINDOW
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("variants", nargs="*")
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--kv-heads", type=int, default=None)
    ap.add_argument("--batch", type=int, default=B)
    ap.add_argument("--heads", type=int, default=N)
    ap.add_argument("--seq", type=int, default=T)
    ap.add_argument("--dqk", type=int, default=DQK)
    ap.add_argument("--dv", type=int, default=DV)
    args = ap.parse_args(argv)
    B, N, T, DQK, DV = args.batch, args.heads, args.seq, args.dqk, args.dv
    NKV, WINDOW = args.kv_heads or N, args.window
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a chip measurement: JAX has {device}")
    names = args.variants or [v for v in VARIANTS if v != "dkv_outside"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    # 512 x 512 tiles the forward grid walks in one pass, every head
    tiles = A.walked_pairs(T, T, True, WINDOW, BLOCK, BLOCK)[0] \
        // (BLOCK * BLOCK) * B * N
    with open(os.path.join(ROOT, "chiprun_out", "flash_tile_times.jsonl"),
              "a") as f:
        def say(row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        stats_ms = beside()
        say({"device": device.device_kind, "beside_ms": stats_ms,
             "tiles_a_pass": tiles, "batch": B, "heads": N,
             "kv_heads": NKV, "seq": T, "dqk": DQK, "dv": DV,
             "window": WINDOW})
        for name in names:
            if name.startswith("shape:"):       # shape:512x1024[:full]
                dims, *full = name[6:].split(":")
                bq, bk = map(int, dims.split("x"))
                try:
                    got = measure("as_is", bq, bk, causal=not full)
                except Exception as e:  # noqa: BLE001 - Mosaic's refusal
                    say({"variant": name, "error": str(e)[-400:]})
                    continue
            else:
                got = measure(name)
            for kernel, ms in got.items():
                if not isinstance(ms, float):
                    say({"variant": name, kernel: ms})
                    continue
                say({"variant": name, "kernel": kernel, "ms": ms,
                     "layers6_ms": 6 * ms, "us_a_tile": 1e3 * ms / tiles})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
