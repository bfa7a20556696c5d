"""What a pass of the flash attention kernels costs on the chip, by kernel
and by what the score tile does (TPU only; `chiprun -- python3
tools/flash_tile_times.py [variant ...]`).

At the JoyAI cell's shape (batch x heads 64, 4,096 positions, q/k 192,
v 128, bf16, 512 x 512 tiles, causal, a key mask of ones) it times the
forward, the dK/dV and the dQ kernel alone, each jitted by itself, as
`ops/attention.py` has them. A variant is one of

- a name of `VARIANTS`: parts of the tile's vector work taken out by
  patching the module's tile functions. These compute WRONG outputs:
  ceilings that say what a part costs, not candidates (PR 30: none of
  them moves a pass);
- `shape:<block_q>x<block_k>`: the kernels as they are at other blocks;
  `shape:<bq>x<bk>:full` without `causal`, every pair of the grid a tile
  (a causal pass over a whole pass says what the grid's walk costs
  beside its tiles).

No variant: all of `VARIANTS`. One JSON object a line on standard output
and in `chiprun_out/flash_tile_times.jsonl`; `ms` is the median of
`REPEATS` host-clock timings of `CALLS` calls (XLA's copies of the
operands into the kernels' layout, about 0.7 ms, are in it, and the
backward kernels' are less `beside_ms`, the statistics operand's
making), `layers6_ms` six layers of it, `us_a_tile` over the causal
512 x 512 grid's 2,304 visible tiles.
"""

from __future__ import annotations

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
    def scores(off, scale, causal, q, k, msk, qb, kb):
        s = _product(q, k)
        if scale_on:
            s = s * scale
        if key_on:
            s = jnp.where(msk > 0, s, A._NEG)
        if causal_on and causal:
            qi, kj = _iotas(q, k, qb, kb, off)
            s = jnp.where(kj <= qi, s, A._NEG)
        return s
    return scores


def _terms_variant(ds_scale, exp_on):
    def terms(off, scale, causal, q_ref, k_ref, v_ref, mask_ref, do_ref,
              st_ref, qb, kb):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = st_ref[0]
        s = A._scores(off, scale, causal, q, k, mask_ref[0], qb, kb)
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
}


def _operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 5)

    def rnd(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(
            jnp.bfloat16)

    q = rnd(keys[0], B * N, T, DQK)
    k = rnd(keys[1], B * N, T, DQK)
    v = rnd(keys[2], B * N, T, DV)
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
        cfg = (N, 0, DQK ** -0.5, causal, bq, bk)
        q, k, v, do, mask = _operands()
        fwd = jax.jit(lambda *a: A._flash_forward(cfg, *a))
        out, lse = jax.block_until_ready(fwd(q, k, v, mask))
        # one backward kernel each: the other's call is dead code
        dkv = jax.jit(lambda *a: A._flash_backward(cfg, *a)[1:])
        dq = jax.jit(lambda *a: A._flash_backward(cfg, *a)[0])
        res = (q, k, v, mask, out, lse, do)
        times = {"fwd": _time(fwd, q, k, v, mask),
                 "dkv": _time(dkv, *res), "dq": _time(dq, *res)}
    finally:
        A._scores, A._tile_terms = own
    return times


def beside():
    """The backward's work beside the kernels (delta and the statistics'
    128-lane buffer), which both backward timings above hold."""
    q, k, v, do, mask = _operands()

    def stats(out, lse, do):
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        return jnp.pad(jnp.stack([lse, delta], axis=-1),
                       ((0, 0), (0, 0), (0, A._STAT_LANES - 2)))

    lse = jnp.zeros((B * N, T), jnp.float32)
    return _time(jax.jit(stats), do, lse, do)


def main(argv) -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a chip measurement: JAX has {device}")
    names = argv or list(VARIANTS)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    # visible 512 x 512 tiles of one pass: 36 a head, 64 heads
    tiles = (T // BLOCK) * (T // BLOCK + 1) // 2 * B * N
    with open(os.path.join(ROOT, "chiprun_out", "flash_tile_times.jsonl"),
              "a") as f:
        def say(row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        stats_ms = beside()
        say({"device": device.device_kind, "beside_ms": stats_ms,
             "tiles_a_pass": tiles})
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
                if kernel != "fwd":
                    ms -= stats_ms
                say({"variant": name, "kernel": kernel, "ms": ms,
                     "layers6_ms": 6 * ms, "us_a_tile": 1e3 * ms / tiles})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
