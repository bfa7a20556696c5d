"""Every Pallas kernel, compiled on the chip, against its reference.

Runs only on a TPU backend, with dispatch following the platform (no
forced kernel mode), and rewrites ``TPU_EVIDENCE.json`` at the repo
root. For every kernel file under ``paddle_tpu/ops`` it records one or
more *cases* — the public entry called at one shape and dtype:

- which path dispatch took (``ops/common.py:record_dispatch``) and how
  many Mosaic custom calls the compiled forward holds — ``compiled`` is
  true only when the program really went through Mosaic;
- forward and gradient parity against the same entry under
  ``force_mode("ref")`` (the reference's CPU-stub-vs-GPU-kernel
  equivalence tests, `paddle/math/tests/test_matrixCompare.cpp`, at TPU
  granularity);
- for the hand-written VJPs, a numeric-vs-analytic directional
  derivative taken on the chip (`Trainer::checkGradient`,
  `paddle/trainer/Trainer.cpp:299`).

A case whose ``expect`` is ``"ref"`` documents a shape dispatch keeps
away from Mosaic (alignment or VMEM narrowing): it must take the
reference path, and says so in the record. A case that raises is
recorded with its error and fails the run — a refusal is evidence too,
and ``PERF.md`` records what was done about each. Timing is not taken
here.

Usage: ``python tools/tpu_evidence.py [--out PATH]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from paddle_tpu.ops import common  # noqa: E402
from paddle_tpu.utils import runtime  # noqa: E402

FWD_TOL, GRAD_TOL = 2e-2, 5e-2
# bf16 storage: the kernels do their gate math in f32, the scan
# references round every intermediate to bf16 (8 mantissa bits)
BF16_TOLS = {"fwd_tol": 5e-2, "grad_tol": 1e-1}


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-8))


def _first(out):
    return out[0] if isinstance(out, (tuple, list)) else out


def _run(fn, args, grad_argnums, mode):
    """Forward, gradients, dispatch tally and Mosaic-call count of
    ``fn(*args)`` under ``mode`` (None = what the platform selects)."""
    # jit's trace cache is keyed on the function object: without a clear
    # the second mode would re-use the first mode's lowering and the
    # comparison would compare a kernel to itself
    jax.clear_caches()
    with common.force_mode(mode), common.record_dispatch() as tally:
        fwd = jax.jit(fn).lower(*args).compile()
        n_mosaic = fwd.as_text().count(
            'custom_call_target="tpu_custom_call"')
        out = jax.device_get(_first(fwd(*args)))
        grads = None
        if grad_argnums:
            loss = lambda *a: jnp.sum(  # noqa: E731
                _first(fn(*a)).astype(jnp.float32) ** 2)
            grads = jax.device_get(
                jax.jit(jax.grad(loss, argnums=grad_argnums))(*args))
    return out, grads, tally, n_mosaic


def case(report, name, file, fn, args, grad_argnums=(), *, expect="pallas",
         shape="", fwd_tol=FWD_TOL, grad_tol=GRAD_TOL):
    entry = {"file": file, "shape": shape, "expect": expect}
    report["cases"][name] = entry
    try:
        out, grads, tally, n_mosaic = _run(fn, args, grad_argnums, None)
        ref_out, ref_grads, _, ref_mosaic = _run(fn, args, grad_argnums,
                                                 "ref")
        entry["dispatch"] = tally
        entry["tpu_custom_calls"] = n_mosaic
        entry["compiled"] = n_mosaic > 0 and ref_mosaic == 0
        entry["fwd_rel_err_vs_ref"] = round(_rel(out, ref_out), 8)
        ok = entry["fwd_rel_err_vs_ref"] < fwd_tol
        if grad_argnums:
            entry["grad_rel_err_vs_ref"] = round(max(
                _rel(a, b) for a, b in zip(grads, ref_grads)), 8)
            ok = ok and entry["grad_rel_err_vs_ref"] < grad_tol
        entry["parity_ok"] = bool(ok)
        # the path taken must be the one this case documents
        entry["ok"] = bool(ok and entry["compiled"] == (expect != "ref"))
    except Exception as e:  # noqa: BLE001 — a refusal is the evidence
        entry["ok"] = False
        entry["error"] = f"{type(e).__name__}: {e}"[:1500]
        traceback.print_exc()
    print(f"{name}: {json.dumps(entry)[:400]}", flush=True)


def checkgrad(report, name, loss_fn, args, eps=1e-3):
    """Directional numeric-vs-analytic derivative on the chip, highest
    matmul precision (the --job=checkgrad contract on device numerics)."""
    entry = {}
    report["checkgrad"][name] = entry
    try:
        with jax.default_matmul_precision("highest"):
            loss = jax.jit(loss_fn)
            g = jax.jit(jax.grad(
                loss_fn, argnums=tuple(range(len(args)))))(*args)
            rng = np.random.RandomState(7)
            dirs = [jnp.asarray(rng.randn(*np.shape(a)).astype(np.float32))
                    for a in args]
            analytic = float(sum(jnp.vdot(gi, di)
                                 for gi, di in zip(g, dirs)))
            plus = loss(*[a + eps * d for a, d in zip(args, dirs)])
            minus = loss(*[a - eps * d for a, d in zip(args, dirs)])
            numeric = float((plus - minus) / (2 * eps))
        rel = abs(analytic - numeric) / (abs(numeric) + 1e-8)
        entry.update(analytic=analytic, numeric=numeric,
                     rel_err=round(rel, 8), ok=bool(rel < 5e-2))
    except Exception as e:  # noqa: BLE001 — a refusal is the evidence
        entry.update(ok=False, error=f"{type(e).__name__}: {e}"[:1500])
        traceback.print_exc()
    print(f"checkgrad[{name}]: {json.dumps(entry)[:300]}", flush=True)


def _lstm_host_f64(xs, w, pI, pF, pO):
    """The LSTM recurrence (``ops/lstm.py`` cell math, zero initial
    state, no padding) in numpy float64 on the host: the arbiter when
    two device spellings disagree."""
    xs, w, pI, pF, pO = (np.asarray(a, np.float64)
                         for a in (xs, w, pI, pF, pO))
    T, B, H4 = xs.shape
    h = c = np.zeros((B, H4 // 4))
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
    ys = []
    for t in range(T):
        a_i, a_ig, a_fg, a_og = np.split(xs[t] + h @ w, 4, axis=-1)
        c = np.tanh(a_i) * sig(a_ig + c * pI) + c * sig(a_fg + c * pF)
        h = sig(a_og + c * pO) * np.tanh(c)
        ys.append(h)
    return np.stack(ys)


def lstm_precision(report, T=100, H=256, early=4):
    """Which side deviates at batch 1? With weights drawn at scale 0.2
    (gates saturate, the recurrence amplifies) the compiled kernel and
    the scan reference agree bitwise at B >= 2 and differ at B = 1,
    late in the sequence by as much as 0.6. The other cases tame the
    weights so that parity means something; this one keeps them and
    asks a float64 host recurrence which device spelling is off: the
    kernel, the reference as XLA compiles it by default, or neither
    once the reference's matmuls run at ``highest`` precision. Errors
    are max |y - y_f64| (outputs lie in [-1, 1]) over the first
    ``early`` steps — before the recurrence amplifies anything — and
    over all ``T``. ``PERF.md`` records the answer (PR 21: the kernel
    is as accurate at B=1 as both spellings are at B=2; it is XLA's
    reference that changes, to full f32, for a matrix-vector product)."""
    from paddle_tpu.ops.lstm import lstm_sequence
    entry = {"shape": f"T{T} H{H} float32, xs and w at scale 0.2",
             "early_steps": early}
    report["precision"] = {"lstm_b1_original_weights": entry}
    rng = np.random.RandomState(1)
    draw = lambda *s, scale=0.2: (  # noqa: E731
        rng.randn(*s).astype(np.float32) * scale)
    w = draw(H, 4 * H)
    pI, pF, pO = (draw(H, scale=0.1) for _ in range(3))
    zb = jnp.zeros((4 * H,), jnp.float32)

    def err(y, y64):
        d = np.abs(np.asarray(y, np.float64) - y64)
        return {"early": float(d[:early].max()), "all": float(d.max())}

    try:
        for B in (1, 2):
            xs = draw(T, B, 4 * H)
            mask = jnp.ones((T, B), jnp.float32)
            z = jnp.zeros((B, H), jnp.float32)
            fn = lambda xs_, w_: lstm_sequence(  # noqa: E731
                xs_, mask, w_, zb, jnp.asarray(pI), jnp.asarray(pF),
                jnp.asarray(pO), z, z)[0]
            args = (jnp.asarray(xs), jnp.asarray(w))
            kernel, _, tally, n_mosaic = _run(fn, args, (), None)
            xla, _, _, _ = _run(fn, args, (), "ref")
            with jax.default_matmul_precision("highest"):
                xla_hi, _, _, _ = _run(fn, args, (), "ref")
            y64 = _lstm_host_f64(xs, w, pI, pF, pO)
            entry[f"B{B}"] = {
                "kernel_dispatch": tally, "tpu_custom_calls": n_mosaic,
                "kernel_vs_f64": err(kernel, y64),
                "xla_default_vs_f64": err(xla, y64),
                "xla_highest_vs_f64": err(xla_hi, y64),
                "kernel_vs_xla_default": err(kernel, np.asarray(
                    xla, np.float64)),
            }
        b1, b2 = entry["B1"], entry["B2"]
        entry["closest_to_f64_at_B1"] = min(
            ("kernel", "xla_default"),
            key=lambda k: b1[f"{k}_vs_f64"]["early"])
        # the kernel at B=1 must be no less accurate than the kernel
        # and the default reference are at B=2, where they agree
        bound = 2 * max(b2["kernel_vs_f64"]["early"],
                        b2["xla_default_vs_f64"]["early"]) + 1e-6
        entry["ok"] = bool(b1["kernel_vs_f64"]["early"] <= bound)
    except Exception as e:  # noqa: BLE001 — a refusal is the evidence
        entry.update(ok=False, error=f"{type(e).__name__}: {e}"[:1500])
        traceback.print_exc()
    print(f"precision[lstm_b1]: {json.dumps(entry)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "TPU_EVIDENCE.json"))
    args = ap.parse_args(argv)
    if common.forced() is not None:
        raise SystemExit(f"tpu_evidence: unset {common.FORCE_ENV} — the "
                         "evidence is about the path the platform selects")
    device = runtime.require_tpu("tpu_evidence")
    import jaxlib
    report = {"device": device, "jax": jax.__version__,
              "jaxlib": jaxlib.__version__,
              "note": "compiled Pallas kernels vs their pure-JAX "
                      "references, dispatch left to the platform",
              "cases": {}, "checkgrad": {}}
    rng = np.random.RandomState(0)

    def arr(*shape, scale=0.2, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale,
                           dtype)

    # ------------------------------------------------------- ops/lstm.py
    from paddle_tpu.ops.lstm import lstm_dispatch, lstm_sequence

    def lstm_case(name, T, B, H, *, dtype=jnp.float32, grad=True,
                  expect="pallas", scale=0.2, wscale=0.2):
        bf16 = jnp.dtype(dtype) == jnp.bfloat16
        # The gate bias is pre-folded into xs for BOTH paths: the Pallas
        # entry folds it before the kernel, the scan reference adds it
        # after the recurrent matmul, and that one add-reorder amplifies
        # through the recurrence. Parity compares one rounding schedule.
        mask = jnp.ones((T, B), jnp.float32)
        xs = arr(T, B, 4 * H, scale=scale, dtype=dtype)
        w = arr(H, 4 * H, scale=wscale, dtype=dtype)
        zb = jnp.zeros((4 * H,), dtype)
        pI, pF, pO = (arr(H, scale=0.1, dtype=dtype) for _ in range(3))
        h0 = c0 = jnp.zeros((B, H), dtype)
        case(report, name, "ops/lstm.py",
             lambda xs_, w_: lstm_sequence(xs_, mask, w_, zb, pI, pF, pO,
                                           h0, c0),
             (xs, w), (0, 1) if grad else (), expect=expect,
             shape=f"T{T} B{B} H{H} {jnp.dtype(dtype).name}",
             **(BF16_TOLS if bf16 else {}))

    # every BASELINE rnn-table shape on the path dispatch gives it (the
    # headline at the real sequence length, the rest short): "ref"
    # shapes are the ones the VMEM count keeps away from Mosaic
    from paddle_tpu.ops.lstm import BENCH_SHAPES
    with common.force_mode("pallas"):
        paths = {bh: lstm_dispatch(*bh) for bh in BENCH_SHAPES}
    for (b, hid), path in paths.items():
        lstm_case(f"lstm_{path}_b{b}_h{hid}",
                  100 if (b, hid) == (64, 256) else 4, b, hid,
                  scale=0.1, wscale=0.05,
                  expect="ref" if path == "ref" else "pallas")
    # either side of the resident/tiled boundary at batch 64
    lstm_case("lstm_resident_b64_h640", 4, 64, 640, scale=0.1, wscale=0.05)
    lstm_case("lstm_tiled_b64_h768", 4, 64, 768, scale=0.1, wscale=0.05)
    lstm_case("lstm_bf16_b64_h256", 16, 64, 256, dtype=jnp.bfloat16,
              scale=0.1, wscale=0.05)
    lstm_case("lstm_bf16_tiled_b64_h1280", 4, 64, 1280,
              dtype=jnp.bfloat16, scale=0.1, wscale=0.05)
    for b in (1, 2, 4):                                     # serving buckets
        lstm_case(f"lstm_infer_b{b}_h256", 100, b, 256, grad=False,
                  scale=0.1, wscale=0.05)
    # H % 128 != 0: lane-unaligned gate slices inside the kernel
    lstm_case("lstm_unaligned_b8_h16", 8, 8, 16)
    lstm_case("lstm_unaligned_b8_h200", 8, 8, 200)

    # -------------------------------------------------------- ops/gru.py
    from paddle_tpu.ops.gru import gru_sequence

    def gru_case(name, T, B, H, *, dtype=jnp.float32, grad=True,
                 expect="pallas"):
        mask = jnp.ones((T, B), jnp.float32)
        xs = arr(T, B, 3 * H, dtype=dtype)
        # small recurrent weights: at 0.2 the gates saturate and the
        # recurrence amplifies summation-order noise past any tolerance
        wg = arr(H, 2 * H, scale=0.05, dtype=dtype)
        ws = arr(H, H, scale=0.05, dtype=dtype)
        bias = arr(3 * H, dtype=dtype)
        h0 = jnp.zeros((B, H), dtype)
        case(report, name, "ops/gru.py",
             lambda xs_, wg_, ws_: gru_sequence(xs_, mask, wg_, ws_, bias,
                                                h0),
             (xs, wg, ws), (0, 1, 2) if grad else (), expect=expect,
             shape=f"T{T} B{B} H{H} {jnp.dtype(dtype).name}",
             **(BF16_TOLS if jnp.dtype(dtype) == jnp.bfloat16 else {}))

    gru_case("gru_b64_h256", 100, 64, 256)
    gru_case("gru_bf16_b64_h256", 16, 64, 256, dtype=jnp.bfloat16)
    gru_case("gru_infer_b4_h256", 100, 4, 256, grad=False)
    gru_case("gru_unaligned_b8_h48", 8, 8, 48)

    # -------------------------------------------------- ops/attention.py
    from paddle_tpu.ops.attention import flash_attention
    q, k, v = (arr(4, 8, 1024, 64) for _ in range(3))
    case(report, "flash_attention_causal", "ops/attention.py",
         lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True),
         (q, k, v), (0, 1, 2), shape="B4 N8 T1024 D64 float32 causal")
    qb, kb, vb = (arr(2, 4, 512, 64, dtype=jnp.bfloat16) for _ in range(3))
    case(report, "flash_attention_bf16", "ops/attention.py",
         lambda q_, k_, v_: flash_attention(q_, k_, v_),
         (qb, kb, vb), (0, 1, 2), shape="B2 N4 T512 D64 bfloat16",
         **BF16_TOLS)

    # -------------------------------------------------------- ops/crf.py
    from paddle_tpu.ops.crf import crf_log_z
    xc = arr(64, 32, 9, scale=1.0)
    maskc = jnp.ones((64, 32), jnp.float32)
    transc, ac, bc = (arr(9, 9, scale=1.0), arr(9, scale=1.0),
                      arr(9, scale=1.0))
    case(report, "crf_log_z", "ops/crf.py",
         lambda x_, t_: crf_log_z(x_, maskc, t_, ac, bc),
         (xc, transc), (0, 1), shape="B64 T32 C9 (padded to 128)")

    # -------------------------------------------------------- ops/ctc.py
    from paddle_tpu.layers.chain import ctc_loss
    lp = jax.nn.log_softmax(arr(32, 40, 12, scale=1.0), axis=-1)
    lab = jnp.asarray(rng.randint(0, 11, size=(32, 8)).astype(np.int32))
    in_m = jnp.ones((32, 40), jnp.float32)
    lab_m = jnp.ones((32, 8), jnp.float32)
    case(report, "ctc_loss", "ops/ctc.py",
         lambda lp_: ctc_loss(lp_, lab, in_m, lab_m, blank=11),
         (lp,), (0,), shape="B32 T40 C12 L8 (S=17 padded to 128)")

    # ------------------------------ on-device checkgrad of the custom VJPs
    t, b, h = 8, 8, 128
    cx, cm = arr(t, b, 4 * h), jnp.ones((t, b), jnp.float32)
    cw, cb = arr(h, 4 * h), arr(4 * h)
    czc = jnp.zeros((h,), jnp.float32)
    ch = cc = jnp.zeros((b, h), jnp.float32)
    checkgrad(report, "lstm_pallas",
              lambda xs_, w_: jnp.sum(lstm_sequence(
                  xs_, cm, w_, cb, czc, czc, czc, ch, cc)[0] ** 2),
              (cx, cw))
    gx, gwg, gws, gb = arr(t, b, 3 * h), arr(h, 2 * h), arr(h, h), arr(3 * h)
    checkgrad(report, "gru_pallas",
              lambda xs_, wg_, ws_: jnp.sum(gru_sequence(
                  xs_, cm, wg_, ws_, gb, ch)[0] ** 2),
              (gx, gwg, gws))
    fq, fk, fv = (arr(2, 2, 256, 64) for _ in range(3))
    checkgrad(report, "flash_attention_pallas",
              lambda q_, k_, v_: jnp.sum(
                  flash_attention(q_, k_, v_, causal=True) ** 2),
              (fq, fk, fv))
    kx = arr(8, 6, 9, scale=1.0)
    kmask = jnp.ones((8, 6), jnp.float32)
    ktr, ka, kb2 = arr(9, 9, scale=1.0), arr(9, scale=1.0), arr(9, scale=1.0)
    checkgrad(report, "crf_pallas",
              lambda x_, t_: jnp.sum(crf_log_z(x_, kmask, t_, ka, kb2) ** 2),
              (kx, ktr))
    clp = arr(8, 12, 6, scale=1.0)
    clab = jnp.asarray(rng.randint(0, 5, size=(8, 3)).astype(np.int32))
    checkgrad(report, "ctc_pallas",
              lambda lp_: jnp.sum(ctc_loss(
                  jax.nn.log_softmax(lp_, axis=-1), clab,
                  jnp.ones((8, 12), jnp.float32),
                  jnp.ones((8, 3), jnp.float32), blank=5)),
              (clp,))

    lstm_precision(report)

    files = sorted({c["file"] for c in report["cases"].values()})
    report["files_covered"] = files
    report["all_cases_ok"] = all(c["ok"] for c in report["cases"].values())
    report["all_checkgrad_ok"] = all(
        c["ok"] for c in report["checkgrad"].values())
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    failed = [n for n, c in report["cases"].items() if not c["ok"]] + \
        [f"checkgrad:{n}" for n, c in report["checkgrad"].items()
         if not c["ok"]] + \
        [f"precision:{n}" for n, c in report["precision"].items()
         if not c["ok"]]
    print(json.dumps({"out": args.out, "cases": len(report["cases"]),
                      "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
