"""Canonical workload mixes for the self-tuning loop.

The committed ``WORKLOAD_r21_*.json`` traces pin the REQUEST stream;
this module pins the fleet they were recorded against.
``tests/test_workload_replay.py`` (which replays the committed traces
and asserts the determinism contract) builds its engines HERE, so a
drifted model or knob default shows up as a test failure, not as a
silently unreplayable artifact. Nothing records such a trace any more
(ROADMAP D1b): the module serves the tests only.

Two mixes, chosen to stress different knobs:

- ``short_burst`` — the DIM-8 classifier behind score traffic arriving
  in synchronized bursts: the burst width vs ``queue_depth`` /
  ``batch_timeout_ms`` trade is what the tuner must discover.
- ``convoy`` — a shrunk r10 length-controlled decode model (EOS logit =
  3 * sum(memory), memory boots tanh(2*src): positive src finishes in
  <= 2 steps, a 20% ``-1`` tail never emits EOS and runs the full
  max_length) behind generate traffic — the mostly-short-plus-long-tail
  stream where batch coalescing convoys the short requests.

Both models are deterministic by construction (fixed seeds, fixed
surgery), small enough for the 1-core CPU host, and sized so the
structural outcomes (shed counts, batch occupancy) — not absolute
latencies — carry the comparison.
"""

from __future__ import annotations

from typing import List, Optional

from paddle_tpu.serving.workload import Workload

# shrunk r10 decode-convoy geometry; small enough that warmup compiles
# fit tier-1
CONVOY_V, CONVOY_E, CONVOY_H = 64, 8, 16
CONVOY_K, CONVOY_L, CONVOY_CHUNK = 2, 16, 4

CLASSIFIER_DIM, CLASSIFIER_CLASSES = 8, 4


# ----------------------------------------------------------- classifier

def classifier_model(seed: int = 0):
    """Tiny dense classifier (the serving-test workhorse shape);
    returns ``(graph, params, feeding)``."""
    import jax
    from paddle_tpu.config import dsl
    from paddle_tpu.core.network import Network
    from paddle_tpu.data import dense_vector, integer_value

    dsl.reset()
    x = dsl.data(name="x", size=CLASSIFIER_DIM)
    lab = dsl.data(name="label", size=CLASSIFIER_CLASSES)
    hid = dsl.fc(input=x, size=12, act="relu", name="hid")
    out = dsl.fc(input=hid, size=CLASSIFIER_CLASSES, act="softmax",
                 name="out")
    dsl.classification_cost(input=out, label=lab, name="cost")
    graph = dsl.current_graph()
    params = Network(graph, outputs=["out"]).init_params(
        jax.random.PRNGKey(seed))
    feeding = {"x": dense_vector(CLASSIFIER_DIM),
               "label": integer_value(CLASSIFIER_CLASSES)}
    return graph, params, feeding


def build_classifier_engine(*, max_batch: int = 2,
                            batch_timeout_ms: float = 4.0,
                            queue_depth: int = 6,
                            warmup: bool = True):
    """The ``short_burst`` serving engine. The DEFAULT knobs are the
    deliberately hand-set ones the bench's A/B measures against: a
    queue narrower than the burst (structural sheds) and a long
    coalescing wait — exactly what ``--autotune``'s grid search is
    expected to fix (queue >= burst, shorter timeout). Menu is
    ``batch_buckets=[1, 2, 4]``, so ``max_batch=8`` is the canonical
    off-menu refusal."""
    from paddle_tpu.serving import ServingEngine, ServingPredictor

    graph, params, feeding = classifier_model()
    pred = ServingPredictor(graph, params, ["out"], feeding,
                            batch_buckets=[1, 2, 4])
    return ServingEngine(pred, max_batch=max_batch,
                         batch_timeout_ms=batch_timeout_ms,
                         queue_depth=queue_depth).start(warmup=warmup)


def short_burst_schedule(n_bursts: int = 4, burst: int = 12,
                         gap_s: float = 0.08) -> List[dict]:
    """Synthetic pacer events: ``n_bursts`` synchronized bursts of
    ``burst`` score requests each. Samples are deterministic (seeded)
    and in-distribution for :func:`classifier_model`."""
    import numpy as np
    rng = np.random.RandomState(0)
    events = []
    for b in range(n_bursts):
        for _ in range(burst):
            vec = (rng.rand(CLASSIFIER_DIM) / CLASSIFIER_DIM).tolist()
            events.append({"t": round(b * gap_s, 6), "kind": "score",
                           "sample": (vec, 1)})
    return events


def short_burst_workload() -> Workload:
    return Workload("short_burst", short_burst_schedule())


# --------------------------------------------------------------- convoy

def convoy_model():
    """The r10 length-controlled decode model, shrunk: boot = 2*eye so
    memory starts at tanh(2*src); ``_prob.w0[:, 1] = 3`` makes the EOS
    logit 3 * sum(memory). ``[1]*H`` sources finish in <= 2 steps,
    ``[-1]*H`` sources never emit EOS and run the full ``CONVOY_L`` —
    margins too fat for cross-batch-width drift to flip a token.
    Returns ``(graph, params, feeding)``."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.config import dsl
    from paddle_tpu.core.network import Network
    from paddle_tpu.core.registry import get_layer_impl
    from paddle_tpu.data import dense_vector

    V, E, H = CONVOY_V, CONVOY_E, CONVOY_H
    dsl.reset()
    src = dsl.data("src", size=H)
    boot = dsl.fc(src, size=H, act="tanh", name="boot", bias_attr=False)

    def step(prev_emb):
        m = dsl.memory(name="h", size=H, boot_layer=boot)
        h = dsl.fc([prev_emb, m], size=H, act="tanh", name="h",
                   bias_attr=False)
        return dsl.fc(h, size=V, act="softmax", name="prob",
                      bias_attr=False)

    dsl.beam_search(
        step, [dsl.GeneratedInput(size=V, embedding_name="gen_emb",
                                  embedding_size=E)],
        bos_id=0, eos_id=1, beam_size=CONVOY_K, max_length=CONVOY_L,
        name="gen")
    graph = dsl.current_graph()
    net = Network(graph, outputs=["boot"])
    params = dict(net.init_params(jax.random.PRNGKey(0)))
    boot_key = next(k for k in params if "boot" in k)
    params[boot_key] = jnp.asarray(2.0 * np.eye(H, dtype=np.float32))
    for _, spec in get_layer_impl("beam_search_group").params(
            graph.layers["gen"], []).items():
        params[spec.absolute_name] = jnp.zeros(spec.shape, jnp.float32)
    params["_h.w1"] = jnp.asarray(np.eye(H, dtype=np.float32))
    u = np.zeros((H, V), np.float32)
    u[:, 1] = 3.0
    params["_prob.w0"] = jnp.asarray(u)
    params["gen_emb"] = jnp.zeros((V, E), jnp.float32)
    return graph, params, {"src": dense_vector(H)}


def build_convoy_engine(*, max_batch: int = 4,
                        batch_timeout_ms: float = 8.0,
                        queue_depth: int = 4,
                        continuous_batching: bool = True,
                        warmup: bool = True):
    """The ``convoy`` serving engine. Defaults again hand-set on the
    slow side (wide coalescing window, queue narrower than the offered
    burst) so the bench's tuned config has structural headroom. Menu is
    ``batch_buckets=[1, 2, 4]``."""
    from paddle_tpu.serving import ServingEngine, ServingPredictor

    graph, params, feeding = convoy_model()
    pred = ServingPredictor(graph, params, ["gen"], feeding,
                            batch_buckets=[1, 2, 4],
                            gen_decode_chunk=CONVOY_CHUNK)
    return ServingEngine(pred, max_batch=max_batch,
                         batch_timeout_ms=batch_timeout_ms,
                         queue_depth=queue_depth,
                         continuous_batching=continuous_batching,
                         ).start(warmup=warmup)


def convoy_schedule(n: int = 20, long_frac: float = 0.2,
                    spacing_s: float = 0.02,
                    burst: int = 10) -> List[dict]:
    """Synthetic pacer events: generate requests in bursts of ``burst``
    with a deterministic ~``long_frac`` tail of full-length ``[-1]*H``
    convoys interleaved among ``[1]*H`` shorts (seeded, so the SAME
    positions are long on every build)."""
    import numpy as np
    H = CONVOY_H
    rng = np.random.RandomState(7)
    events = []
    for i in range(n):
        is_long = bool(rng.rand() < long_frac)
        sample = ([-1.0] * H,) if is_long else ([1.0] * H,)
        t = (i // burst) * (burst * spacing_s)
        events.append({"t": round(t, 6), "kind": "generate",
                       "sample": sample})
    return events


def convoy_workload() -> Workload:
    return Workload("convoy", convoy_schedule())


# ----------------------------------------------------------------- menu

MIXES = {
    "short_burst": (build_classifier_engine, short_burst_workload),
    "convoy": (build_convoy_engine, convoy_workload),
}


def committed_trace_path(mix: str, root: Optional[str] = None) -> str:
    """Repo-root path of the committed ``WORKLOAD_r21_<mix>.json``."""
    import os
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    return os.path.join(root, f"WORKLOAD_r21_{mix}.json")
