"""Chunked early-exit beam decode (``core/generation.py``): the
``lax.while_loop``-over-scan-chunks search must be byte-identical to the
single length-L full scan for EVERY beam-control hook and for greedy
(K=1), must actually exit early (decode cost proportional to actual
output length), and must keep its compiled-variant cache bounded.

The parity matrix is closure-enforced: the hook axis is derived from the
engine's own hook-name tuple, so adding a fifth beam-control hook without
a matrix row fails the closure test, not silently ships unverified."""

import inspect

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.generation import (DEFAULT_DECODE_CHUNK, _HOOK_NAMES,
                                        SequenceGenerator)
from tests.test_generation_callbacks import (EOS, H, K, L, V, _boost_eos,
                                             _build, _drop_token,
                                             _min_len_4, _outer, _params,
                                             _stop_after_2)

# one matrix row per hook kind (+ the hookless row); norm_or_drop rides
# with candidate_adjust so endings exist for it to veto — matching the
# construction test_generation_callbacks uses
HOOK_MATRIX = {
    None: {},
    "candidate_adjust": {"candidate_adjust": _boost_eos},
    "drop_callback": {"drop_callback": _drop_token(2)},
    "norm_or_drop": {"candidate_adjust": _boost_eos,
                     "norm_or_drop": _min_len_4},
    "stop_beam_search": {"stop_beam_search": _stop_after_2},
}


def test_hook_matrix_is_closed():
    """Every beam-control hook the engine supports has a parity row, and
    every hook kwarg of ``generate`` is one the matrix knows — a new hook
    must land with a chunked-parity row."""
    assert set(_HOOK_NAMES) == {k for k in HOOK_MATRIX if k is not None}
    sig = inspect.signature(SequenceGenerator.generate)
    hook_params = {n for n in sig.parameters if n in _HOOK_NAMES
                   or n.endswith(("_adjust", "_callback", "_search"))
                   or n == "norm_or_drop"}
    assert hook_params == set(_HOOK_NAMES)


@pytest.fixture(scope="module")
def model():
    graph = _build()
    net, params = _params(graph)
    outer = _outer(net, params, B=3)
    return graph, params, outer


@pytest.mark.parametrize("hook_kind", list(HOOK_MATRIX))
@pytest.mark.parametrize("beam", [1, K])
def test_chunked_byte_identical_to_full_scan(model, hook_kind, beam):
    """For every hook kind and for greedy (K=1, the gather-skipping fast
    path): tokens, scores, AND lengths byte-identical across full scan
    and chunk sizes that divide, exceed-in-one, and straddle L."""
    graph, params, outer = model
    hooks = HOOK_MATRIX[hook_kind]
    gen = SequenceGenerator(graph, "gen")
    full = [np.asarray(x) for x in gen.generate(
        params, outer, beam_size=beam, full_scan=True, **hooks)]
    assert gen.last_info["decode_steps"] == L
    for chunk in (3, 5, L):
        got = [np.asarray(x) for x in gen.generate(
            params, outer, beam_size=beam, decode_chunk=chunk, **hooks)]
        for name, a, b in zip(("tokens", "scores", "lengths"), full, got):
            assert np.array_equal(a, b), (hook_kind, beam, chunk, name)
        info = gen.last_info
        assert info["decode_steps"] + info["steps_saved"] == L
        assert info["decode_chunk"] == chunk


def test_early_exit_saves_steps(model):
    """A workload whose beams all finish early must pay ceil(finish/C)*C
    steps, not L — the whole point of the chunked restructure."""
    graph, params, outer = model
    gen = SequenceGenerator(graph, "gen")
    # _boost_eos ends every beam at step 0 (EOS dominates immediately)
    gen.generate(params, outer, decode_chunk=3,
                 candidate_adjust=_boost_eos)
    assert gen.last_info["decode_steps"] == 3  # one chunk, not L=8
    assert gen.last_info["steps_saved"] == L - 3
    # stop_beam_search freezes at t=2 -> exit at the next boundary
    gen.generate(params, outer, decode_chunk=3,
                 stop_beam_search=_stop_after_2)
    assert gen.last_info["decode_steps"] == 3


def test_unfinished_beams_run_the_full_length(model):
    """No early exit without finished beams: the chunked search must not
    cut a live search short."""
    graph, params, outer = model
    gen = SequenceGenerator(graph, "gen")
    tokens, _, lengths = gen.generate(params, outer, decode_chunk=3)
    if (np.asarray(lengths) >= L).any():
        assert gen.last_info["decode_steps"] == L


def test_jit_cache_is_lru_bounded():
    """Per-call hook lambdas mint a fresh (beam, length, chunk, hooks)
    key every generate; the cache must evict, not leak compiled
    executables (regression for the unbounded ``_jitted`` dict)."""
    graph = _build()
    net, params = _params(graph)
    outer = _outer(net, params, B=2)
    gen = SequenceGenerator(graph, "gen")
    cap = SequenceGenerator._JIT_CACHE_CAP
    for i in range(cap + 9):
        # a fresh closure each call = a fresh cache key each call
        gen.generate(params, outer, max_length=3,
                     candidate_adjust=lambda logp, state, _i=i: logp)
        assert len(gen._jitted) <= cap
    assert len(gen._jitted) == cap
    assert gen._evict_warned
    # stable keys (module-level hooks / no hooks) still reuse: repeated
    # identical calls do not grow the cache at all
    n = len(gen._jitted)
    for _ in range(3):
        gen.generate(params, outer, max_length=3)
    assert len(gen._jitted) <= max(n, cap)


def test_config_pinned_decode_policy():
    """``dsl.beam_search(decode_chunk=, full_scan=)`` pin the decode
    policy for every generate call on the config — and per-call args
    still override."""
    from paddle_tpu.config import dsl  # noqa: F401 — via _build kwargs
    graph = _build(decode_chunk=3)
    net, params = _params(graph)
    outer = _outer(net, params, B=2)
    gen = SequenceGenerator(graph, "gen")
    gen.generate(params, outer, candidate_adjust=_boost_eos)
    assert gen.last_info["decode_chunk"] == 3
    assert gen.last_info["decode_steps"] == 3  # early exit honored
    gen.generate(params, outer, full_scan=True)
    assert gen.last_info["full_scan"]
    graph2 = _build(full_scan=True)
    gen2 = SequenceGenerator(graph2, "gen")
    gen2.generate(params, outer)
    assert gen2.last_info["full_scan"]
    gen2.generate(params, outer, decode_chunk=4, full_scan=False)
    assert gen2.last_info["decode_chunk"] == 4


def test_session_matches_dedicated_search_with_staggered_admission():
    """DecodeSession lanes are independent: a request admitted mid-flight
    (neighbors deep into their outputs) decodes byte-identically to the
    dedicated chunked search over the same width."""
    graph = _build()
    net, params = _params(graph)
    outer = _outer(net, params, B=4, seed=11)
    gen = SequenceGenerator(graph, "gen")
    sess = gen.session(params, width=4, decode_chunk=2)
    sess.admit(0, outer, row=0)
    sess.admit(1, outer, row=1)
    results = {}
    admitted = 2
    while sess.active_lanes():
        sess.run_chunk()
        if admitted < 4:  # staggered, mid-flight admissions
            sess.admit(admitted, outer, row=admitted)
            admitted += 1
        for lane in sess.finished_lanes():
            results[lane] = sess.peek(lane)
            sess.release(lane)
    ref = [np.asarray(x) for x in gen.generate(params, outer,
                                               decode_chunk=2)]
    for lane in range(4):
        tokens, scores, lengths, steps = results[lane]
        assert np.array_equal(tokens, ref[0][lane]), lane
        assert np.array_equal(scores, ref[1][lane]), lane
        assert np.array_equal(lengths, ref[2][lane]), lane
        assert 0 < steps <= L


def _build_cell_decoder(cell):
    """Beam-search config whose step net runs a real recurrent cell."""
    from paddle_tpu.config import dsl
    dsl.reset()
    src = dsl.data("src", size=H)
    boot = dsl.fc(src, size=H, act="tanh", name="boot", bias_attr=False)

    if cell == "gru":
        def step(prev_emb):
            m = dsl.memory(name="g", size=H, boot_layer=boot)
            x = dsl.fc(prev_emb, size=3 * H, act="linear", name="xg",
                       bias_attr=False)
            g = dsl.gru_step_layer(x, m, name="g")
            return dsl.fc(g, size=V, act="softmax", name="prob",
                          bias_attr=False)
    else:
        def step(prev_emb):
            out_m = dsl.memory(name="h", size=H, boot_layer=boot)
            c_m = dsl.memory(name="cst", size=H)
            gates = dsl.fc([prev_emb, out_m], size=4 * H, act="linear",
                           name="gates", bias_attr=False)
            h = dsl.lstm_step_layer(gates, c_m, name="h")
            dsl.get_output_layer(h, arg_name="state", size=H, name="cst")
            return dsl.fc(h, size=V, act="softmax", name="prob",
                          bias_attr=False)

    dsl.beam_search(
        step,
        [dsl.GeneratedInput(size=V, embedding_name="gen_emb",
                            embedding_size=4)],
        bos_id=0, eos_id=EOS, beam_size=K, max_length=L, name="gen")
    return dsl.current_graph()


@pytest.mark.parametrize("cell", ["gru", "lstm"])
def test_recurrent_cell_decoders_chunked_byte_identical(cell):
    """The decode loop over a real recurrent cell (``gru_step``;
    ``lstm_step`` with its state carried through a second memory):
    chunked search byte-identical to the full scan, one compiled program
    per compile key and none for a repeated call."""
    graph = _build_cell_decoder(cell)
    net, params = _params(graph)
    outer = _outer(net, params, B=3)
    gen = SequenceGenerator(graph, "gen")
    full = [np.asarray(x) for x in gen.generate(params, outer, beam_size=K,
                                                full_scan=True)]
    chunked = [np.asarray(x) for x in gen.generate(params, outer,
                                                   beam_size=K,
                                                   decode_chunk=3)]
    n = len(gen._jitted)
    assert n == 2
    again = [np.asarray(x) for x in gen.generate(params, outer, beam_size=K,
                                                 decode_chunk=3)]
    assert len(gen._jitted) == n
    for name, a, b, c in zip(("tokens", "scores", "lengths"), full, chunked,
                             again):
        assert np.array_equal(a, b), (cell, name)
        assert np.array_equal(a, c), (cell, name)
