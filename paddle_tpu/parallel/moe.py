"""Mixture of experts: sigmoid routing with a selection bias, top-k of all
the experts, SwiGLU experts, a shared expert, and a layer that is told
which experts it holds.

The 2017 reference has no MoE (SURVEY §2), so this is a capability-add.
The mathematics is DeepSeek-V3's (arXiv:2412.19437 §2.1.2, the form
today's large open models carry in their ``config.json``):

- router: ``s = sigmoid(u W_r)`` over all ``E`` experts in float32; the
  ``k`` chosen are the top ``k`` of ``s + b`` (``b``: a selection bias
  that no gradient trains); their weights are ``s[chosen] /
  sum(s[chosen]) * scale``. The gradient reaches ``W_r`` through the
  weights; the choice itself is piecewise constant. Or (``score=
  "softmax"``, Qwen3-MoE's form) ``p = softmax(u W_r)``, the top ``k`` of
  ``p`` chosen, weights ``p[chosen] / sum(p[chosen])``, with the
  load-balancing term of Switch Transformer (arXiv:2101.03961 §2.2) over
  ``p``: ``balance_sums`` gives a layer's share of it.
- experts: ``E(u) = (silu(u W_g) * (u W_u)) W_d``, no bias; the routed
  part of the result is ``sum_i w_i E_i(u)`` over the chosen experts.
- expert parallelism: a device holds experts ``offset .. offset + held``.
  It routes over all ``E``, computes the sum over the chosen experts it
  holds and leaves the rest out (``routed_experts``). Over a mesh axis
  the partial sums are added (``make_moe``); on one chip that stands for
  one of a group the layer runs without any exchange, and what the
  absent experts would have added is simply not there.
- dispatch: the (token, choice) pairs that name a held expert are sorted
  by expert and gathered ``R`` at a time into an ``[R, d]`` buffer, over
  which the three products run as grouped matrix products
  (``grouped_matmul``: megablox on the TPU, whose grid follows the rows
  really there; ``lax.ragged_dot`` elsewhere). ``R`` is twice what a
  uniform router sends here, so the usual batch is one buffer. **No
  capacity and no drop**: a loop takes as many buffers as the rows need
  (``lax.fori_loop`` with a traced bound), so a batch in which every
  token chooses held experts comes out exact, and both time and memory
  follow the rows really routed here, not the worst case ``T * min(k,
  held)``. The buffers after the first hold at most 8,192 rows. On the
  TPU the rows are moved in and out of a buffer by ``ops/rows.py``'s
  row kernels, which move only the rows it holds; XLA's gather and
  scatter-add (the reference) pay for every row of it. The loop sits inside a
  ``custom_vjp`` whose backward makes the same turns, recomputing each
  buffer from the layer's input (a loop of traced length has no
  transpose of its own).
- scopes: every operation lies under one of ``moe_route``,
  ``moe_dispatch`` (plan, sort, a chunk's gather, the backward rule's sums
  over the chunks), ``moe_experts``, ``moe_combine``, ``moe_shared`` and
  ``moe_balance`` (the router's statistics for the balancing term), so
  that a device trace divides the layer's time (``docs/observability.md``).
"""

from __future__ import annotations

import functools
import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.ops import common
from paddle_tpu.ops import rows as row_ops

_HIGHEST = lax.Precision.HIGHEST
_ROW_TILE = 512     # rows a grouped-product tile holds on the TPU
_SPILL_ROWS = 8192  # the most rows a buffer after the first holds


def init_moe_params(key, d_model: int, d_hidden: int, n_experts: int,
                    d_shared: Optional[int] = None
                    ) -> Dict[str, jnp.ndarray]:
    """Router ``wr``/``br``, routed experts ``wg wu wd`` (stacked, expert
    major), shared expert ``sg su sd`` (``d_shared`` 0: none)."""
    d_shared = d_hidden if d_shared is None else d_shared
    ks = jax.random.split(key, 7)
    s1, s2 = d_model ** -0.5, d_hidden ** -0.5
    out = {
        "wr": jax.random.normal(ks[0], (d_model, n_experts)) * s1,
        "br": jnp.zeros((n_experts,)),
        "wg": jax.random.normal(ks[1], (n_experts, d_model, d_hidden)) * s1,
        "wu": jax.random.normal(ks[2], (n_experts, d_model, d_hidden)) * s1,
        "wd": jax.random.normal(ks[3], (n_experts, d_hidden, d_model)) * s2,
    }
    if d_shared:
        out.update(
            sg=jax.random.normal(ks[4], (d_model, d_shared)) * s1,
            su=jax.random.normal(ks[5], (d_model, d_shared)) * s1,
            sd=jax.random.normal(ks[6], (d_shared, d_model))
            * d_shared ** -0.5)
    return out


def swiglu(x, wg, wu, wd):
    """``(silu(x W_g) * (x W_u)) W_d``: the dense FFN and every expert."""
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def route(x, wr, br, top_k: int, scale: float, norm_eps: float = 0.0,
          score: str = "sigmoid"):
    """``(ids [T,k] int32, weights [T,k] float32, scores [T,E] float32)``:
    scores over all the experts in float32 (the product at ``highest``: a
    TPU's default float32 product rounds its operands to bfloat16), the
    choice and the weights from them. ``score`` ``"sigmoid"``: ``s =
    sigmoid(u W_r)``, the choice by ``s + b``, the weights ``s[chosen]``
    normalised (by their sum, plus ``norm_eps`` where a model publishes
    one) and scaled. ``"softmax"``: ``p = softmax(u W_r)``, the choice by
    ``p`` (no bias), the weights ``p[chosen] / sum(p[chosen])`` (no eps,
    no scale: asking for either is an error)."""
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"no router score {score!r}")
    if score == "softmax" and (scale != 1 or norm_eps):
        raise ValueError("a softmax router's weights take no scale and no "
                         f"eps (scale {scale}, norm_eps {norm_eps})")
    with jax.named_scope("moe_route"):
        logits = jnp.matmul(x.astype(jnp.float32), wr.astype(jnp.float32),
                            precision=_HIGHEST)
        if score == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            _, ids = lax.top_k(s, top_k)
        else:
            s = jax.nn.sigmoid(logits)
            _, ids = lax.top_k(
                s + lax.stop_gradient(br.astype(jnp.float32)), top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        total = jnp.sum(w, axis=-1, keepdims=True)
        if norm_eps:        # added only where asked: the others' text stays
            total = total + norm_eps
        w = w / total
        if score == "sigmoid":
            w = w * scale
    return ids.astype(jnp.int32), w, s


def balance_sums(ids, probs, live=None):
    """A layer's share of the load-balancing term, over its live tokens:
    ``{"probs": sum_n p_n [E], "slots": [E] the (token, choice) pairs
    that chose each expert, "tokens": the live tokens}``, float32. The
    slots are counts of a choice and carry no gradient; ``probs`` does.
    Summed over layers, ``E * sum_e (slots_e / N) (probs_e / N)`` is the
    term (``N`` the summed tokens): ``k`` at a balanced router, ``E`` at
    one that sends every token to one expert."""
    with jax.named_scope("moe_balance"):
        T, E = probs.shape
        live = (jnp.ones((T,), jnp.float32) if live is None
                else live.reshape(-1).astype(jnp.float32))[:, None]
        slots = jnp.sum(ids[..., None] == jnp.arange(E, dtype=ids.dtype),
                        axis=1, dtype=jnp.float32)          # [T, E]
        return {"probs": jnp.sum(probs * live, axis=0),
                "slots": jnp.sum(slots * live, axis=0),
                "tokens": jnp.sum(live)}


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [R, k]`` whose rows lie sorted by group, ``rhs [G, k, n]``,
    ``group_sizes [G]`` (their sum may fall short of ``R``): row r times
    its group's matrix; rows past the sum come out zero. (The kernel
    leaves them unwritten, and what lies there may be no number at all:
    they are zeroed by a select, never by a product, here and in the
    left operand's gradient.)"""
    group_sizes = group_sizes.astype(jnp.int32)
    if common.partitioned() or not common.use_pallas():
        common.note("moe_grouped_matmul", "ref")
        return lax.ragged_dot(lhs, rhs, group_sizes)
    common.note("moe_grouped_matmul", common.pallas_path())
    return _gmm(lhs, rhs, group_sizes, common.interpret())


def _megablox():
    """megablox's kernels, ``gmm`` and ``tgmm`` (the package exports its
    custom-VJP ``gmm`` under the module's name)."""
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _widths(size: int):
    """A dimension's tile widths, widest first: the multiples of 128 that
    divide it (no block runs past its end, so no remainder is masked),
    or the whole of a dimension that is no multiple of 128."""
    if size % common.LANE:
        return (size,)
    return tuple(t for t in range(size, 0, -common.LANE) if size % t == 0)


def _vmem_bytes(kind: str, tm: int, tk: int, tn: int, item: int) -> int:
    """What a megablox call holds in VMEM at tiles ``(tm, tk, tn)``, as
    ``common.VMEM_BUDGET_BYTES`` counts: every block twice (the
    pipeline's two buffers) and the float32 accumulator; for ``gmm`` the
    product's float32 ``[tm, tn]`` and one more left block, for ``tgmm``
    (output block ``[tk, tn]``) the left block's float32 transpose. Each
    count lies at or above what Mosaic allocates at the expert cells'
    tiles (``tests/test_tpu_compile.py`` compiles them under the budget)."""
    if kind == "tgmm":
        return 2 * item * (tm * tk + tm * tn + tk * tn) + 4 * (tk * tn
                                                               + tk * tm)
    return (item * (3 * tm * tk + 2 * tk * tn + 2 * tm * tn)
            + 8 * tm * tn)


def gmm_tiles(kind: str, m: int, k: int, n: int, item: int):
    """``(tm, tk, tn)`` for one megablox call, ``kind`` ``"gmm"`` or
    ``"tgmm"``, of ``m`` rows, contraction ``k`` and output width ``n``
    (for ``tgmm``: ``[G, k, n]`` summed over the ``m`` rows), operands of
    ``item`` bytes: of the widths that divide their dimension, the tiles
    with the most work a grid step that fit the VMEM budget, the larger
    contraction first where two do as much. ``tm`` is the rows' tile."""
    tm = next((t for t in (_ROW_TILE, 256, 128) if m % t == 0), m)
    fits = [(a, b) for a in _widths(k) for b in _widths(n)
            if _vmem_bytes(kind, tm, a, b, item) <= common.VMEM_BUDGET_BYTES]
    tk, tn = max(fits, key=lambda t: (t[0] * t[1], t[0]),
                 default=(_widths(k)[-1], _widths(n)[-1]))
    return tm, tk, tn


def _kernel(name, lhs, rhs, group_sizes, dtype, interpret):
    """One megablox call at its own tiles, noted as ``moe_gmm_tiles``:
    ``"fwd"`` ``lhs [m, k]`` times ``rhs [G, k, n]``; ``"dlhs"`` times
    ``rhs [G, n, k]`` transposed; ``"drhs"`` ``lhs [m, k]^T`` times ``rhs
    [m, n]``, group by group, into ``[G, k, n]``."""
    m, k = lhs.shape
    kernels = _megablox()
    if name == "drhs":
        tiles = gmm_tiles("tgmm", m, k, rhs.shape[1], lhs.dtype.itemsize)
        # tgmm takes the left operand [k, m] and swaps it back
        call = functools.partial(kernels.tgmm, lhs.swapaxes(0, 1))
    else:
        n = rhs.shape[1 if name == "dlhs" else 2]
        tiles = gmm_tiles("gmm", m, k, n, lhs.dtype.itemsize)
        call = functools.partial(kernels.gmm, lhs,
                                 transpose_rhs=name == "dlhs")
    common.note("moe_gmm_tiles", name + " " + "x".join(map(str, tiles)))
    return call(rhs, group_sizes, dtype, tiles, interpret=interpret)


def _written(rows: int, group_sizes):
    return jnp.arange(rows)[:, None] < jnp.sum(group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    out = _kernel("fwd", lhs, rhs, group_sizes, lhs.dtype, interpret)
    return (jnp.where(_written(lhs.shape[0], group_sizes), out, 0),
            (lhs, rhs, group_sizes))


def _gmm_bwd(interpret, saved, g):
    """megablox's own rule (``ops._gmm_bwd``) with each call's tiles
    reckoned from its own shape: ``dlhs = g rhs^T`` by ``gmm``
    transposed, ``drhs = lhs^T g`` by ``tgmm``. The forward's select
    passes no cotangent to the rows past the sum, and ``gmm`` leaves them
    unwritten in ``dlhs``: a select zeroes them there too."""
    lhs, rhs, group_sizes = saved
    written = _written(lhs.shape[0], group_sizes)
    g = jnp.where(written, g, 0)
    dlhs = _kernel("dlhs", g, rhs, group_sizes, lhs.dtype, interpret)
    drhs = _kernel("drhs", lhs, g, group_sizes, rhs.dtype, interpret)
    return jnp.where(written, dlhs, 0), drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _plan(ids, offset, held: int, live):
    """For every (token, choice) pair the local index of its expert, or
    ``held`` where that expert is not here or the token is padding; and
    the rows each held expert gets."""
    with jax.named_scope("moe_dispatch"):
        local = ids - offset
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & (live.reshape(-1, 1) > 0)
        key = jnp.where(here, local, held).reshape(-1)
        counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype),
                         axis=0, dtype=jnp.int32)
    return key, counts


def _chunk_rows(T: int, K: int, E: int, held: int) -> int:
    """Rows of the dispatch buffer: twice what a uniform router sends
    here, or the worst case ``T * min(K, held)`` where that is less."""
    def up(n):
        n = max(int(n), 8)
        q = _ROW_TILE if n >= _ROW_TILE else 8
        return -(-n // q) * q
    return up(min(2 * T * K * held / E, T * min(K, held)))


def _chunk(x, wg, wu, wd, gates, pairs, counts, first, into, slot=None):
    """``into`` ([T, d] float32) plus the routed sum of the sorted pairs
    ``first .. first + R`` (``pairs [R]``: their indices into the
    flattened [T * K] choices). ``counts [held]`` are the whole batch's
    rows per expert; the chunk's own follow from where it starts. The
    buffer's rows are moved by ``ops/rows.py``'s kernels where ``slot``
    (every pair's sorted position) is given, else by XLA's gather and
    scatter-add."""
    R, K = pairs.shape[0], gates.shape[1]
    with jax.named_scope("moe_dispatch"):
        ends = jnp.cumsum(counts)
        mine = jnp.clip(jnp.minimum(ends, first + R)
                        - jnp.maximum(ends - counts, first), 0, R)
        xg = row_ops.gather_rows(x, pairs, K, mine, slot, first)
    with jax.named_scope("moe_experts"):
        a = (jax.nn.silu(grouped_matmul(xg, wg, mine))
             * grouped_matmul(xg, wu, mine))
        o = grouped_matmul(a, wd, mine)
    with jax.named_scope("moe_combine"):
        # a token's up to k contributions are added in float32; rows
        # past the last expert's are zero and add nothing
        return row_ops.sum_rows(o, gates, pairs, mine, slot, first, into)


def _spill_rows(R: int) -> int:
    """Rows of each chunk after the first, which takes ``R``. A chunk
    costs what its size costs, held rows or not (the grouped products'
    grid and every row's zero or copy), and what a batch sends past the
    first chunk is mostly a small part of it: so these chunks hold at
    most ``_SPILL_ROWS``."""
    return min(R, _SPILL_ROWS)


def _first_row(c, R: int):
    """Where chunk ``c`` starts among the sorted pairs: the first holds
    ``R`` rows, each after it ``_spill_rows(R)``."""
    S = _spill_rows(R)
    with jax.named_scope("moe_dispatch"):
        if S == R:
            return c * R
        return c * S + jnp.minimum(c, 1) * (R - S)


def _sorted_pairs(key, R: int):
    """The (token, choice) pairs sorted by expert, held experts' first,
    padded to a first chunk of ``R`` and whole chunks after it; and,
    where the row kernels run, every pair's place in that order (else
    None)."""
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        n = order.shape[0]
        pad = max(R - n, 0) + (-max(n - R, 0)) % _spill_rows(R)
        slot = row_ops.slots(order, n) if row_ops.kernels(n, R) else None
        return jnp.pad(order, (0, pad)), slot


def _turns(R, counts):
    """The chunks that the held experts' rows fill: a first of ``R``
    sorted rows, then as many of ``_spill_rows(R)`` as the rest needs."""
    S = _spill_rows(R)
    with jax.named_scope("moe_dispatch"):
        rows = jnp.sum(counts)
        if S == R:
            return (rows + R - 1) // R
        return jnp.where(rows > 0,
                         1 + (jnp.maximum(rows - R, 0) + S - 1) // S, 0)


def _over_chunks(R, counts, init, step, first=0):
    """``step(c, carry)`` for every chunk of sorted rows from ``first``
    on that holds a held expert's row: as many turns as the rows need,
    no more. The loop itself lies under no inner scope, or every
    operation of its body would carry two."""
    return lax.fori_loop(first, _turns(R, counts), step, init)


def _chunk_pairs(order, c, R: int, rows: int):
    """The ``rows`` sorted pairs of chunk ``c``."""
    with jax.named_scope("moe_dispatch"):
        return lax.dynamic_slice(order, (_first_row(c, R),), (rows,))


def _routed_sum(R, order, slot, x, wg, wu, wd, gates, counts):
    S = _spill_rows(R)

    def chunk(c, rows, y):
        return _chunk(x, wg, wu, wd, gates, _chunk_pairs(order, c, R, rows),
                      counts, _first_row(c, R), y, slot)

    with jax.named_scope("moe_combine"):
        y = jnp.zeros(x.shape, jnp.float32)
    if S == R:      # one loop over chunks of one size, from the first
        return _over_chunks(R, counts, y, lambda c, y: chunk(c, R, y))
    return _over_chunks(R, counts, chunk(0, R, y),
                        lambda c, y: chunk(c, S, y), first=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(R, x, wg, wu, wd, gates, key, counts):
    return _routed_sum(R, *_sorted_pairs(key, R), x, wg, wu, wd, gates,
                       counts)


def _routed_fwd(R, x, wg, wu, wd, gates, key, counts):
    # sorted once, kept for the backward
    order, slot = _sorted_pairs(key, R)
    return (_routed_sum(R, order, slot, x, wg, wu, wd, gates, counts),
            (x, wg, wu, wd, gates, order, slot, counts))


def _routed_bwd(R, saved, dy):
    """The first chunk's gradients come out in the types the transposed
    ``_chunk`` gives them (the input's and the leaves' own; float32 for
    the gates) and are the carry of a loop over the chunks after it. The
    usual batch is one chunk, so that loop makes no turn, and no buffer
    is filled with zeros, summed in float32 or cast back. At two chunks
    the add of two partials in their own type is their float32 sum
    rounded once, as a float32 sum over the chunks would give; from
    three on each turn's add rounds: a batch that sends this chip more
    rows than the first chunk and one after it hold. The rule's own
    operations (a chunk's pairs, the sums) open ``moe_dispatch``
    themselves: a hand-written rule inherits no forward scope. What
    ``_chunk`` opens it opens here too, recomputed and transposed."""
    *floats, order, slot, counts = saved
    with jax.named_scope("moe_dispatch"):
        zero = jnp.zeros(floats[0].shape, jnp.float32)

    def grads_of(c, rows):
        pairs = _chunk_pairs(order, c, R, rows)
        _, vjp = jax.vjp(
            lambda *f: _chunk(*f, pairs, counts, _first_row(c, R), zero,
                              slot),
            *floats)
        return vjp(dy)

    def step(c, grads):
        ds = grads_of(c, _spill_rows(R))
        with jax.named_scope("moe_dispatch"):
            return tuple(g + d for g, d in zip(grads, ds))

    return (*_over_chunks(R, counts, grads_of(0, R), step, first=1),
            None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def routed_experts(x, wg, wu, wd, ids, gates, *, n_experts: int,
                   offset=0, live=None):
    """``sum_i w_i E_i(x)`` over the chosen experts that are held here:
    experts ``offset .. offset + wg.shape[0]`` of ``n_experts``. Returns
    ``(y [T, d], rows [held] int32, turns int32)``: the rows each held
    expert got, and the chunks of sorted rows the loop over them took (1
    for the usual batch; more is the overflow path)."""
    held = wg.shape[0]
    key, counts = _plan(ids, offset, held, live)
    R = _chunk_rows(x.shape[0], ids.shape[1], n_experts, held)
    y = _routed(R, x, wg, wu, wd, gates.astype(jnp.float32), key, counts)
    turns = _turns(R, counts)
    with jax.named_scope("moe_combine"):
        return y.astype(x.dtype), counts, turns


def moe_ffn(params, x, *, top_k: int, scale: float = 1.0, offset=0,
            live=None, shared: bool = True, norm_eps: float = 0.0,
            score: str = "sigmoid"):
    """The layer on one device: ``x [T, d]`` -> ``(y [T, d], rows, turns,
    balance)`` (``routed_experts``'s counts; ``balance_sums`` of a softmax
    router's scores, None for a sigmoid router, whose scores are no
    distribution).
    ``params["wg"]`` holds ``held`` experts, those from ``offset`` on, of
    the ``params["wr"].shape[-1]`` the router scores. ``live`` ([T], 0 or
    1) marks real tokens: padding is routed nowhere. ``shared`` False
    leaves the shared expert out (``make_moe`` adds it once)."""
    ids, w, s = route(x, params["wr"], params["br"], top_k, scale, norm_eps,
                      score)
    y, rows, turns = routed_experts(
        x, params["wg"], params["wu"], params["wd"], ids, w,
        n_experts=params["wr"].shape[-1], offset=offset, live=live)
    if shared and "sg" in params:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(x, params["sg"], params["su"], params["sd"])
    balance = balance_sums(ids, s, live) if score == "softmax" else None
    return y, rows, turns, balance


_EXPERT_LEAVES = ("wg", "wu", "wd")


def make_moe(mesh: Mesh, axis: str, *, top_k: int, scale: float = 1.0):
    """Expert-parallel MoE over ``axis``: the experts' leaves split expert
    major, router and shared expert replicated, ``x`` replicated. Every
    device routes the whole batch over all the experts, computes the
    partial sum of the experts it holds, and the partial sums are added
    over the axis (one ``psum``); the shared expert is counted once.
    Returns ``fn(params, x, live=None) -> y``."""
    from paddle_tpu.parallel.mesh import shard_map_compat
    n_dev = mesh.shape[axis]

    def local(params, x, live):
        held = params["wg"].shape[0]
        y = moe_ffn(params, x, top_k=top_k, scale=scale,
                    offset=lax.axis_index(axis) * held, live=live,
                    shared=False)[0]
        y = lax.psum(y, axis)
        if "sg" in params:
            with jax.named_scope("moe_shared"):
                y = y + swiglu(x, params["sg"], params["su"], params["sd"])
        return y

    @functools.lru_cache(maxsize=None)
    def jitted(names):
        specs = {k: P(axis) if k in _EXPERT_LEAVES else P() for k in names}
        return jax.jit(shard_map_compat(
            local, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P(),
            check_vma=False))

    def call(params, x, live=None):
        if params["wg"].shape[0] % n_dev:
            raise ValueError(f"{params['wg'].shape[0]} experts over "
                             f"{n_dev} devices")
        if live is None:
            live = jnp.ones((x.shape[0],), jnp.float32)
        return jitted(tuple(sorted(params)))(params, x, live)

    return call


def shard_moe_params(params, mesh: Mesh, axis: str):
    """Place MoE params: experts split over ``axis``, the rest whole."""
    return {k: jax.device_put(v, NamedSharding(
        mesh, P(axis) if k in _EXPERT_LEAVES else P()))
        for k, v in params.items()}
