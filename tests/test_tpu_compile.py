"""The language-model cells' kernels, and the optimizer's update of one
leaf, compiled for a described TPU v5e at the widths the cells run
them, with no chip: Mosaic refuses here what it would refuse there (a
slice off the tiling, too much VMEM), and the compiled text shows what
the chip's compiler makes of plain jnp. Nothing runs; a compile that
passes is not a chip run. All in this one file:
the worker that gets it is the one process that loads the TPU's
compiler."""

import contextlib
import math
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import common


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


_MOSAIC = 'custom_call_target="tpu_custom_call"'


def _compile(fn, *shapes, donate_argnums=()):
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with common.force_mode("pallas"):
            return jax.jit(fn, donate_argnums=donate_argnums).lower(
                *shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_latent_attention_core_forward_and_backward(one_chip):
    """q, k of 192 and v of 128, 32 heads, 4,096 positions, causal,
    512 x 512 tiles: the forward kernel and the one backward kernel."""
    from paddle_tpu.ops.attention import flash_attention

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def step(q, k, v, g):
        return jax.vjp(lambda *a: flash_attention(
            *a, None, causal=True, block_q=512, block_k=512), q, k, v)[1](g)

    compiled = _compile(step, sd(2, 32, 4096, 192), sd(2, 32, 4096, 192),
                        sd(2, 32, 4096, 128), sd(2, 32, 4096, 128))
    assert compiled.as_text().count(_MOSAIC) == 2


def test_recomputed_latent_layer_runs_the_forward_kernel_once(one_chip):
    """The cell's attention layer (2 x 4,096 tokens of 2,048, 32 heads,
    192/128 core at 512 x 512 tiles) marked `recompute`, through the
    executor: its forward and backward hold the forward kernel and the
    one backward kernel, and no second forward kernel for the
    recomputation."""
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.core.network import Network

    dsl.reset()
    x = dsl.data(name="x", size=2048, is_sequence=True)
    attn = dsl.mla_attention(
        x, num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=32e6, name="attn", layer_attr={"recompute": True})
    net = Network(dsl.current_graph(), outputs=[attn.name])

    def sd(shape):
        return jax.ShapeDtypeStruct(shape.shape, jnp.bfloat16,
                                    sharding=one_chip)

    params = jax.tree_util.tree_map(
        sd, jax.eval_shape(net.init_params, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16,
                                  sharding=one_chip)

    def step(params, xv, g):
        def layer(params, xv):
            return net.apply(params, {"x": Argument(value=xv)},
                             train=True)[attn.name].value
        # the output too, or a forward pass nobody reads would be dropped
        out, back = jax.vjp(layer, params, xv)
        return out, back(g)

    compiled = _compile(step, params, tokens, tokens)
    assert compiled.as_text().count(_MOSAIC) == 2


@pytest.mark.parametrize("heads,window,backward",
                         [(64, 512, "fused"), (48, None, "split")])
def test_grouped_query_cores_forward_and_backward(one_chip, heads, window,
                                                  backward):
    """The Laguna cell's two cores: 64 query heads with a window of 512
    and 48 without, over 8 key-value heads of 128, 8,192 positions,
    512 x 512 tiles; K and V go in at 8 heads. Under the window the
    forward kernel and the one backward kernel, which sweeps a group's 8
    query heads inside a kv block's sweep and keeps their two open q
    blocks each; without it the forward kernel, dK/dV with its sweep
    over 6, and dQ (dQ for 6 heads' 8,192 positions is 25 MB)."""
    from paddle_tpu.ops.attention import flash_attention

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def step(q, k, v, g):
        return jax.vjp(lambda *a: flash_attention(
            *a, None, causal=True, block_q=512, block_k=512,
            window=window), q, k, v)[1](g)

    with common.record_dispatch() as tally:
        compiled = _compile(step, sd(1, heads, 8192, 128),
                            sd(1, 8, 8192, 128), sd(1, 8, 8192, 128),
                            sd(1, heads, 8192, 128))
    assert tally == {"flash_attention": {"pallas": 1},
                     "flash_backward": {backward: 1}}
    assert compiled.as_text().count(_MOSAIC) \
        == {"fused": 2, "split": 3}[backward]
    dq, dk, dv = compiled.out_info
    assert dq.shape == (1, heads, 8192, 128)
    assert dk.shape == dv.shape == (1, 8, 8192, 128)


def test_recomputed_sliding_layer_runs_the_forward_kernel_once(one_chip):
    """The cell's sliding layer (8,192 tokens of 2,048, 64 heads over 8
    key-value heads, a window of 512) marked `recompute`, through the
    executor: the forward kernel and the one backward kernel, no second
    forward kernel."""
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.core.network import Network

    dsl.reset()
    x = dsl.data(name="x", size=2048, is_sequence=True)
    attn = dsl.gqa_attention(x, num_heads=64, num_kv_heads=8, head_dim=128,
                             window=512, name="swa",
                             layer_attr={"recompute": True})
    net = Network(dsl.current_graph(), outputs=[attn.name])

    def sd(shape):
        return jax.ShapeDtypeStruct(shape.shape, jnp.bfloat16,
                                    sharding=one_chip)

    params = jax.tree_util.tree_map(
        sd, jax.eval_shape(net.init_params, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16,
                                  sharding=one_chip)

    def step(params, xv, g):
        def layer(params, xv):
            return net.apply(params, {"x": Argument(value=xv)},
                             train=True)[attn.name].value
        out, back = jax.vjp(layer, params, xv)
        return out, back(g)

    compiled = _compile(step, params, tokens, tokens)
    assert compiled.as_text().count(_MOSAIC) == 2


def test_looped_step_runs_each_cores_forward_kernel_once(one_chip):
    """A small looped decoder whole (``models.ouro``: 2 layers run 3
    times over one copy of their weights, 2 heads of 128 over 1,024
    tokens of 256, ``recompute`` on), cost and every leaf's gradient:
    two Mosaic calls an application of an attention layer (the forward
    and the backward kernel) and no forward kernel a second time for a recomputation
    or for a later pass over the same weights: PR 28's finding holds
    across passes."""
    from paddle_tpu import models
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.trainer.trainer import Topology

    layers, passes = 2, 3
    dsl.reset()
    cost, _out, _names = models.ouro(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=layers, num_attention_heads=2,
        num_key_value_heads=2, head_dim=128, total_ut_steps=passes,
        recompute=True, loss_chunk=512, attention_block=512)
    net = Topology(cost).network
    assert len(net.param_specs) == 5 + 11 * layers      # one leaf a weight

    def sd(shape):
        return jax.ShapeDtypeStruct(shape.shape, jnp.bfloat16,
                                    sharding=one_chip)

    params = jax.tree_util.tree_map(
        sd, jax.eval_shape(net.init_params, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)

    def step(params, ids):
        def loss(params):
            out = net.apply(params, {"words": Argument(value=ids)},
                            train=True)
            return jnp.mean(out["out_head"].value)
        return jax.value_and_grad(loss)(params)

    compiled = _compile(step, params, ids)
    assert compiled.as_text().count(_MOSAIC) == 2 * layers * passes


@pytest.mark.parametrize("d,tokens,hidden,n_experts,top_k,temp_mb", [
    (2048, 8192, 1536, 64, 4, 400),         # the LFM2 cell's
    (2048, 8192, 768, 256, 8, 150),         # the JoyAI cell's
    (2048, 8192, 512, 256, 8, 150),         # the Laguna cell's
    (2304, 16384, 896, 64, 8, 1000),        # the Mellum2 cell's
])
def test_routed_experts_forward_and_backward(one_chip, d, tokens, hidden,
                                             n_experts, top_k, temp_mb,
                                             monkeypatch):
    """8 held experts, a cell's tokens, model width, expert width, top k
    and expert count: the loop over buffers of twice a uniform router's
    rows, forward and backward. **The expert rule's static counter**
    (``PERF.md`` section 3): the first buffer's gradients are the loop's
    carry in the leaves' own types, so no instruction writes and no
    ``while`` carries a float32 array of an expert leaf's shape, and the
    temporaries stay under a bound the float32 sums over the buffers
    passed (701, 364, 339 MB at the LFM2, JoyAI and Laguna shapes).
    **The grouped products' tiles**: every megablox call (forward, and
    the backward's ``gmm`` transposed and ``tgmm``) takes tiles that
    divide its dimensions, and Mosaic fits each into
    ``VMEM_BUDGET_BYTES``: compiled with the scoped limit LOWERED to the
    budget (here alone; the program sets no limit); at Mellum2's widths
    no tile is 128 wide."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.parallel.moe import routed_experts

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(x, wg, wu, wd, ids, gates, dy):
        def f(x, wg, wu, wd, gates):
            return routed_experts(x, wg, wu, wd, ids, gates,
                                  n_experts=n_experts, offset=8)[0]
        return jax.vjp(f, x, wg, wu, wd, gates)[1](dy)

    params = pltpu.CompilerParams
    monkeypatch.setattr(pltpu, "CompilerParams", lambda **kw: params(
        vmem_limit_bytes=common.VMEM_BUDGET_BYTES, **kw))
    with common.record_dispatch() as tally:
        compiled = _compile(
            step, sd((tokens, d)), sd((8, d, hidden)), sd((8, d, hidden)),
            sd((8, hidden, d)), sd((tokens, top_k), jnp.int32),
            sd((tokens, top_k), jnp.float32), sd((tokens, d)))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 9
    noted = [t.split() for t in tally["moe_gmm_tiles"]]
    assert {call for call, _ in noted} == {"fwd", "dlhs", "drhs"}
    for _, tiles in noted:
        tm, tk, tn = map(int, tiles.split("x"))
        # (k, n) is (d, hidden) or (hidden, d) for every call of the two
        assert any(k % tk == 0 and n % tn == 0
                   for k, n in ((d, hidden), (hidden, d))), noted
        assert d != 2304 or 128 not in (tk, tn), noted
    leaf = re.compile(rf"\b(\w+)\[(?:8,{d},{hidden}|8,{hidden},{d})\]")
    assert {m.group(1) for m in leaf.finditer(text)} == {"bf16"}
    # the loop over the buffers after the first carries the leaves in
    # their own type
    carried = [set(leaf.findall(line)) for line in text.splitlines()
               if " while(" in line]
    assert {"bf16"} in carried and all(c <= {"bf16"} for c in carried)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_mb * 1e6, temp


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(")


def _entry_ops(compiled):
    """``[(opcode, elements of its largest result)]`` of the compiled
    module's entry computation."""
    text = compiled.as_text()
    ops = []
    for line in text[text.index("ENTRY"):].splitlines()[1:]:
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        sizes = [math.prod(int(d) for d in dims.split(",") if d)
                 for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
        ops.append((m.group(2), max(sizes, default=0)))
    return ops


@pytest.mark.parametrize("grad", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(2048, 8192), (8, 2048, 768),
                                   (3, 3, 256, 256), (2048,), (1280, 2)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kind", ["adam", "momentum"])
def test_update_is_one_pass_over_a_leaf_in_its_own_layout(one_chip, kind,
                                                          shape, grad):
    """One leaf through ``Optimizer._update_param`` (L2 and value
    clipping on, the gradient converted inside, parameter and slots
    donated), as the step runs it: ONE loop fusion holds every
    leaf-sized result, so no ``reshape``, ``copy``, ``pad``,
    ``concatenate`` or ``slice`` re-lays a leaf out on the way in or out
    (the `[rows, 128]` view of PR 16's kernel cost four reshapes in and
    three back whenever the last dimension was not 128: a float32
    matrix is tiled (8, 128)); no temporary; the parameter and every
    slot updated in place. A ``copy-start``/``copy-done`` pair is the
    compiler's prefetch of a small operand into VMEM, not a relayout.
    This is the optimizer row's static counter (``PERF.md`` section 3)."""
    from paddle_tpu.optim import Adam, Momentum
    opt = (Adam(learning_rate=1e-5, beta1=0.9, beta2=0.95, l2_rate=8e-4,
                gradient_clipping_threshold=25.0)
           if kind == "adam" else
           Momentum(learning_rate=0.1, momentum=0.9, l2_rate=1e-4,
                    gradient_clipping_threshold=25.0))

    def sd(dtype=jnp.float32, shape=shape):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    slots = {name: sd() for name in opt.slot_names()}

    def update(g, p, slots, lr, t):
        return opt._update_param(g.astype(jnp.float32), p, slots, None,
                                 lr, t)

    compiled = _compile(update, sd(grad), sd(), slots, sd(shape=()),
                        sd(jnp.int32, ()), donate_argnums=(1, 2))
    leaf = math.prod(shape)
    big = [op for op, n in _entry_ops(compiled)
           if n >= leaf and op != "parameter"]
    assert big.count("fusion") == 1, big
    assert set(big) <= {"fusion", "copy-start", "copy-done"}, big
    assert "tpu_custom_call" not in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert memory.alias_size_in_bytes == 4 * leaf * (1 + len(slots))


def test_lstm_scan_backward_streams_no_weight_gradient(one_chip):
    """Two LSTM layers forward and backward at the LSTM cell's shape
    (T 100, B 256, H 1280, float32; dispatch says ``ref``: the scan), as
    the chip's compiler sees them: no while loop holds a float32 array
    of the weight's shape (the weight itself rides along rounded to
    bfloat16 once; a float32 one would be ``dW`` crossing HBM every
    step, as in JAX's own transpose of the scan), and each layer's
    ``dW`` is one product after the scan that still carries the layer's
    scope, where ``lstm_seq_roofline`` and ``breakdown`` look for it.
    This is the LSTM row's static counter (``PERF.md`` section 3)."""
    from paddle_tpu.ops.lstm import lstm_sequence
    T, B, H = 100, 256, 1280

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(xs, mask, w0, w1, bias, peep, h0):
        with common.record_dispatch() as tally:
            for i, w in enumerate((w0, w1)):
                with jax.named_scope(f"lstm{i}"):
                    xs = jnp.tile(lstm_sequence(xs, mask, w, bias, peep,
                                                peep, peep, h0, h0)[0], 4)
        assert tally == {"lstm": {"ref": 2}}
        return jnp.sum(xs)

    text = _compile(jax.grad(loss, argnums=(0, 2, 3, 4, 5)),
                    sd(T, B, 4 * H), sd(T, B), sd(H, 4 * H), sd(H, 4 * H),
                    sd(4 * H), sd(H), sd(B, H)).as_text()
    loops = re.findall(r"^\s*%?[\w.\-]+ = (\(.*?\)) while\(", text, re.M)
    assert len(loops) == 4, len(loops)
    assert not any(f"f32[{H},{4 * H}]" in loop for loop in loops)
    for i in range(2):
        products = re.findall(
            rf" convolution\(.*op_name=\"[^\"]*transpose\(jvp\(lstm{i}\)\)"
            r"/tbh,tbg->hg/dot_general\"", text)
        assert len(products) == 1, (i, products)


# the inner scopes a layer opens, by the layer's kind; PR 35 added all but
# the cores and ``moe_route`` / ``moe_experts`` / ``moe_combine``
_PARTS = {"attn": ("attn_qkv", "attn_qk_norm", "attn_rope", "mla_core",
                   "attn_core", "attn_out"),
          "moe": ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
                  "moe_shared", "moe_balance"),
          "sconv": ("sconv_in", "sconv_core", "sconv_out")}
# ... then the short convolution's three, ``attn_qk_norm`` and
# ``moe_balance``, the balancing term's
_ADDED = {"attn_qkv", "attn_rope", "attn_out", "moe_dispatch", "moe_shared",
          "attn_qk_norm", "sconv_in", "sconv_core", "sconv_out",
          "moe_balance"}
_NAMED = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(.*op_name=\"([^\"]+)\"",
    re.M)
# what may lie under a layer's scope and under none of its parts, and is
# no operation of the layer's: the checkpoint's own call (constants and a
# weight's copy the compiler hoists out of it), the expert loop's control
# (the ``while``, its bound's test, its counter) and the one sum of the
# input's cotangents across ``_routed``'s call (a custom_vjp's call is an
# equation of the layer, so its sum is named by the layer alone)
_BARE = re.compile(r"jvp\(blk0_\w+\)\)?/(?:remat2|add_any|"
                   r"while(?:/cond/lt|/body/add)?)$")


def _layer_step(kind, one_chip):
    """``(step, shapes, layer's name)``: one layer of a cell at its
    widths over 1,024 tokens through the executor, its output and the
    gradients of its input and parameters."""
    from paddle_tpu.config import dsl

    dsl.reset()
    x = dsl.data(name="x", size=2048, is_sequence=True)
    remat = {"recompute": True}
    if kind == "latent":
        layer = dsl.mla_attention(
            x, num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rope_theta=32e6, name="blk0_attn", layer_attr=remat)
    elif kind == "full":
        layer = dsl.gqa_attention(
            x, num_heads=48, num_kv_heads=8, head_dim=128, rotary_dim=64,
            rope_theta=5e5, name="blk0_attn", layer_attr=remat,
            yarn={"factor": 64.0, "original_max_position_embeddings": 4096})
    elif kind == "windowed":
        layer = dsl.gqa_attention(
            x, num_heads=64, num_kv_heads=8, head_dim=128, window=512,
            name="blk0_swa", layer_attr=remat)
    elif kind == "qk_norm":         # the LFM2 cell's: a head of 64, no gate
        layer = dsl.gqa_attention(
            x, num_heads=32, num_kv_heads=8, head_dim=64, rope_theta=1e6,
            gate=False, qk_norm=True, qk_norm_eps=1e-5, name="blk0_attn",
            layer_attr=remat)
    elif kind == "sconv":
        layer = dsl.short_conv(x, kernel=3, name="blk0_sconv",
                               layer_attr=remat)
    elif kind == "balanced":        # the Mellum2 cell's: a softmax router
        layer = dsl.moe(
            x, expert_hidden=896, num_experts=64, top_k=8, experts_held=8,
            expert_offset=8, score="softmax", name="blk0_moe")
        dsl.moe_balance_cost([layer], coeff=0.001, name="moe_balance")
        return (*_output_and_gradients(layer.name, one_chip, 1024,
                                       costs=["moe_balance"]), layer.name)
    else:
        layer = dsl.moe(
            x, expert_hidden=768, num_experts=256, top_k=8, experts_held=8,
            expert_offset=8, shared_hidden=768, routed_scaling_factor=2.5,
            name="blk0_moe")
    return (*_output_and_gradients(layer.name, one_chip, 1024), layer.name)


def _output_and_gradients(out_name, one_chip, tokens, costs=()):
    """``(step, shapes)`` over the DSL's current graph, whose one input
    ``x`` is a row of ``tokens`` of 2,048 in bfloat16 with its mask: the
    layer ``out_name``'s output and the gradients of the parameters and
    of ``x``, with the layers ``costs``' values added to the loss that
    is differentiated."""
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.core.network import Network

    net = Network(dsl.current_graph(), outputs=[out_name, *costs])

    def sd(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16,
                                    sharding=one_chip)

    params = jax.tree_util.tree_map(
        sd, jax.eval_shape(net.init_params, jax.random.PRNGKey(0)))
    rows = jax.ShapeDtypeStruct((1, tokens, 2048), jnp.bfloat16,
                                sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, tokens), jnp.float32, sharding=one_chip)

    def step(params, xv, m, g):
        def apply(params, xv):
            outs = net.apply(params, {"x": Argument(value=xv, mask=m)},
                             train=True)
            return outs[out_name].value, [outs[c].value for c in costs]

        (out, added), back = jax.vjp(apply, params, xv)
        return out, back((g, [jnp.ones_like(c) for c in added]))

    return step, (params, rows, mask, rows)


@pytest.mark.parametrize("shape,window", [
    ((2, 32, 32, 4096, 192, 128), None),        # the JoyAI cell's latent core
    ((1, 16, 16, 4096, 128, 128), None),        # the Ouro cell's
    ((1, 64, 8, 8192, 128, 128), 512),          # the Laguna cell's sliding
])
def test_the_cores_backward_is_one_mosaic_call_inside_the_budget(
        one_chip, shape, window, monkeypatch):
    """The static counter of the one backward kernel: at a cell's shape
    (512 x 512 tiles) the backward of a core holds ONE Mosaic call where
    dK/dV and dQ are two, with the same results' shapes, and Mosaic fits
    it into ``VMEM_BUDGET_BYTES``: compiled with the scoped limit LOWERED
    to the budget (here alone; the program sets no limit), where the
    reckoning of `_vmem_bytes` said it would."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import attention as A
    B, N, Nkv, T, Dqk, Dv = shape
    cfg = (N, 0, Dqk ** -0.5, True, 512, 512, window, N // Nkv)

    def sd(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    residuals = (sd(B * N, T, Dqk), sd(B * Nkv, T, Dqk), sd(B * Nkv, T, Dv),
                 sd(B, 1, T, dtype=jnp.float32), sd(B * N, T, Dv),
                 sd(B * N, T, dtype=jnp.float32), sd(B * N, T, Dv))
    operands = residuals[:4] + (residuals[6], sd(B * N, T, A._STAT_LANES,
                                                 dtype=jnp.float32))
    two = _compile(lambda *a: A._backward_split(cfg, *a), *operands)
    assert two.as_text().count(_MOSAIC) == 2
    params = pltpu.CompilerParams
    monkeypatch.setattr(pltpu, "CompilerParams", lambda **kw: params(
        vmem_limit_bytes=common.VMEM_BUDGET_BYTES, **kw))
    with common.record_dispatch() as tally:
        one = _compile(lambda *a: A._flash_backward(cfg, *a), *residuals)
    assert tally == {"flash_backward": {"fused": 1}}
    assert one.as_text().count(_MOSAIC) == 1
    assert [(o.shape, o.dtype) for o in one.out_info] \
        == [(o.shape, o.dtype) for o in two.out_info]


@pytest.mark.parametrize("kind", ["latent", "full", "windowed", "experts",
                                  "qk_norm", "sconv", "balanced"])
def test_every_operation_of_a_layer_lies_in_one_inner_scope(
        one_chip, kind, monkeypatch):
    """A latent layer, a full and a windowed grouped-query layer, one
    with a q/k normalisation at a head of 64 and a gated short
    convolution under ``recompute``, and an expert layer with its shared
    expert, output and
    gradients: every instruction named under the layer carries exactly
    one of the layer's inner scopes, forward, recomputed and backward
    (but ``_BARE``); ``rematted_computation`` marks the recomputed
    forward and nothing else (its products are the forward's, the
    backward's are twice those); and with PR 35's scopes muted the
    compiled text is the same but for its metadata, so the scopes cost
    the step nothing and the cores' patterns match the instructions
    they matched before."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import step_text        # `same`'s comparison: the text without metadata
    step, shapes, name = _layer_step(kind, one_chip)
    text = _compile(step, *shapes).as_text()
    scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda part: contextlib.nullcontext()
        if part in _ADDED else scope(part))
    # a step of its own: jit keeps the trace of a function it has seen
    step, shapes, _ = _layer_step(kind, one_chip)
    before = _compile(step, *shapes).as_text()
    monkeypatch.undo()

    assert "attn_qkv" not in before and "moe_dispatch" not in before
    assert step_text.bare(text) == step_text.bare(before)

    parts = _PARTS[{"experts": "moe", "balanced": "moe",
                    "sconv": "sconv"}.get(kind, "attn")]
    under = [(inst, op, path) for inst, op, path in _NAMED.findall(text)
             if f"jvp({name})" in path]
    assert len(under) > 100
    products = {}
    for inst, op, path in under:
        have = [p for p in parts if re.search(rf"\b{p}\b", path)]
        if not have:
            assert _BARE.search(path), (inst, op, path)
            assert op in ("constant", "copy", "while", "compare", "add",
                          "get-tuple-element"), (inst, op, path)
            continue
        assert len(have) == 1, (inst, path)
        way = ("again" if "rematted_computation" in path else
               "bwd" if f"transpose(jvp({name}))" in path else "fwd")
        if op == "convolution":
            products[way, have[0]] = products.get((way, have[0]), 0) + 1
    if kind == "experts":           # not under recompute: nothing again
        assert "rematted_computation" not in text
        assert {p for _, p in products} == {"moe_route", "moe_shared"}
        assert products["bwd", "moe_shared"] \
            == 2 * products["fwd", "moe_shared"] == 6
    elif kind == "balanced":
        # the router's product alone (no shared expert); the balancing
        # statistics forward and, through the probabilities, backward
        assert {p for _, p in products} == {"moe_route"}
        ways = {("bwd" if f"transpose(jvp({name}))" in path else "fwd")
                for _, _, path in under if "moe_balance" in path}
        assert ways == {"fwd", "bwd"}
    elif kind == "sconv":
        # `W_in`'s product runs forward, again, and twice backward;
        # `W_out`'s output feeds nothing the backward pass needs
        assert products == {
            ("fwd", "sconv_in"): 1, ("again", "sconv_in"): 1,
            ("bwd", "sconv_in"): 2, ("fwd", "sconv_out"): 1,
            ("bwd", "sconv_out"): 2}, products
        assert {p for _, _, path in under for p in parts
                if re.search(rf"\b{p}\b", path)} == set(parts)
        return
    else:
        # q, k and v's products run forward, again, and twice backward;
        # `wo`'s (and the gate's) output feeds nothing the backward pass
        # needs, so only the gate's, which the core's output is
        # multiplied by, is run again
        n = {"latent": 4}.get(kind, 3)
        gate = 0 if kind in ("latent", "qk_norm") else 1
        assert products == {
            ("fwd", "attn_qkv"): n, ("again", "attn_qkv"): n,
            ("bwd", "attn_qkv"): 2 * n, ("fwd", "attn_out"): 1 + gate,
            ("bwd", "attn_out"): 2 * (1 + gate),
            **({("again", "attn_out"): gate} if gate else {})}, products
    # the cores' and the grouped products' patterns (benchmark/metrics/)
    # read what they read before: the same instructions by name
    for pattern in (r"_attn\).*mla_core", r"_attn\).*attn_core",
                    r"_swa\).*attn_core", r"_moe\).*moe_experts",
                    r"jvp\(\w+_moe\)"):
        now, then = ({inst for inst, _, path in _NAMED.findall(t)
                      if re.search(pattern, path)} for t in (text, before))
        assert now == then, pattern
    if kind == "qk_norm":   # the norms' own instructions, all three ways
        ways = {("again" if "rematted_computation" in path else
                 "bwd" if f"transpose(jvp({name}))" in path else "fwd")
                for _, _, path in under if "attn_qk_norm" in path}
        assert ways == {"fwd", "again", "bwd"}
    core = {"latent": "mla_core", "experts": "moe_experts",
            "balanced": "moe_experts"}.get(kind, "attn_core")
    calls = [path for _, op, path in under
             if op == "custom-call" and "pallas_call" in path]
    assert len(calls) >= 2 and all(core in path for path in calls)


def _tokens_step(layer_of, one_chip, tokens=8192):
    """``(step, shapes)``: a norm, the layer ``layer_of(normed)`` under
    ``recompute`` and the residual add around it, as a block has them,
    over one row of ``tokens`` of 2,048; output and gradients."""
    from paddle_tpu.config import dsl

    dsl.reset()
    x = dsl.data(name="x", size=2048, is_sequence=True)
    layer = layer_of(dsl.rms_norm(x, epsilon=1e-5, name="blk0_a_norm"))
    out = dsl.addto([x, layer], name="blk0_op_add")
    return _output_and_gradients(out.name, one_chip, tokens)


def test_the_head_of_64_runs_the_forward_kernel_once_and_two_backward(
        one_chip):
    """The LFM2 cell's one attention layer at its shape (8,192 tokens, 32
    query heads over 8 of 64, a q/k normalisation, under ``recompute``):
    Mosaic takes a head of half a lane tile as it is; the forward kernel
    once, not again for the recomputation, and the backward as dK/dV and
    dQ (dQ's float32 slots for 4 heads x 8,192 positions, a whole lane
    tile wide, are 16.8 MB: over the budget, as the Laguna cell's full
    layers')."""
    from paddle_tpu.config import dsl
    step, shapes = _tokens_step(lambda x: dsl.gqa_attention(
        x, num_heads=32, num_kv_heads=8, head_dim=64, rope_theta=1e6,
        gate=False, qk_norm=True, qk_norm_eps=1e-5, name="blk0_attn",
        layer_attr={"recompute": True}), one_chip)
    with common.record_dispatch() as tally:
        text = _compile(step, *shapes).as_text()
    assert tally == {"flash_attention": {"pallas": 1},
                     "flash_backward": {"split": 1}}
    assert text.count(_MOSAIC) == 3


def test_the_short_convolutions_core_is_one_pass_forward_and_two_backward(
        one_chip):
    """What the compiler makes of ``gated_short_conv`` in plain jnp at the
    cell's shape (8,192 tokens of 2,048, bfloat16, under ``recompute``,
    a norm before and the residual add after): under ``sconv_core`` ONE
    fusion that writes a ``[T, d]`` array forward (``s = B * X``; the
    taps and the ``C`` gate ride in the ``W_out`` product's fusion), the
    same one again for the recomputation, two backward; none of them
    float32 (``s`` kept in float32 was written out at twice the bytes,
    and four float32 buffers backward); the taps' gradient is no pass of
    its own."""
    from paddle_tpu.config import dsl
    T, d = 8192, 2048
    step, shapes = _tokens_step(lambda x: dsl.short_conv(
        x, kernel=3, name="blk0_sconv", layer_attr={"recompute": True}),
        one_chip, T)
    text = _compile(step, *shapes).as_text()
    entry = text[text.index("\nENTRY "):]
    wide = {}
    for inst, op, path in _NAMED.findall(entry):
        if op != "fusion" or "sconv_core" not in path:
            continue
        line = re.search(rf"^\s*(?:ROOT )?%?{re.escape(inst)} = (.*?) fusion\(",
                         entry, re.M).group(1)
        outs = re.findall(rf"(\w+)\[1,{T},{d}\]", line)
        if outs:
            way = ("again" if "rematted_computation" in path else
                   "bwd" if "transpose(jvp(" in path else "fwd")
            wide.setdefault(way, []).append(outs)
    # fusions, and the [T, d] arrays they write: dB and dX from one, dC
    # (or the taps' input gradient) from the other
    assert {way: (len(f), sum(map(len, f))) for way, f in wide.items()} \
        == {"fwd": (1, 1), "again": (1, 1), "bwd": (2, 3)}, wide
    assert {t for f in wide.values() for outs in f for t in outs} \
        == {"bf16"}, wide


def test_the_mellum2_cells_model_fits_the_chip_with_its_mosaic_calls(
        one_chip):
    """The Mellum2 cell's model at its shape (2 x 8,192 tokens, every
    width as published, bfloat16 compute over float32 masters, the
    routers float32): its loss and every gradient compiled for a
    described v5e, from shapes alone. Its bytes lie well under the chip
    (the trainer's step adds the moments and the update: 8.69 GB by
    ``benchmark.rehearse``), and its Mosaic calls are pinned: the four
    attention layers' forward kernels, the three sliding layers' fused
    backward and the full layer's dK/dV and dQ (dQ's float32 slots for
    its query heads over 8,192 positions do not fit the budget), and 24
    grouped products an expert layer (forward 3 for the first buffer of
    32,768 rows and 3 in the loop's body after it, over buffers of
    8,192; the first buffer's backward 9, the recomputed forward among
    them; the loop's body after it 9)."""
    import json
    from paddle_tpu import models
    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.trainer.trainer import Topology
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2_12b_a2b5_ep8.json")) as f:
        cfg = json.load(f)
    dsl.reset()
    cost = models.mellum2(**cfg["model"]["args"])[0]
    net = Topology(cost).network
    f32 = {n for n, spec in net.param_specs.items() if spec.compute_f32}

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {n: sd(v.shape, jnp.float32) for n, v in jax.eval_shape(
        net.init_params, jax.random.PRNGKey(0)).items()}

    def step(params, ids, mask):
        def loss(p):
            p = {n: v if n in f32 else v.astype(jnp.bfloat16)
                 for n, v in p.items()}
            out = net.apply(p, {"words": Argument(value=ids, mask=mask)},
                            train=True)
            return jnp.mean(out[cost.name].value.astype(jnp.float32))
        return jax.value_and_grad(loss)(params)

    with common.record_dispatch() as tally:
        compiled = _compile(step, params, sd((2, 8192), jnp.int32),
                            sd((2, 8192), jnp.float32))
    # each grouped product's three calls at their own tiles: wg and wu
    # (2,304 -> 896) and wd (896 -> 2,304), forward traced twice a buffer
    assert tally == {"flash_attention": {"pallas": 4},
                     "flash_backward": {"fused": 3, "split": 1},
                     "moe_grouped_matmul": {"pallas": 48},
                     "moe_gmm_tiles": {
                         "fwd 512x1152x896": 32, "fwd 512x896x1152": 16,
                         "dlhs 512x896x1152": 16, "dlhs 512x1152x896": 8,
                         "drhs 512x768x896": 16, "drhs 512x896x1152": 8}}
    assert compiled.as_text().count(_MOSAIC) == 4 + 3 + 2 + 4 * 24
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert held < 0.75 * 16_909_336_064, held
