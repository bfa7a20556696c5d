"""The language-model cells' kernels, and the optimizer's update of one
leaf, compiled for a described TPU v5e at the widths the cells run
them, with no chip: Mosaic refuses here what it would refuse there (a
slice off the tiling, too much VMEM), and the compiled text shows what
the chip's compiler makes of plain jnp. Nothing runs; a compile that
passes is not a chip run. All in this one file:
the worker that gets it is the one process that loads the TPU's
compiler."""

import math
import re

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
    512 x 512 tiles: the forward and the two backward kernels."""
    from paddle_tpu.ops.attention import flash_attention

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def step(q, k, v, g):
        return jax.vjp(lambda *a: flash_attention(
            *a, None, causal=True, block_q=512, block_k=512), q, k, v)[1](g)

    compiled = _compile(step, sd(2, 32, 4096, 192), sd(2, 32, 4096, 192),
                        sd(2, 32, 4096, 128), sd(2, 32, 4096, 128))
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_recomputed_latent_layer_runs_the_forward_kernel_once(one_chip):
    """The cell's attention layer (2 x 4,096 tokens of 2,048, 32 heads,
    192/128 core at 512 x 512 tiles) marked `recompute`, through the
    executor: its forward and backward hold the forward kernel, dK/dV
    and dQ, and no second forward kernel for the recomputation."""
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
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)])
def test_grouped_query_cores_forward_and_backward(one_chip, heads, window):
    """The Laguna cell's two cores: 64 query heads with a window of 512
    and 48 without, over 8 key-value heads of 128, 8,192 positions,
    512 x 512 tiles: the forward kernel, dK/dV with its sweep over a
    group's 8 or 6 query heads, and dQ; K and V go in at 8 heads."""
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
    assert tally["flash_attention"] == {"pallas": 1}
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3
    dq, dk, dv = compiled.out_info
    assert dq.shape == (1, heads, 8192, 128)
    assert dk.shape == dv.shape == (1, 8, 8192, 128)


def test_recomputed_sliding_layer_runs_the_forward_kernel_once(one_chip):
    """The cell's sliding layer (8,192 tokens of 2,048, 64 heads over 8
    key-value heads, a window of 512) marked `recompute`, through the
    executor: forward kernel, dK/dV and dQ, no second forward kernel."""
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
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3


def test_looped_step_runs_each_cores_forward_kernel_once(one_chip):
    """A small looped decoder whole (``models.ouro``: 2 layers run 3
    times over one copy of their weights, 2 heads of 128 over 1,024
    tokens of 256, ``recompute`` on), cost and every leaf's gradient:
    three Mosaic calls an application of an attention layer (forward,
    dK/dV, dQ) and no forward kernel a second time for a recomputation
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
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3 * layers * passes


def test_routed_experts_forward_and_backward(one_chip):
    """8 held experts of 256, 8 a token, 8,192 tokens of 2,048, width
    768: the loop over buffers of 4,096 rows, forward and backward."""
    from paddle_tpu.parallel.moe import routed_experts

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(x, wg, wu, wd, ids, gates, dy):
        def f(x, wg, wu, wd, gates):
            return routed_experts(x, wg, wu, wd, ids, gates,
                                  n_experts=256, offset=8)[0]
        return jax.vjp(f, x, wg, wu, wd, gates)[1](dy)

    compiled = _compile(
        step, sd((8192, 2048)), sd((8, 2048, 768)), sd((8, 2048, 768)),
        sd((8, 768, 2048)), sd((8192, 8), jnp.int32),
        sd((8192, 8), jnp.float32), sd((8192, 2048)))
    assert compiled.as_text().count("tpu_custom_call") >= 9


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
