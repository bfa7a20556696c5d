"""The full layer gradient/forward matrix.

The reference checks every registered layer with
``paddle/gserver/tests/test_LayerGrad.cpp`` (79 TESTs over
``REGISTER_LAYER`` types). This is the same closure property, enforced
mechanically: ``test_registry_fully_covered`` fails the moment a layer
type is registered without a matrix entry. Differentiable types get a
numeric-vs-analytic gradient check; non-differentiable outputs (argmax,
ids, NMS...) get a forward/shape check; group/driver types point at their
dedicated test files.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.layers  # noqa: F401 — trigger registrations
from paddle_tpu.config import dsl
from paddle_tpu.config.model_config import Input, LayerDef
from paddle_tpu.core.argument import Argument
from paddle_tpu.core.network import Network
from paddle_tpu.core.registry import _LAYER_REGISTRY

EPS, RTOL, ATOL = 1e-3, 3e-2, 6e-2


def _rng(seed=0):
    return np.random.RandomState(seed)


def _dense(b=3, d=6, seed=0, positive=False):
    v = _rng(seed).randn(b, d).astype(np.float32)
    if positive:
        v = np.abs(v) + 0.5
    return Argument(value=jnp.asarray(v))


def _labels(b=3, classes=4, seed=1):
    return Argument(value=jnp.asarray(
        _rng(seed).randint(0, classes, size=b).astype(np.int32)))


def _seq(b=3, t=5, d=6, seed=0, full=False, positive=False):
    r = _rng(seed)
    mask = np.ones((b, t), np.float32)
    if not full:
        for i, L in enumerate(r.randint(2, t + 1, size=b)):
            mask[i, L:] = 0.0
    v = r.randn(b, t, d).astype(np.float32)
    if positive:
        v = np.abs(v) + 0.5
    v = v * mask[..., None]
    return Argument(value=jnp.asarray(v), mask=jnp.asarray(mask))


def _seq_ids(b=3, t=5, classes=4, seed=2, full=True):
    r = _rng(seed)
    mask = np.ones((b, t), np.float32)
    ids = r.randint(0, classes, size=(b, t)).astype(np.int32)
    return Argument(value=jnp.asarray(ids), mask=jnp.asarray(mask))


def _img(b=2, c=2, h=6, w=6, seed=0):
    return Argument(value=jnp.asarray(
        _rng(seed).randn(b, h, w, c).astype(np.float32)))


def L(name, type_, inputs, **kw):
    """Shorthand LayerDef builder used by the case table."""
    ins = [Input(i) if isinstance(i, str) else i for i in inputs]
    return LayerDef(name=name, type=type_, inputs=ins,
                    size=kw.pop("size", None), act=kw.pop("act", "linear"),
                    bias=kw.pop("bias", False), attrs=kw)


# ---------------------------------------------------------------- the matrix
# type -> (data_defs, layer_def, feed) builders. data_defs: list of
# (name, size, kwargs) for dsl.data.
def _case_fc():
    return [("x", 6, {})], L("out", "fc", ["x"], size=4, act="tanh",
                             bias=True), {"x": _dense()}


def _case_embedding():
    return ([("x", 5, {"is_sequence": True})],
            L("out", "embedding", ["x"], size=4, vocab_size=5),
            {"x": _seq_ids(classes=5)})


def _case_conv():
    ld = L("out", "exconv", [Input("x", extra={"filter_size": 3, "stride": 1,
                                               "padding": 1, "channels": 2})],
           act="relu", bias=True, num_filters=3)
    return ([("x", 72, {"channels": 2, "height": 6, "width": 6})],
            ld, {"x": _img()})


def _case_convt():
    ld = L("out", "exconvt", [Input("x", extra={"filter_size": 3, "stride": 2,
                                                "padding": 1, "channels": 2})],
           bias=True, num_filters=3)
    return ([("x", 32, {"channels": 2, "height": 4, "width": 4})],
            ld, {"x": _img(h=4, w=4)})


def _case_pool():
    ld = L("out", "pool", [Input("x", extra={"filter_size": 2, "stride": 2,
                                             "pool_type": "max-projection"})])
    return ([("x", 72, {"channels": 2, "height": 6, "width": 6})],
            ld, {"x": _img()})


def _case_norm():
    ld = L("out", "norm", [Input("x", extra={"size": 3, "scale": 1e-2,
                                             "pow": 0.75})])
    return ([("x", 72, {"channels": 2, "height": 6, "width": 6})],
            ld, {"x": _img()})


def _case_batch_norm():
    return ([("x", 5, {})], L("out", "batch_norm", ["x"], act="relu",
                              bias=True), {"x": _dense(d=5)})


def _case_addto():
    return ([("a", 6, {}), ("b", 6, {})],
            L("out", "addto", ["a", "b"], act="tanh"),
            {"a": _dense(), "b": _dense(seed=1)})


def _case_concat():
    return ([("a", 6, {}), ("b", 4, {})],
            L("out", "concat", ["a", "b"]),
            {"a": _dense(), "b": _dense(d=4, seed=1)})


def _case_concat2():
    ld = L("out", "concat2", ["a", "b"], size=8, act="tanh",
           projections=[{"type": "full_matrix", "size": 4},
                        {"type": "identity", "size": 4}])
    return ([("a", 6, {}), ("b", 4, {})], ld,
            {"a": _dense(), "b": _dense(d=4, seed=1)})


def _case_mixed():
    ld = L("out", "mixed", ["a", "b"], size=4, act="tanh",
           projections=[{"type": "full_matrix"}, {"type": "dot_mul"}])
    return ([("a", 6, {}), ("b", 4, {})], ld,
            {"a": _dense(), "b": _dense(d=4, seed=1)})


def _case_lstmemory():
    return ([("x", 12, {"is_sequence": True})],
            L("out", "lstmemory", ["x"], bias=True), {"x": _seq(d=12)})


def _case_gru():
    return ([("x", 9, {"is_sequence": True})],
            L("out", "gated_recurrent", ["x"], bias=True), {"x": _seq(d=9)})


def _case_recurrent():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "recurrent", ["x"], bias=True,
              active_type="tanh"), {"x": _seq()})


def _case_mdlstm():
    return ([("x", 4 * 4 * 10, {"channels": 10, "height": 4, "width": 4,
                                "is_sequence": False})],
            L("out", "mdlstmemory", [Input("x", extra={"channels": 10})],
              size=2, bias=True),
            {"x": Argument(value=jnp.asarray(
                _rng(3).randn(2, 4, 4, 10).astype(np.float32)))})


def _case_gru_step():
    return ([("x", 9, {}), ("m", 3, {})],
            L("out", "gru_step", ["x", "m"], size=3, bias=True),
            {"x": _dense(d=9), "m": _dense(d=3, seed=1)})


def _case_lstm_step():
    return ([("x", 12, {}), ("c", 3, {})],
            L("out", "lstm_step", ["x", "c"], size=3, bias=True),
            {"x": _dense(d=12), "c": _dense(d=3, seed=1)})


def _case_max():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "max", ["x"]), {"x": _seq()})


def _case_average():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "average", ["x"]), {"x": _seq()})


def _case_seqlastins():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "seqlastins", ["x"]), {"x": _seq()})


def _case_seqreshape():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "seqreshape", ["x"], size=3), {"x": _seq(full=True)})


def _case_seqconcat():
    return ([("a", 6, {"is_sequence": True}),
             ("b", 6, {"is_sequence": True})],
            L("out", "seqconcat", ["a", "b"]),
            {"a": _seq(), "b": _seq(seed=1)})


def _case_expand():
    return ([("v", 6, {}), ("ref", 4, {"is_sequence": True})],
            L("out", "expand", ["v", "ref"]),
            {"v": _dense(), "ref": _seq(d=4, seed=1)})


def _case_featmap_expand():
    return ([("x", 6, {})],
            L("out", "featmap_expand", ["x"], num_filters=3),
            {"x": _dense()})


def _case_interpolation():
    return ([("w", 1, {}), ("a", 6, {}), ("b", 6, {})],
            L("out", "interpolation", ["w", "a", "b"]),
            {"w": Argument(value=jnp.asarray(
                _rng(2).rand(3, 1).astype(np.float32))),
             "a": _dense(), "b": _dense(seed=1)})


def _case_power():
    return ([("w", 1, {}), ("x", 6, {})],
            L("out", "power", ["w", "x"]),
            {"w": Argument(value=jnp.asarray(
                np.full((3, 1), 2.0, np.float32))),
             "x": _dense(positive=True)})


def _case_scaling():
    return ([("w", 1, {}), ("x", 6, {})],
            L("out", "scaling", ["w", "x"]),
            {"w": _dense(d=1, seed=2), "x": _dense()})


def _case_slope_intercept():
    return ([("x", 6, {})],
            L("out", "slope_intercept", ["x"], slope=2.0, intercept=1.0),
            {"x": _dense()})


def _case_clip():
    return ([("x", 6, {})],
            L("out", "clip", ["x"], min=-0.5, max=0.5), {"x": _dense()})


def _case_sum_to_one_norm():
    return ([("x", 6, {})], L("out", "sum_to_one_norm", ["x"]),
            {"x": _dense(positive=True)})


def _case_row_l2_norm():
    return ([("x", 6, {})], L("out", "row_l2_norm", ["x"]), {"x": _dense()})


def _case_cos():
    return ([("a", 6, {}), ("b", 6, {})],
            L("out", "cos", ["a", "b"], cos_scale=1.0),
            {"a": _dense(), "b": _dense(seed=1)})


def _case_cos_vm():
    return ([("a", 4, {}), ("b", 12, {})],
            L("out", "cos_vm", ["a", "b"], size=3, cos_scale=1.0),
            {"a": _dense(d=4), "b": _dense(d=12, seed=1)})


def _case_convex_comb():
    return ([("w", 3, {}), ("v", 12, {})],
            L("out", "convex_comb", ["w", "v"], size=4),
            {"w": _dense(d=3), "v": _dense(d=12, seed=1)})


def _case_trans():
    return ([("x", 6, {})], L("out", "trans", ["x"]),
            {"x": _dense(b=6, d=6)})


def _case_rotate():
    return ([("x", 32, {"channels": 2, "height": 4, "width": 4})],
            L("out", "rotate", ["x"]), {"x": _img(c=2, h=4, w=4)})


def _case_resize():
    return ([("x", 6, {})], L("out", "resize", ["x"], size=3),
            {"x": _dense(b=2, d=6)})


def _case_pad():
    return ([("x", 32, {"channels": 2, "height": 4, "width": 4})],
            L("out", "pad", ["x"], pad_c=[1, 1], pad_h=[0, 1],
              pad_w=[1, 0]),
            {"x": _img(c=2, h=4, w=4)})


def _case_crop():
    return ([("x", 32, {"channels": 2, "height": 4, "width": 4})],
            L("out", "crop", ["x"], axis=2, offset=[1, 1], shape=[2, 2]),
            {"x": _img(c=2, h=4, w=4)})


def _case_maxout():
    return ([("x", 72, {"channels": 2, "height": 6, "width": 6})],
            L("out", "maxout", ["x"], groups=2), {"x": _img()})


def _case_blockexpand():
    return ([("x", 32, {"channels": 2, "height": 4, "width": 4})],
            L("out", "blockexpand", ["x"], block_x=2, block_y=2,
              stride_x=2, stride_y=2, channels=2),
            {"x": _img(c=2, h=4, w=4)})


def _case_spp():
    return ([("x", 72, {"channels": 2, "height": 6, "width": 6})],
            L("out", "spp", ["x"], pyramid_height=2,
              pool_type="max-projection", channels=2), {"x": _img()})


def _case_bilinear():
    return ([("x", 32, {"channels": 2, "height": 4, "width": 4})],
            L("out", "bilinear_interp", ["x"], out_size_x=8, out_size_y=8),
            {"x": _img(c=2, h=4, w=4)})


def _case_row_conv():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "row_conv", ["x"], context_length=2), {"x": _seq()})


def _case_conv_shift():
    return ([("a", 7, {}), ("b", 3, {})],
            L("out", "conv_shift", ["a", "b"]),
            {"a": _dense(d=7), "b": _dense(d=3, seed=1)})


def _case_tensor():
    return ([("a", 4, {}), ("b", 5, {})],
            L("out", "tensor", ["a", "b"], size=3, bias=True),
            {"a": _dense(d=4), "b": _dense(d=5, seed=1)})


def _case_selective_fc():
    sel = np.zeros((3, 4), np.float32)
    sel[:, :2] = 1.0
    return ([("x", 6, {}), ("sel", 4, {})],
            L("out", "selective_fc", ["x", "sel"], size=4, bias=True,
              active_type="tanh"),
            {"x": _dense(), "sel": Argument(value=jnp.asarray(sel))})


def _case_prelu():
    return ([("x", 6, {})], L("out", "prelu", ["x"]), {"x": _dense()})


def _case_multi_head_attention():
    return ([("x", 8, {"is_sequence": True})],
            L("out", "multi_head_attention", ["x"], size=8, num_heads=2),
            {"x": _seq(d=8)})


def _case_mla_attention():
    return ([("x", 8, {"is_sequence": True})],
            L("out", "mla_attention", ["x"], num_heads=2, q_lora_rank=6,
              kv_lora_rank=4, qk_nope_head_dim=4, qk_rope_head_dim=2,
              v_head_dim=4, rope_theta=100.0),
            {"x": _seq(d=8)})


def _case_gqa_attention():
    return ([("x", 8, {"is_sequence": True})],
            L("out", "gqa_attention", ["x"], num_heads=4, num_kv_heads=2,
              head_dim=4, window=3, rotary_dim=2, rope_theta=100.0,
              yarn={"factor": 8.0, "original_max_position_embeddings": 4,
                    "beta_fast": 4.0, "beta_slow": 1.0}),
            {"x": _seq(d=8)})


def _case_short_conv():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "short_conv", ["x"], kernel=3), {"x": _seq()})


def _case_rms_norm():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "rms_norm", ["x"]), {"x": _seq()})


def _case_swiglu():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "swiglu", ["x"], hidden=5), {"x": _seq()})


def _case_seq_shift():
    return ([("x", 6, {"is_sequence": True})],
            L("out", "seq_shift", ["x"], offset=1), {"x": _seq(full=True)})


def _case_lm_cost():
    return ([("x", 6, {"is_sequence": True}),
             ("ids", 4, {"is_sequence": True})],
            L("out", "lm_cost", ["x", "ids"], vocab_size=4, shift=1,
              coeff=0.7, chunk=4),
            {"x": _seq(full=True), "ids": _seq_ids()})


def _case_looped_lm_cost():
    return ([("x1", 6, {"is_sequence": True}),
             ("x2", 6, {"is_sequence": True}),
             ("x3", 6, {"is_sequence": True}),
             ("ids", 4, {"is_sequence": True})],
            L("out", "looped_lm_cost", ["x1", "x2", "x3", "ids"],
              vocab_size=4, shift=1, beta=0.1, chunk=4),
            {"x1": _seq(full=True), "x2": _seq(full=True),
             "x3": _seq(full=True), "ids": _seq_ids()})


def _case_agent():
    return ([("x", 6, {})], L("out", "agent", ["x"]), {"x": _dense()})


# costs ------------------------------------------------------------------
def _case_xent():
    return ([("p", 4, {}), ("y", 4, {})],
            L("out", "multi-class-cross-entropy", ["p", "y"]),
            {"p": Argument(value=jax.nn.softmax(jnp.asarray(
                _rng(0).randn(3, 4).astype(np.float32)))),
             "y": _labels()})


def _case_xent_selfnorm():
    return ([("p", 4, {}), ("y", 4, {})],
            L("out", "multi_class_cross_entropy_with_selfnorm", ["p", "y"],
              softmax_selfnorm_alpha=0.1),
            {"p": _dense(d=4, positive=True), "y": _labels()})


def _case_soft_xent():
    t = _rng(1).rand(3, 4).astype(np.float32)
    return ([("p", 4, {}), ("y", 4, {})],
            L("out", "soft_binary_class_cross_entropy", ["p", "y"]),
            {"p": Argument(value=jax.nn.sigmoid(jnp.asarray(
                _rng(0).randn(3, 4).astype(np.float32)))),
             "y": Argument(value=jnp.asarray(t))})


def _case_multi_binary_xent():
    t = (_rng(1).rand(3, 4) > 0.5).astype(np.float32)
    return ([("p", 4, {}), ("y", 4, {})],
            L("out", "multi_binary_label_cross_entropy", ["p", "y"]),
            {"p": Argument(value=jax.nn.sigmoid(jnp.asarray(
                _rng(0).randn(3, 4).astype(np.float32)))),
             "y": Argument(value=jnp.asarray(t))})


def _case_square_error():
    return ([("p", 4, {}), ("y", 4, {})],
            L("out", "square_error", ["p", "y"]),
            {"p": _dense(d=4), "y": _dense(d=4, seed=1)})


def _case_smooth_l1():
    return ([("p", 4, {}), ("y", 4, {})],
            L("out", "smooth_l1", ["p", "y"]),
            {"p": _dense(d=4), "y": _dense(d=4, seed=1)})


def _case_huber():
    return ([("p", 1, {}), ("y", 1, {})],
            L("out", "huber_classification", ["p", "y"]),
            {"p": _dense(d=1),
             "y": Argument(value=jnp.asarray(
                 _rng(1).randint(0, 2, size=3).astype(np.int32)))})


def _case_rank_cost():
    return ([("l", 1, {}), ("r", 1, {}), ("y", 1, {})],
            L("out", "rank-cost", ["l", "r", "y"]),
            {"l": _dense(d=1), "r": _dense(d=1, seed=1),
             "y": Argument(value=jnp.asarray(
                 _rng(2).randint(0, 2, size=(3, 1)).astype(np.float32)))})


def _case_lambda_cost():
    # one "sample" per list: per-timestep scores + relevance labels
    rel = _rng(1).rand(3, 5, 1).astype(np.float32)
    s = _seq(d=1, t=5, seed=0)
    return ([("s", 1, {"is_sequence": True}),
             ("y", 1, {"is_sequence": True})],
            L("out", "lambda_cost", ["s", "y"], NDCG_num=3),
            {"s": s, "y": Argument(value=jnp.asarray(rel), mask=s.mask)})


def _case_sum_cost():
    return ([("x", 4, {})], L("out", "sum_cost", ["x"]),
            {"x": _dense(d=4)})


def _case_crf():
    return ([("x", 4, {"is_sequence": True}), ("y", 4,
                                               {"is_sequence": True})],
            L("out", "crf", ["x", "y"]),
            {"x": _seq(d=4, full=True), "y": _seq_ids(classes=4)})


def _case_ctc():
    return ([("x", 5, {"is_sequence": True}), ("y", 4,
                                               {"is_sequence": True})],
            L("out", "ctc", ["x", "y"], blank=4),
            {"x": _seq(d=5, t=8, full=True),
             "y": _seq_ids(t=3, classes=4)})


def _case_sample_gaussian():
    return ([("mu", 4, {}), ("lv", 4, {})],
            L("out", "sample_gaussian", ["mu", "lv"]),
            {"mu": _dense(d=4), "lv": _dense(d=4, seed=1)})


def _case_kl_gaussian():
    return ([("mu", 4, {}), ("lv", 4, {})],
            L("out", "kl_gaussian", ["mu", "lv"]),
            {"mu": _dense(d=4), "lv": _dense(d=4, seed=1)})


def _case_nce():
    return ([("x", 6, {}), ("y", 8, {})],
            L("out", "nce", ["x", "y"], bias=True, num_classes=8,
              num_neg_samples=4),
            {"x": _dense(), "y": _labels(classes=8)})


def _case_hsigmoid():
    return ([("x", 6, {}), ("y", 8, {})],
            L("out", "hsigmoid", ["x", "y"], bias=True, num_classes=8),
            {"x": _dense(), "y": _labels(classes=8)})


# forward-only (non-differentiable outputs) ------------------------------
def _case_maxid():
    return ([("x", 6, {})], L("out", "maxid", ["x"]), {"x": _dense()})


def _case_eos_id():
    return ([("x", 1, {"is_sequence": True})],
            L("out", "eos_id", ["x"], eos_id=1),
            {"x": _seq_ids(classes=3)})


def _case_sampling_id():
    return ([("x", 4, {})],
            L("out", "sampling_id", ["x"]),
            {"x": Argument(value=jax.nn.softmax(jnp.asarray(
                _rng(0).randn(3, 4).astype(np.float32))))})


def _case_kmax():
    return ([("x", 1, {"is_sequence": True})],
            L("out", "kmax_seq_score", ["x"], beam_size=2),
            {"x": _seq(d=1)})


def _case_crf_decoding():
    return ([("x", 4, {"is_sequence": True})],
            L("out", "crf_decoding", ["x"]), {"x": _seq(d=4, full=True)})


def _case_multiplex():
    idx = np.array([0, 1, 0], np.int32)
    return ([("i", 1, {}), ("a", 6, {}), ("b", 6, {})],
            L("out", "multiplex", ["i", "a", "b"]),
            {"i": Argument(value=jnp.asarray(idx)),
             "a": _dense(), "b": _dense(seed=1)})


def _case_priorbox():
    return ([("x", 32, {"channels": 2, "height": 4, "width": 4}),
             ("img", 48, {"channels": 3, "height": 4, "width": 4})],
            L("out", "priorbox", ["x", "img"], min_size=[2],
              max_size=[], aspect_ratio=[1.0], variance=[0.1] * 4),
            {"x": _img(c=2, h=4, w=4), "img": _img(c=3, h=4, w=4)})


def _case_print():
    return ([("x", 4, {})], L("out", "print", ["x"]), {"x": _dense(d=4)})


def _case_scatter_agent():
    # wired identity (inside an expanded sub-model it is an input-less
    # feed slot; tests/test_proto_import.py covers that execution mode)
    return ([("x", 6, {})], L("out", "scatter_agent", ["x"]),
            {"x": _dense()})


def _case_gather_agent():
    # two wired sequence inputs concatenate along time
    return ([("x", 6, {"is_sequence": True}),
             ("y", 6, {"is_sequence": True})],
            L("out", "gather_agent", ["x", "y"]),
            {"x": _seq(), "y": _seq(seed=3)})


def _case_out_prod():
    return ([("x", 3, {}), ("y", 4, {})],
            L("out", "out_prod", ["x", "y"]),
            {"x": _dense(d=3), "y": _dense(d=4, seed=5)})


def _case_data_norm():
    from paddle_tpu.config.model_config import ParamAttr
    # random (non-zero) stats via the input param_attr so every strategy
    # scales by something; the 5xD parameter itself is static
    attr = ParamAttr(init="normal", initial_mean=0.1, initial_std=0.5)
    return ([("x", 6, {})],
            L("out", "data_norm", [Input("x", param_attr=attr)],
              data_norm_strategy="z-score"),
            {"x": _dense()})


def _case_subseq():
    b, t = 3, 6
    off = Argument(value=jnp.asarray([0, 1, 2], jnp.int32))
    n = Argument(value=jnp.asarray([3, 2, 4], jnp.int32))
    return ([("x", 5, {"is_sequence": True}), ("off", 1, {}), ("n", 1, {})],
            L("out", "subseq", ["x", "off", "n"]),
            {"x": _seq(b=b, t=t, d=5, full=True), "off": off, "n": n})


GRAD_CASES = {
    "fc": _case_fc, "embedding": _case_embedding, "exconv": _case_conv,
    "exconvt": _case_convt, "pool": _case_pool, "norm": _case_norm,
    "batch_norm": _case_batch_norm, "addto": _case_addto,
    "concat": _case_concat,
    "concat2": _case_concat2, "mixed": _case_mixed,
    "lstmemory": _case_lstmemory, "gated_recurrent": _case_gru,
    "recurrent": _case_recurrent, "mdlstmemory": _case_mdlstm,
    "gru_step": _case_gru_step, "lstm_step": _case_lstm_step,
    "max": _case_max, "average": _case_average,
    "seqlastins": _case_seqlastins, "seqreshape": _case_seqreshape,
    "seqconcat": _case_seqconcat, "expand": _case_expand,
    "featmap_expand": _case_featmap_expand,
    "interpolation": _case_interpolation, "power": _case_power,
    "scaling": _case_scaling, "slope_intercept": _case_slope_intercept,
    "clip": _case_clip, "sum_to_one_norm": _case_sum_to_one_norm,
    "row_l2_norm": _case_row_l2_norm, "cos": _case_cos,
    "cos_vm": _case_cos_vm, "convex_comb": _case_convex_comb,
    "trans": _case_trans, "rotate": _case_rotate, "resize": _case_resize,
    "pad": _case_pad, "crop": _case_crop, "maxout": _case_maxout,
    "blockexpand": _case_blockexpand, "spp": _case_spp,
    "bilinear_interp": _case_bilinear, "row_conv": _case_row_conv,
    "conv_shift": _case_conv_shift, "tensor": _case_tensor,
    "selective_fc": _case_selective_fc, "prelu": _case_prelu,
    "multi_head_attention": _case_multi_head_attention,
    "mla_attention": _case_mla_attention,
    "gqa_attention": _case_gqa_attention, "rms_norm": _case_rms_norm,
    "swiglu": _case_swiglu, "seq_shift": _case_seq_shift,
    "short_conv": _case_short_conv,
    "lm_cost": _case_lm_cost, "looped_lm_cost": _case_looped_lm_cost,
    "agent": _case_agent,
    "scatter_agent": _case_scatter_agent,
    "gather_agent": _case_gather_agent,
    "out_prod": _case_out_prod, "data_norm": _case_data_norm,
    "subseq": _case_subseq,
    # costs
    "multi-class-cross-entropy": _case_xent,
    "multi_class_cross_entropy_with_selfnorm": _case_xent_selfnorm,
    "soft_binary_class_cross_entropy": _case_soft_xent,
    "multi_binary_label_cross_entropy": _case_multi_binary_xent,
    "square_error": _case_square_error, "smooth_l1": _case_smooth_l1,
    "huber_classification": _case_huber, "rank-cost": _case_rank_cost,
    "lambda_cost": _case_lambda_cost, "sum_cost": _case_sum_cost,
    "crf": _case_crf, "ctc": _case_ctc, "nce": _case_nce,
    "hsigmoid": _case_hsigmoid, "sample_gaussian": _case_sample_gaussian,
    "kl_gaussian": _case_kl_gaussian,
}

FWD_CASES = {
    "maxid": _case_maxid, "eos_id": _case_eos_id,
    "sampling_id": _case_sampling_id, "kmax_seq_score": _case_kmax,
    "crf_decoding": _case_crf_decoding, "multiplex": _case_multiplex,
    "priorbox": _case_priorbox, "print": _case_print,
}

# types whose behavior needs richer scaffolding than a one-layer net; each
# points at the dedicated test file exercising it
COVERED_ELSEWHERE = {
    "data": "fed directly by every test",
    "moe": "tests/test_moe.py (the top-k choice is piecewise constant, "
           "so a numeric difference can cross a boundary; the layer's "
           "gradients are compared there with the plain reference's, "
           "leaf by leaf, with sharded parity and the no-drop case)",
    "moe_balance_cost": "tests/test_mellum_model.py (it reads the router "
                        "statistics that expert layers built with "
                        "balance hand on; its value and its gradient to "
                        "every router logit against a hand count and the "
                        "plain reference)",
    "recurrent_layer_group": "tests/test_recurrent_group.py",
    "beam_search_group": "tests/test_generation.py, tests/test_seq_models.py",
    "group_output": "tests/test_recurrent_group.py",
    "get_output": "tests/test_misc_layers.py (lstm_step + get_output)",
    "sub_nested_seq": "tests/test_misc_layers.py (nested selection)",
    "detection_output": "tests/test_misc_layers.py (detection stack)",
    "multibox_loss": "tests/test_misc_layers.py (detection stack)",
}


def test_registry_fully_covered():
    """Every canonical registered layer type has a matrix entry."""
    canonical = {impl.type_name for impl in _LAYER_REGISTRY.values()}
    covered = set(GRAD_CASES) | set(FWD_CASES) | set(COVERED_ELSEWHERE)
    missing = canonical - covered
    assert not missing, f"layer types without a grad/forward test: {missing}"
    stale = covered - canonical
    assert not stale, f"matrix entries for unregistered types: {stale}"


def _build(case):
    dsl.reset()
    data_defs, ld, feed = case()
    for name, size, kw in data_defs:
        dsl.data(name=name, size=size, **kw)
    dsl.current_graph().add(ld)
    net = Network(dsl.current_graph(), outputs=[ld.name])
    params = net.init_params(jax.random.PRNGKey(0))
    return net, ld, params, feed


@pytest.mark.parametrize("type_name", sorted(GRAD_CASES))
def test_layer_grad(type_name):
    net, ld, params, feed = _build(GRAD_CASES[type_name])
    rng = _rng(7)
    out0 = net.apply(params, feed, train=False,
                     rng=jax.random.PRNGKey(0))[ld.name]
    w = jnp.asarray(rng.randn(*out0.value.shape).astype(np.float32))

    def loss_fn(p, f):
        out = net.apply(p, f, train=False, rng=jax.random.PRNGKey(0))
        return jnp.sum(out[ld.name].value * w)

    # parameters
    analytic = jax.grad(loss_fn)(params, feed)
    for name, g in analytic.items():
        if net.param_specs[name].is_static:
            continue
        p0 = np.asarray(params[name], np.float64)
        for idx in rng.choice(p0.size, size=min(4, p0.size), replace=False):
            d = np.zeros(p0.size)
            d[idx] = EPS
            d = d.reshape(p0.shape)
            pp = dict(params)
            pp[name] = jnp.asarray(p0 + d, jnp.float32)
            pm = dict(params)
            pm[name] = jnp.asarray(p0 - d, jnp.float32)
            num = (float(loss_fn(pp, feed)) - float(loss_fn(pm, feed))) \
                / (2 * EPS)
            ana = float(np.asarray(g).reshape(-1)[idx])
            assert num == pytest.approx(ana, rel=RTOL, abs=ATOL), (
                f"{type_name} param {name}[{idx}]: {num} vs {ana}")

    # first float input
    for in_name, a in feed.items():
        if not np.issubdtype(np.asarray(a.value).dtype, np.floating):
            continue
        ga = jax.grad(
            lambda v: loss_fn(params, {
                **feed, in_name: Argument(value=v, mask=a.mask,
                                          sub_starts_mask=a.sub_starts_mask)
            }))(a.value)
        v0 = np.asarray(a.value, np.float64)
        live = (np.broadcast_to(np.asarray(a.mask)[..., None], v0.shape)
                .reshape(-1) > 0 if a.mask is not None
                else np.ones(v0.size, bool))
        choices = np.flatnonzero(live)
        for idx in rng.choice(choices, size=min(4, len(choices)),
                              replace=False):
            d = np.zeros(v0.size)
            d[idx] = EPS
            d = d.reshape(v0.shape)
            fp = {**feed, in_name: Argument(value=jnp.asarray(
                v0 + d, jnp.float32), mask=a.mask)}
            fm = {**feed, in_name: Argument(value=jnp.asarray(
                v0 - d, jnp.float32), mask=a.mask)}
            num = (float(loss_fn(params, fp)) - float(loss_fn(params, fm))) \
                / (2 * EPS)
            ana = float(np.asarray(ga).reshape(-1)[idx])
            assert num == pytest.approx(ana, rel=RTOL, abs=ATOL), (
                f"{type_name} input {in_name}[{idx}]: {num} vs {ana}")
        break


@pytest.mark.parametrize("type_name", sorted(FWD_CASES))
def test_layer_forward(type_name):
    net, ld, params, feed = _build(FWD_CASES[type_name])
    out = net.apply(params, feed, train=False,
                    rng=jax.random.PRNGKey(0))[ld.name]
    v = np.asarray(out.value)
    if type_name != "priorbox":  # priorbox emits per-prior rows, no batch
        assert v.shape[0] == next(iter(feed.values())).value.shape[0]
    assert np.all(np.isfinite(v.astype(np.float64)))
