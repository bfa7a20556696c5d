"""Plain reference of ResNet-50 (He et al., arXiv:1512.03385, table 1):
7x7/2 stem, 3x3/2 max-pool, bottleneck stages [3,4,6,3] with projection
shortcuts where the shape changes and the stride on the first 1x1,
batch-norm after every convolution, global average pool, fc softmax.

float32, ``highest``, NHWC. Each bottleneck block (and the stem) is a
``jax.checkpoint``: batch-norm needs the whole batch's statistics, so it
is recomputation by block, not micro-batching, that lets batch 256 in
float32 fit a 16 GB chip. Departures from the paper, both the program's
and stated in the configuration: the max-pool rounds its output size up
(caffe's rule, 112 -> 57 and so 29, 15, 8 after it), padding on the far
side with -inf; images arrive as channel-major rows [B, C*H*W].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STAGES = (3, 4, 6, 3)
EPS = 1e-5


def _units(cfg):
    """(name, filter, in, out, stride) of every conv+bn unit, in order."""
    m = cfg["model"]["args"]
    width, c = m["width"], m["channels"]
    units = [("stem", 7, c, width, 2)]
    nf, cin = width, width
    for stage, n in enumerate(STAGES):
        for i in range(n):
            name = f"res{stage + 2}{chr(ord('a') + i)}"
            stride = 2 if (stage > 0 and i == 0) else 1
            units += [(f"{name}_a", 1, cin, nf, stride),
                      (f"{name}_b", 3, nf, nf, 1),
                      (f"{name}_c", 1, nf, nf * 4, 1)]
            if i == 0:
                units.append((f"{name}_sc", 1, cin, nf * 4, stride))
            cin = nf * 4
        nf *= 2
    return units, cin


def leaves(cfg):
    units, cin = _units(cfg)
    # the scale of each bottleneck's last batch-norm (the paper: 1; Goyal
    # et al., arXiv:1706.02677: 0); see the configuration's ``assumed``
    last = f"const:{cfg['model'].get('residual_bn_scale', 1.0)}"
    out = {}
    for name, fs, ci, co, _ in units:
        out[f"_{name}_conv.w0"] = ((fs, fs, ci, co), "normal")
        out[f"_{name}_bn.w0"] = ((co,), last if name.endswith("_c")
                                 else "ones")
        out[f"_{name}_bn.wbias"] = ((co,), "zeros")
        out[f"_{name}_bn.w1"] = ((co,), "static")
        out[f"_{name}_bn.w2"] = ((co,), "static")
    classes = cfg["model"]["args"]["classes"]
    out["_output.w0"] = ((cin, classes), "normal")
    out["_output.wbias"] = ((classes,), "zeros")
    return out


def _conv_bn(params, name, x, fs, stride, relu, arith):
    y = arith.conv(x, params[f"_{name}_conv.w0"], stride, (fs - 1) // 2)
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) * jax.lax.rsqrt(var + EPS) * params[f"_{name}_bn.w0"] \
        + params[f"_{name}_bn.wbias"]
    return arith.out(jax.nn.relu(y) if relu else y)


def _block(params, name, x, stride, project, arith):
    r = _conv_bn(params, f"{name}_a", x, 1, stride, True, arith)
    r = _conv_bn(params, f"{name}_b", r, 3, 1, True, arith)
    r = _conv_bn(params, f"{name}_c", r, 1, 1, False, arith)
    sc = (_conv_bn(params, f"{name}_sc", x, 1, stride, False, arith)
          if project else x)
    return arith.out(jax.nn.relu(r + sc))


def _stem(params, x, arith):
    x = _conv_bn(params, "stem", x, 7, 2, True, arith)
    size = x.shape[1]
    out = math.ceil((size + 2 - 3) / 2) + 1
    far = max((out - 1) * 2 + 3 - size - 1, 0)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, far), (1, far), (0, 0)))


def logits(params, batch, cfg, arith):
    m = cfg["model"]["args"]
    s, c = m["image_size"], m["channels"]
    x = batch["image"].reshape(-1, c, s, s).transpose(0, 2, 3, 1)
    x = jax.checkpoint(lambda p, v: _stem(p, v, arith))(params, x)
    for stage, n in enumerate(STAGES):
        for i in range(n):
            name = f"res{stage + 2}{chr(ord('a') + i)}"
            stride = 2 if (stage > 0 and i == 0) else 1
            x = jax.checkpoint(
                lambda p, v, name=name, stride=stride, project=(i == 0):
                _block(p, name, v, stride, project, arith))(params, x)
    pooled = arith.out(jnp.mean(x, axis=(1, 2)))
    return arith.dot(pooled, params["_output.w0"]) + params["_output.wbias"]


def loss(params, batch, cfg, arith):
    p = jax.nn.softmax(logits(params, batch, cfg, arith), axis=-1)
    ll = jnp.take_along_axis(p, batch["label"][:, None], axis=-1)[:, 0]
    return jnp.mean(-jnp.log(jnp.clip(ll, 1e-10, 1.0)))
