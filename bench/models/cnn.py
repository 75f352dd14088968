"""Plain reference of the paper's two CNNs (arXiv:2304.07460, Sec. 8.1).

The modified VGG-11 and ResNet-18 as the configuration files describe
them, in straightforward ``jax.numpy``: 3x3 convolutions with "SAME"
padding and no bias, ReLU, 2x2 max pooling (VGG) or basic residual blocks
with 1x1 projections where the shape changes and global average pooling
(ResNet), one dense output layer; He-normal weights. It imports nothing of
the program. ``model`` is the configuration file's ``model`` object.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

VGG11_PLAN = (64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M")
RESNET18_WIDTHS = (64, 128, 256, 512)


# ------------------------------------------------------------------ model

def _he(key, cout, cin, ksize):
    w = jax.random.normal(key, (cout, cin, ksize, ksize), jnp.float32)
    return w * math.sqrt(2.0 / (cin * ksize * ksize))


def _dense(key, fan_in, out):
    return {"w": jax.random.normal(key, (fan_in, out)) * math.sqrt(1 / fan_in),
            "b": jnp.zeros((out,))}


def init_params(key, model):
    """Weights from ``key``: one split of 64 keys, consumed layer by layer
    in network order, the output layer last. ``width_mult`` (1 at the
    published widths) scales the channel counts of the small test copies."""
    ks = iter(jax.random.split(key, 64))
    cin, size = model["in_channels"], model["image_size"]
    wm = model.get("width_mult", 1.0)
    if model["arch"] == "vgg":
        convs = []
        for item in VGG11_PLAN:
            if item == "M":
                size = size // 2 if size > 1 else size
                continue
            cout = max(int(item * wm), 8)
            convs.append(_he(next(ks), cout, cin, 3))
            cin = cout
        out = _dense(next(ks), cin * size * size, model["num_classes"])
        return {"convs": convs, "out": out}
    widths = [max(int(c * wm), 8) for c in RESNET18_WIDTHS]
    stem = _he(next(ks), widths[0], cin, 3)
    cin = widths[0]
    stages = []
    for si, cout in enumerate(widths):
        blocks = []
        for bi in range(2):
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {"c1": _he(next(ks), cout, cin, 3),
                   "c2": _he(next(ks), cout, cout, 3)}
            if stride != 1 or cin != cout:
                blk["proj"] = _he(next(ks), cout, cin, 1)
            blocks.append(blk)
            cin = cout
        stages.append(blocks)
    return {"stem": stem, "stages": stages,
            "out": _dense(next(ks), cin, model["num_classes"])}


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, 2, 2), (1, 1, 2, 2), "VALID")


def forward(params, model, x):
    """Logits (B, classes) of images (B, C, H, W)."""
    if model["arch"] == "vgg":
        size, ci = model["image_size"], 0
        for item in VGG11_PLAN:
            if item == "M":
                if size > 1:
                    x, size = _pool(x), size // 2
            else:
                x = jax.nn.relu(_conv(x, params["convs"][ci]))
                ci += 1
        x = x.reshape(x.shape[0], -1)
    else:
        x = jax.nn.relu(_conv(x, params["stem"]))
        for si, stage in enumerate(params["stages"]):
            for bi, blk in enumerate(stage):
                stride = 2 if (si > 0 and bi == 0) else 1
                h = jax.nn.relu(_conv(x, blk["c1"], stride))
                h = _conv(h, blk["c2"])
                sc = _conv(x, blk["proj"], stride) if "proj" in blk else x
                x = jax.nn.relu(h + sc)
        x = jnp.mean(x, axis=(2, 3))
    return x @ params["out"]["w"] + params["out"]["b"]


def loss(params, model, x, y):
    logp = jax.nn.log_softmax(forward(params, model, x))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
