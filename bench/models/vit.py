"""Plain reference of ViT-S/16 on CIFAR-10 at 224 px.

ViT (Dosovitskiy et al., arXiv:2010.11929) at the DeiT-S widths (Touvron
et al., arXiv:2012.12877), as the configuration file describes it, in
straightforward ``jax.numpy``: the 32 x 32 images upsampled to 224 x 224
(nearest neighbour, each pixel a 7 x 7 block), cut into 16 x 16 patches
and embedded by one dense layer; a learned class token and position
embedding; pre-LayerNorm blocks of multi-head self-attention (qkv bias,
softmax of q k^T / sqrt(head size)) and a GELU MLP (tanh approximation),
each added to the residual stream; a final LayerNorm and a dense head on
the class token. It imports nothing of the program. ``model`` is the
configuration file's ``model`` object.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


# ------------------------------------------------------------------ model

def _dense(key, fan_in, out):
    kw, _ = jax.random.split(key)
    return {"w": (1.0 / math.sqrt(fan_in))
            * jax.random.normal(kw, (fan_in, out), jnp.float32),
            "b": jnp.zeros((out,), jnp.float32)}


def _ln(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def tokens(model):
    return (model["resize_to"] // model["patch_size"]) ** 2 + 1


def init_params(key, model):
    """Weights from ``key``: one split into 4 + depth keys (patch
    embedding, class token, position embedding, head, then one per
    block); a block's key splits into qkv, projection, MLP in and MLP out;
    a dense layer's key splits into weight and (unused) bias keys. Dense
    weights normal with std 1/sqrt(fan_in), class token and position
    embedding normal with std 0.02, biases zero, LayerNorm scale one."""
    ks = jax.random.split(key, 4 + model["depth"])
    h, m = model["hidden"], model["mlp_dim"]
    patch_dim = model["in_channels"] * model["patch_size"] ** 2
    blocks = []
    for kb in ks[4:]:
        kq, kp, k1, k2 = jax.random.split(kb, 4)
        blocks.append({"ln1": _ln(h), "qkv": _dense(kq, h, 3 * h),
                       "proj": _dense(kp, h, h), "ln2": _ln(h),
                       "fc1": _dense(k1, h, m), "fc2": _dense(k2, m, h)})
    return {
        "patch": _dense(ks[0], patch_dim, h),
        "cls": 0.02 * jax.random.normal(ks[1], (1, h), jnp.float32),
        "pos": 0.02 * jax.random.normal(ks[2], (tokens(model), h),
                                        jnp.float32),
        "blocks": blocks,
        "norm": _ln(h),
        "head": _dense(ks[3], h, model["num_classes"]),
    }


def _layer_norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _linear(p, x):
    return x @ p["w"] + p["b"]


def forward(params, model, x):
    """Logits (B, classes) of images (B, C, H, W)."""
    b, c = x.shape[0], model["in_channels"]
    p, size = model["patch_size"], model["resize_to"]
    f, g = size // model["image_size"], size // p
    x = jnp.repeat(jnp.repeat(x, f, axis=2), f, axis=3)
    x = x.reshape(b, c, g, p, g, p).transpose(0, 2, 4, 1, 3, 5)
    x = _linear(params["patch"], x.reshape(b, g * g, c * p * p))
    h = model["hidden"]
    x = jnp.concatenate([jnp.broadcast_to(params["cls"], (b, 1, h)), x],
                        axis=1) + params["pos"]
    t, heads = x.shape[1], model["heads"]
    dh = h // heads
    for blk in params["blocks"]:
        qkv = _linear(blk["qkv"], _layer_norm(blk["ln1"], x))
        qkv = qkv.reshape(b, t, 3, heads, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + _linear(blk["proj"], o.reshape(b, t, h))
        y = jax.nn.gelu(_linear(blk["fc1"], _layer_norm(blk["ln2"], x)),
                        approximate=True)
        x = x + _linear(blk["fc2"], y)
    return _linear(params["head"], _layer_norm(params["norm"], x[:, 0]))


def loss(params, model, x, y):
    logp = jax.nn.log_softmax(forward(params, model, x))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
