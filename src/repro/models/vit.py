"""Vision transformer image classifier (ViT, arXiv:2010.11929).

Pre-LayerNorm encoder blocks (multi-head self-attention with a qkv bias,
then a GELU MLP, each added to the residual stream), a learned class token
and position embedding, a final LayerNorm and a dense head on the class
token. The resident images are upsampled inside the forward pass (nearest
neighbour, by a whole factor) to ``cfg.resize_to`` pixels, as the device's
own preprocessing, then cut into ``cfg.patch_size`` patches.

Same signatures as :mod:`repro.models.cnn`: ``init_vit(key, cfg)`` and
``vit_loss(params, cfg, batch) -> (loss, aux)``. Each part of the forward
pass runs under one flat name scope (``vit.patch_embed``,
``vit.attention``, ``vit.mlp``, ``vit.head``), which the compiled ops
carry as op_name metadata into the profiler's trace. The softmax-attention
core of each block, forward and backward, is one Pallas kernel
(:mod:`repro.kernels.vit_attention`), so its (t, t) scores never leave the
chip's VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ViTConfig
from repro.kernels.vit_attention.ops import self_attention
from repro.models.layers import dense_apply, dense_init, norm_apply, norm_init


def _dense(key, d_in, d_out):
    return dense_init(key, d_in, d_out, bias=True, dtype=jnp.float32)[0]


def _ln(d):
    return norm_init(d, "layernorm", jnp.float32)[0]


def num_tokens(cfg: ViTConfig) -> int:
    return (cfg.resize_to // cfg.patch_size) ** 2 + 1


def init_vit(key, cfg: ViTConfig):
    """Weights from ``key``: one split into the patch embedding, the class
    token, the position embedding, the head and one key per block; each
    block splits its key into qkv, projection and the two MLP layers.
    Dense weights are normal with std 1/sqrt(fan_in), the class token and
    position embedding normal with std 0.02, biases zero, LayerNorm scale
    one."""
    ks = jax.random.split(key, 4 + cfg.depth)
    h = cfg.hidden
    patch_dim = cfg.in_channels * cfg.patch_size ** 2
    blocks = []
    for kb in ks[4:]:
        kq, kp, k1, k2 = jax.random.split(kb, 4)
        blocks.append({"ln1": _ln(h), "qkv": _dense(kq, h, 3 * h),
                       "proj": _dense(kp, h, h), "ln2": _ln(h),
                       "fc1": _dense(k1, h, cfg.mlp_dim),
                       "fc2": _dense(k2, cfg.mlp_dim, h)})
    return {
        "patch": _dense(ks[0], patch_dim, h),
        "cls": 0.02 * jax.random.normal(ks[1], (1, h), jnp.float32),
        "pos": 0.02 * jax.random.normal(ks[2], (num_tokens(cfg), h),
                                        jnp.float32),
        "blocks": blocks,
        "norm": _ln(h),
        "head": _dense(ks[3], h, cfg.num_classes),
    }


def _patches(images, cfg: ViTConfig):
    """(B, C, S, S) -> (B, n_patches, C * p * p), upsampled first."""
    f = cfg.resize_to // cfg.image_size
    x = jnp.repeat(jnp.repeat(images, f, axis=2), f, axis=3)
    b, c, p, g = x.shape[0], x.shape[1], cfg.patch_size, \
        cfg.resize_to // cfg.patch_size
    x = x.reshape(b, c, g, p, g, p).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(b, g * g, c * p * p)


def _attention(p, x, heads):
    qkv = dense_apply(p["qkv"], norm_apply(p["ln1"], x, "layernorm"))
    return x + dense_apply(p["proj"], self_attention(qkv, heads))


def _mlp(p, x):
    y = jax.nn.gelu(dense_apply(p["fc1"], norm_apply(p["ln2"], x,
                                                     "layernorm")),
                    approximate=True)
    return x + dense_apply(p["fc2"], y)


def apply_vit(params, cfg: ViTConfig, images):
    """images: (B, C, H, W) -> logits (B, num_classes)."""
    if cfg.resize_to % cfg.image_size or cfg.resize_to % cfg.patch_size:
        raise ValueError(f"{cfg.name}: resize_to {cfg.resize_to} must be a "
                         f"multiple of image_size {cfg.image_size} and of "
                         f"patch_size {cfg.patch_size}")
    with jax.named_scope("vit.patch_embed"):
        x = dense_apply(params["patch"], _patches(images, cfg))
        cls = jnp.broadcast_to(params["cls"], (x.shape[0], 1, cfg.hidden))
        x = jnp.concatenate([cls, x], axis=1) + params["pos"]
    for blk in params["blocks"]:
        with jax.named_scope("vit.attention"):
            x = _attention(blk, x, cfg.heads)
        with jax.named_scope("vit.mlp"):
            x = _mlp(blk, x)
    with jax.named_scope("vit.head"):
        return dense_apply(params["head"],
                           norm_apply(params["norm"], x[:, 0], "layernorm"))


def vit_loss(params, cfg: ViTConfig, batch):
    logits = apply_vit(params, cfg, batch["x"])
    with jax.named_scope("vit.head"):
        labels = batch["y"]
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(
            jnp.float32))
    return jnp.mean(nll), {"accuracy": acc}
