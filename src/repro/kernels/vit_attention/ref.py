"""Oracle for the ViT self-attention kernel: the plain einsum softmax
attention over the qkv projection's token-major output.

``qkv`` is (B, t, 3h), its last axis laid out as (3, heads, dh): the
queries, then the keys, then the values, each head's dh columns side by
side. The (B, heads, t, t) scores and probabilities are materialized here;
the kernel never writes them out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def self_attention_ref(qkv: jnp.ndarray, heads: int) -> jnp.ndarray:
    """(B, t, 3h) -> (B, t, h): softmax(q k^T / sqrt(dh)) v per head."""
    b, t, h3 = qkv.shape
    h = h3 // 3
    dh = h // heads
    q, k, v = jnp.split(qkv.reshape(b, t, 3, heads, dh), 3, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, 0], k[:, :, 0]) / math.sqrt(dh)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v[:, :, 0])
    return o.reshape(b, t, h)
