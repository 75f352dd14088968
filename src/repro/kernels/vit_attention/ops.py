"""Differentiable ViT self-attention through the Pallas kernels.

``self_attention(qkv, heads)`` has the contract of
``ref.self_attention_ref``: the qkv projection's (B, t, 3h) output in,
(B, t, h) out, ready for the output projection. A ``jax.custom_vjp``
saves o and the (B, heads, t) float32 log-sum-exp for the backward kernel,
which recomputes the probabilities in VMEM, so no (t, t) tensor is kept
between the passes. On a TPU the kernels compile, with bfloat16 matmul
inputs as XLA's default precision runs them; qkv is then handed over, and
kept for the backward pass, in bfloat16, which rounds it as the kernels'
matmuls would and halves its bytes. Elsewhere the kernels run in the
Pallas interpreter with float32 matmuls, as XLA's CPU backend does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.vit_attention.kernel import (vit_attention_bwd,
                                                vit_attention_fwd)

# samples per grid step: the largest divisor of the batch up to this
MAX_BLOCK = 2


def _block(b: int) -> int:
    return max(c for c in range(1, min(b, MAX_BLOCK) + 1) if b % c == 0)


def _backend():
    on_tpu = jax.default_backend() == "tpu"
    return {"interpret": not on_tpu,
            "mxu_dtype": jnp.bfloat16 if on_tpu else jnp.float32}


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def self_attention(qkv: jnp.ndarray, heads: int) -> jnp.ndarray:
    """(B, t, 3h) -> (B, t, h): softmax(q k^T / sqrt(dh)) v per head."""
    return _fwd(qkv, heads)[0]


def _fwd(qkv, heads):
    kw = _backend()
    x = qkv.astype(kw["mxu_dtype"])
    o, lse = vit_attention_fwd(x, heads=heads, block=_block(x.shape[0]), **kw)
    return o.astype(qkv.dtype), (x, o, lse)


def _bwd(heads, res, do):
    x, o, lse = res
    dqkv = vit_attention_bwd(x, o, lse, do.astype(jnp.float32), heads=heads,
                             block=_block(x.shape[0]), **_backend())
    return (dqkv.astype(do.dtype),)


self_attention.defvjp(_fwd, _bwd)
