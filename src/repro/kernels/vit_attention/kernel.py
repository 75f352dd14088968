"""Pallas TPU kernels: ViT self-attention, forward and backward, with the
(t, t) scores and probabilities kept in VMEM.

Each grid step loads ``block`` whole samples of the qkv projection's
output, (block, t, 3h) in its token-major layout, and slices the query,
key and value columns in VMEM, so neither a (B, heads, t, dh) transpose
nor a concatenate of the gradients goes through HBM. Heads are sliced in
groups that fill one 128-lane row (two heads of 64 for ViT-S/16), so
every load and store is lane-aligned; within a group each head's matmuls
see the other heads' lanes as zeros, which adds no MXU passes: the v5e's
128-deep MXU takes a contraction of 64 in as many as one of 128. The
whole sequence fits (ViT-S/16: t = 197), so the softmax needs no online
rescaling: one pass computes the scores, their row maximum and
log-sum-exp, the probabilities and the weighted sum.

- Forward: S = q k^T / sqrt(dh), lse = logsumexp_rows(S), P = exp(S - lse),
  o = P v, written straight into (B, t, h); lse (B, heads, t) is kept for
  the backward pass in place of P.
- Backward: P is recomputed from q, k and lse; D = rowsum(dO * o),
  dP = dO v^T, dS = P * (dP - D); dq = dS k / sqrt(dh), dk = dS^T q /
  sqrt(dh), dv = P^T dO, written into one (B, t, 3h) d(qkv). It holds
  P^T and dS^T, keys by queries.

Matmul inputs are cast to ``mxu_dtype`` and accumulate in float32
(bfloat16 is what XLA runs a float32 matmul in at the TPU's default
precision); the softmax and its backward run in float32, and o, lse and
d(qkv) are float32 whatever the dtype of qkv.

Both entry points take ``interpret`` and ``mxu_dtype`` as required
arguments; only ``ops`` resolves them from the backend.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b
_MIB = 1 << 20
LANES = 128


def _dot(a, b, dims, dtype):
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           preferred_element_type=jnp.float32)


def _mm(a, b, dtype):
    return jnp.dot(a.astype(dtype), b.astype(dtype),
                   preferred_element_type=jnp.float32)


def _only(mask, x):
    """x on the lanes of one head of a group, zero on the others."""
    return jnp.where(mask, x, jnp.zeros_like(x))


def _group(heads: int, dh: int) -> int:
    """Heads sliced together: the most, dividing ``heads``, whose columns
    fit one 128-lane row, so every load and store is a whole group."""
    return max(g for g in range(1, heads + 1)
               if heads % g == 0 and (g == 1 or g * dh <= LANES))


def _fwd_kernel(qkv_ref, o_ref, lse_ref, *, heads, mxu_dtype):
    """qkv_ref: (block, t, 3h); o_ref: (block, t, h); lse_ref: (block,
    heads, t)."""
    t, h = qkv_ref.shape[1], qkv_ref.shape[2] // 3
    dh = h // heads
    g = _group(heads, dh)
    scale = 1.0 / math.sqrt(dh)
    head_of = lax.broadcasted_iota(jnp.int32, (t, g * dh), 1) // dh

    def sample(s, carry):
        for g0 in range(0, heads, g):
            q, k, v = (qkv_ref[s, :, pl.ds(i * h + g0 * dh, g * dh)]
                       for i in range(3))
            o = 0.0
            for j in range(g):
                mine = head_of == j
                sc = _dot(_only(mine, q), k, _NT, mxu_dtype) * scale
                m = jnp.max(sc, axis=1, keepdims=True)
                p = jnp.exp(sc - m)
                l = jnp.sum(p, axis=1, keepdims=True)
                o = o + _mm(p * (1.0 / l), _only(mine, v), mxu_dtype)
                lse_ref[s, pl.ds(g0 + j, 1), :] = (m + jnp.log(l)).T
            o_ref[s, :, pl.ds(g0 * dh, g * dh)] = o
        return carry

    lax.fori_loop(0, qkv_ref.shape[0], sample, 0)


def _bwd_kernel(qkv_ref, o_ref, lse_ref, do_ref, dqkv_ref, *, heads,
                mxu_dtype):
    """qkv_ref/dqkv_ref: (block, t, 3h); o_ref/do_ref: (block, t, h);
    lse_ref: (block, heads, t). Works on the transposed scores, keys by
    queries, so that lse is read as the row it is stored as and dk, dv are
    plain matmuls; only dq takes a transposed operand."""
    t, h = qkv_ref.shape[1], qkv_ref.shape[2] // 3
    dh = h // heads
    g = _group(heads, dh)
    scale = 1.0 / math.sqrt(dh)
    head_of = lax.broadcasted_iota(jnp.int32, (t, g * dh), 1) // dh

    def sample(s, carry):
        for g0 in range(0, heads, g):
            cols = [pl.ds(i * h + g0 * dh, g * dh) for i in range(3)]
            q, k, v = (qkv_ref[s, :, c] for c in cols)
            o = o_ref[s, :, cols[0]]
            do = do_ref[s, :, cols[0]]
            dq = dk = dv = 0.0
            for j in range(g):
                mine = head_of == j
                qj, doj = _only(mine, q), _only(mine, do)
                lse = lse_ref[s, pl.ds(g0 + j, 1), :]              # (1, t)
                pt = jnp.exp(_dot(k, qj, _NT, mxu_dtype) * scale - lse)
                d = jnp.sum(doj * o, axis=1, keepdims=True).T      # (1, t)
                dst = pt * (_dot(v, doj, _NT, mxu_dtype) - d)
                dq = dq + _dot(dst, _only(mine, k), _TN, mxu_dtype)
                dk = dk + _mm(dst, qj, mxu_dtype)
                dv = dv + _mm(pt, doj, mxu_dtype)
            dqkv_ref[s, :, cols[0]] = dq * scale
            dqkv_ref[s, :, cols[1]] = dk * scale
            dqkv_ref[s, :, cols[2]] = dv
        return carry

    lax.fori_loop(0, qkv_ref.shape[0], sample, 0)


def _specs(block, t, *widths):
    return [pl.BlockSpec((block, t, w), lambda i: (i, 0, 0))
            for w in widths]


def _params(block_bytes):
    """Double-buffered blocks plus room for the (t, t) temporaries."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=min(2 * block_bytes + 32 * _MIB, 100 * _MIB))


@functools.partial(jax.jit, static_argnames=("heads", "block", "interpret",
                                             "mxu_dtype"))
def vit_attention_fwd(qkv, *, heads: int, block: int, interpret: bool,
                      mxu_dtype):
    """qkv: (B, t, 3h) with B % block == 0. Returns (o (B, t, h), lse (B,
    heads, t)), both float32."""
    b, t, h3 = qkv.shape
    h = h3 // 3
    isz = qkv.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, mxu_dtype=mxu_dtype),
        grid=(b // block,),
        in_specs=_specs(block, t, h3),
        out_specs=[*_specs(block, t, h), *_specs(block, heads, t)],
        out_shape=[jax.ShapeDtypeStruct((b, t, h), jnp.float32),
                   jax.ShapeDtypeStruct((b, heads, t), jnp.float32)],
        compiler_params=_params(block * (t * (h3 * isz + h * 4)
                                         + heads * t * 4)),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * t * t * h, transcendentals=b * heads * t * t,
            bytes_accessed=b * (t * (h3 * isz + h * 4) + heads * t * 4)),
        interpret=interpret,
    )(qkv)


@functools.partial(jax.jit, static_argnames=("heads", "block", "interpret",
                                             "mxu_dtype"))
def vit_attention_bwd(qkv, o, lse, do, *, heads: int, block: int,
                      interpret: bool, mxu_dtype):
    """The gradient of :func:`vit_attention_fwd`'s o with respect to qkv,
    given the upstream gradient ``do`` (B, t, h) float32. Returns (B, t,
    3h) float32."""
    b, t, h3 = qkv.shape
    h = h3 // 3
    isz = qkv.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, mxu_dtype=mxu_dtype),
        grid=(b // block,),
        in_specs=[*_specs(block, t, h3, h), *_specs(block, heads, t),
                  *_specs(block, t, h)],
        out_specs=_specs(block, t, h3)[0],
        out_shape=jax.ShapeDtypeStruct((b, t, h3), jnp.float32),
        compiler_params=_params(block * (t * (h3 * (isz + 4) + 8 * h)
                                         + heads * t * 4)),
        cost_estimate=pl.CostEstimate(
            flops=10 * b * t * t * h, transcendentals=b * heads * t * t,
            bytes_accessed=b * (t * (h3 * (isz + 4) + 8 * h)
                                + heads * t * 4)),
        interpret=interpret,
    )(qkv, o, lse, do)
