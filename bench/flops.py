"""Model FLOPs of a forward pass, counted from its jaxpr.

Counts the multiply-adds of every ``conv_general_dilated`` and
``dot_general`` as two operations each. A convolution counts only the
taps that land inside the unpadded input, as XLA's cost analysis does.
Everything else (activations, pooling, the loss) is left out: model FLOPs
are those of the matrix units. Training takes three times the forward
(one forward, two for the backward). Works for any traceable forward, so
a new configuration needs no new code here.
"""
from __future__ import annotations

import math

import jax
import numpy as np


def _valid_taps(in_size, k_size, out_size, stride, pad_lo, rhs_dil, lhs_dil):
    """Number of (output, kernel) position pairs along one spatial
    dimension whose input position falls on a real (unpadded) element."""
    o = np.arange(out_size)[:, None]
    k = np.arange(k_size)[None, :]
    pos = o * stride + k * rhs_dil - pad_lo
    real = (pos >= 0) & (pos % lhs_dil == 0) & (pos // lhs_dil < in_size)
    return int(real.sum())


def _conv_flops(eqn):
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    out = eqn.outvars[0].aval.shape
    p = eqn.params
    dn = p["dimension_numbers"]
    lb, lf, lsp = dn.lhs_spec[0], dn.lhs_spec[1], dn.lhs_spec[2:]
    ro, ri, rsp = dn.rhs_spec[0], dn.rhs_spec[1], dn.rhs_spec[2:]
    osp = dn.out_spec[2:]
    taps = 1
    for i in range(len(lsp)):
        taps *= _valid_taps(lhs[lsp[i]], rhs[rsp[i]], out[osp[i]],
                            p["window_strides"][i], p["padding"][i][0],
                            p["rhs_dilation"][i], p["lhs_dilation"][i])
    batch = lhs[lb] // p["batch_group_count"]
    cin_per_group = rhs[ri]
    cout = rhs[ro]
    return 2 * batch * taps * cin_per_group * cout


def _dot_flops(eqn):
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    contract = math.prod(lhs[i] for i in lc)
    batch = math.prod(lhs[i] for i in lb)
    lfree = math.prod(s for i, s in enumerate(lhs) if i not in lc + lb)
    rfree = math.prod(s for i, s in enumerate(rhs) if i not in rc + rb)
    return 2 * batch * contract * lfree * rfree


def _count(jaxpr):
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "dot_general":
            total += _dot_flops(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _count(sub)
    return total


def forward_flops(fn, *args):
    """Matrix-unit FLOPs of one call ``fn(*args)``; ``args`` may be arrays
    or ``jax.ShapeDtypeStruct``s."""
    return _count(jax.make_jaxpr(fn)(*args).jaxpr)


def train_flops_per_sample(fn, params, sample_shape, dtype="float32"):
    """3 x the forward FLOPs of one sample (forward + backward)."""
    x = jax.ShapeDtypeStruct((1,) + tuple(sample_shape), dtype)
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     params)
    return 3 * forward_flops(fn, p, x)
