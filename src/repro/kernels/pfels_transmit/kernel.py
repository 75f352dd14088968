"""Pallas TPU kernels: fused PFELS transmit pipeline (Alg. 2 lines 12-15).

The whole (r, d) client-update batch goes through clip -> rand_k select ->
Theorem-5 power scale -> noisy AirComp sum in one pass over column tiles of
d, never materializing an (r, d)-sized sparsified/scaled intermediate.

The rand_k gather is reformulated as a dense 0/1 mask over d (computed once
server-side, O(d) not O(r d)), which removes all data-dependent indexing
from the kernel: each grid step loads an (r, block) tile of the updates,
masks it, reduces over clients with the per-client receive coefficients
(VPU multiply + sublane reduction; an MXU matvec at large r), adds the
pre-scattered channel noise, and accumulates the transmit energy
sum_i tx_i^2 ||m * Delta_i||^2 into a (1, 1) output block across the
sequential TPU grid. The accumulator is read and written as a whole (1, 1)
vector (``e_ref[...]``), never as a scalar element: Mosaic cannot store a
scalar to VMEM, so an ``e_ref[0, 0]`` store compiles only in interpret
mode.

The whole wireless-scenario matrix runs in-tile (DESIGN.md §12):

  - per-client transmit mask (the ``dropout`` scenario): a (r, 1) 0/1
    ``txm`` column zeroes a masked client's MAC contribution AND its
    energy term inside the tile pass — no (r, d) pre-masked intermediate;
  - per-antenna MRC combining (the ``mimo_mrc`` scenario): the gains
    arrive as an (r, M) per-antenna matrix and the all-ones-beam combine
    ``g_i = sum_m h_{i,m}`` happens in-tile, so the kernel applies the
    POST-combining effective gain; single-antenna models pass M=1, for
    which the combine is a bit-exact no-op (a sum over one element).

Two passes, like clip_norm: pass 1 (optional, only when a transmit clip is
set) accumulates per-client squared norms over the full d; the host turns
them into clip scales and per-client coefficients; pass 2 does the fused
combine.

Both entry points take ``interpret`` as a required argument. Only
``ops.fused_pipeline`` resolves it (the compiled kernel on a TPU backend,
the Pallas interpreter elsewhere), so no caller can fall back to the
interpreter on a TPU by default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _sumsq_kernel(u_ref, out_ref):
    """Accumulate per-client sum of squares across column tiles.
    u_ref: (r, block) VMEM; out_ref: (r, 1) revisited every step."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.sum(u * u, axis=1, keepdims=True)


def _combine_kernel(g_ref, tx_ref, txm_ref, u_ref, m_ref, z_ref,
                    y_ref, e_ref):
    """One fused tile: mask, MRC combine, client-weighted superposition,
    noise, energy.

    g_ref: (r, M) per-antenna true gains (M=1 for scalar channels);
    tx_ref/txm_ref: (r, 1) transmit amplitudes / 0-1 transmit mask, all
    revisited every step; u_ref: (r, block); m_ref/z_ref/y_ref:
    (1, block); e_ref: (1, 1) accumulated across steps.

    The receive coefficient is built in-tile: the all-ones-beam MRC
    combine ``g_i = sum_m h_{i,m}`` (bit-exact identity at M=1), times
    the transmit amplitude, times the transmit mask — so a dropped
    client (txm=0) contributes exactly 0.0 to the MAC sum and 0.0 energy
    without any (r, d) pre-masked intermediate.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        e_ref[...] = jnp.zeros_like(e_ref)

    g_eff = jnp.sum(g_ref[...].astype(jnp.float32), axis=1, keepdims=True)
    tx = tx_ref[...].astype(jnp.float32)
    txm = txm_ref[...].astype(jnp.float32)
    rxw = g_eff * tx * txm              # (r, 1) masked receive coefficients
    um = u_ref[...].astype(jnp.float32) * m_ref[...].astype(jnp.float32)
    y_ref[...] = (jnp.sum(um * rxw, axis=0, keepdims=True)
                  + z_ref[...]).astype(y_ref.dtype)
    e_ref[...] += jnp.sum((tx * tx * txm)
                          * jnp.sum(um * um, axis=1, keepdims=True),
                          axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def client_sumsq(updates: jnp.ndarray, *, block: int = 4096,
                 interpret: bool) -> jnp.ndarray:
    """updates: (r, d_pad) with d_pad % block == 0. Returns (r, 1) f32
    per-client squared l2 norms (zero-padding is norm-neutral)."""
    r, d_pad = updates.shape
    grid = (d_pad // block,)
    return pl.pallas_call(
        _sumsq_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((r, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((r, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        interpret=interpret,
    )(updates)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_combine(updates: jnp.ndarray, mask: jnp.ndarray,
                  noise_dense: jnp.ndarray, gains_mat: jnp.ndarray,
                  tx: jnp.ndarray, tx_mask: jnp.ndarray, *,
                  block: int = 4096, interpret: bool):
    """updates: (r, d_pad); mask/noise_dense: (1, d_pad); gains_mat:
    (r, M) per-antenna true gains; tx/tx_mask: (r, 1). d_pad % block == 0.
    Returns (y_dense (1, d_pad), energy (1, 1))."""
    r, d_pad = updates.shape
    m_ant = gains_mat.shape[1]
    grid = (d_pad // block,)
    return pl.pallas_call(
        _combine_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((r, m_ant), lambda i: (0, 0)),
            pl.BlockSpec((r, 1), lambda i: (0, 0)),
            pl.BlockSpec((r, 1), lambda i: (0, 0)),
            pl.BlockSpec((r, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(gains_mat, tx, tx_mask, updates, mask, noise_dense)
