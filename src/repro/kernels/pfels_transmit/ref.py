"""Oracle for the fused PFELS transmit pipeline (Alg. 2 lines 12-15).

The whole client-side transmit chain for the (r, d) update batch in one
place: per-client l2 clip -> rand_k selection (dense 0/1 mask over d) ->
Theorem-5 power scaling beta/|h_i| -> MAC superposition with the true gains
-> receiver noise on the selected subcarriers. Unlike the Pallas kernel this
reference is free to materialize (r, d) intermediates — it is the parity
oracle, not the fast path.

Dense-mask formulation: with m the 0/1 indicator of omega and z_dense the
noise scattered onto omega,
    y_dense = sum_i |h_i| (beta/|h_i^est|) s_i (m * Delta_i) + z_dense
where s_i = min(1, C/||Delta_i||) is the optional transmit clip. y_dense is
zero off omega, so Delta_hat = y_dense/(r beta) directly; the k-subcarrier
payload is y_dense[omega].
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dense_noise_and_mask(idx: jnp.ndarray, noise_key, sigma0: float,
                         d: int, active: Optional[jnp.ndarray] = None):
    """(mask, z_dense): the 0/1 indicator of omega and the channel noise
    scattered onto it. THE single PRNG-critical noise draw
    (``sigma0 * normal(noise_key, (k,))``) shared by the fused and sharded
    AirComp paths — parity across execution modes (DESIGN.md §5) depends
    on every path taking it from here. ``active`` is the support's
    optional (k,) 0/1 live-slot column (DESIGN.md §13): the draw keeps
    its fixed k shape (the PRNG stream is schedule-independent), then
    deactivated slots are zeroed out of BOTH columns — no signal, no
    measured noise on an unallocated subcarrier.

    Without ``active`` both columns come from ONE scatter of the support
    (DESIGN.md §4): the noise goes into a NaN-filled column, so a NaN
    marks every coordinate off omega (the noise is finite, so none on
    omega). The support is a subset of [d], hence unique and in bounds,
    and the scatter is told so. With ``active`` an inactive slot needs
    mask 0 and the signed zero ``noise * 0``, which the sentinel cannot
    carry, so that branch scatters the two columns separately."""
    noise = sigma0 * jax.random.normal(noise_key, (idx.shape[0],))
    if active is None:
        col = jnp.full((d,), jnp.nan, jnp.float32).at[idx].set(
            noise, unique_indices=True, mode="promise_in_bounds")
        off = jnp.isnan(col)
        return (~off).astype(jnp.float32), jnp.where(off, 0.0, col)
    noise = noise * active
    mask = jnp.zeros((d,), jnp.float32).at[idx].set(active)
    z_dense = jnp.zeros((d,), jnp.float32).at[idx].set(noise)
    return mask, z_dense


def server_unscale(y_dense: jnp.ndarray, idx: jnp.ndarray, beta, r,
                   d: int, unbiased_rescale: bool = False) -> jnp.ndarray:
    """Receiver-side reconstruction Delta_hat = y_dense/(r beta), with the
    optional beyond-paper d/k unbiasing — the common tail of every
    aggregation path. ``r`` is the unscale divisor: the static nominal
    cohort size, or the traced REALIZED transmitter count under a channel
    transmit mask (DESIGN.md §11)."""
    delta_hat = y_dense / (r * beta)
    if unbiased_rescale:
        delta_hat = delta_hat * (d / idx.shape[0])
    return delta_hat


def scales_from_norms(norms: jnp.ndarray, clip: float) -> jnp.ndarray:
    """s = min(1, C/||.||) with the shared zero-norm guard — the single
    definition of the clip scale used by the fused kernel path, the fused
    reference, and the unfused aircomp_aggregate (parity depends on all
    three agreeing, epsilon included)."""
    return jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12))


def clip_scales(updates: jnp.ndarray, clip: Optional[float]) -> jnp.ndarray:
    """Per-client s_i = min(1, C/||Delta_i||_2) over the FULL update (the
    norm bound of Assumption 1 covers every coordinate, not just omega).
    clip=None disables (s_i = 1)."""
    if clip is None:
        return jnp.ones((updates.shape[0],), jnp.float32)
    return scales_from_norms(jnp.linalg.norm(updates.astype(jnp.float32),
                                             axis=1), clip)


def effective_gains(gains: jnp.ndarray) -> jnp.ndarray:
    """(r,) post-combining effective gains from a (r,) scalar-channel
    vector (identity) or a (r, M) per-antenna matrix (the all-ones-beam
    MRC combine ``g_i = sum_m h_{i,m}`` — bit-exact identity at M=1).
    The single definition the fused kernel's in-tile combine, this
    oracle, and the β design must agree on (DESIGN.md §12)."""
    return gains if gains.ndim == 1 else jnp.sum(gains, axis=-1)


def transmit_coeffs(gains, beta, scales, gains_est=None):
    """(tx, rx): tx_i = (beta/|h_i^est|) s_i is the per-client transmit
    amplitude; rx_i = |h_i| tx_i is the coefficient the MAC applies to
    Delta_i at the receiver (perfect CSI: rx_i = beta s_i). ``gains``
    may be (r,) effective or (r, M) per-antenna (combined here); the
    observed ``gains_est`` is always the effective view — devices
    precompensate with the post-combining gain they experience."""
    eff = effective_gains(gains)
    comp = gains_est if gains_est is not None else eff
    tx = (beta / comp) * scales
    return tx, eff * tx


def masked_coeffs(tx, rx, tx_mask=None):
    """(rx_eff, tx_sq): the receive coefficients and squared transmit
    amplitudes with an optional (r,) 0/1 transmit mask folded in — a
    masked client contributes zero signal and zero energy. This O(r)
    fold is the unfused analogue of the kernel's in-tile ``txm``
    column; both paths mask via the coefficients, never via an (r, d)
    pre-masked intermediate (DESIGN.md §12)."""
    tx_sq = tx * tx
    if tx_mask is None:
        return rx, tx_sq
    return rx * tx_mask, tx_sq * tx_mask


def pfels_transmit_ref(updates: jnp.ndarray, mask: jnp.ndarray,
                       noise_dense: jnp.ndarray, rx_coeffs: jnp.ndarray,
                       tx_sq: jnp.ndarray):
    """Fused combine, dense formulation (the part the Pallas kernel fuses).

    updates: (r, d); mask: (d,) 0/1 indicator of omega; noise_dense: (d,)
    channel noise scattered onto omega; rx_coeffs: (r,) receive-side
    per-client coefficients; tx_sq: (r,) squared transmit amplitudes.

    Returns (y_dense (d,), energy scalar):
        y_dense = sum_i rx_i (m * Delta_i) + z_dense
        energy  = sum_i tx_i^2 ||m * Delta_i||^2      (= sum_i ||x_i||^2)
    """
    masked = updates.astype(jnp.float32) * mask[None, :]
    y_dense = jnp.einsum("rd,r->d", masked, rx_coeffs) + noise_dense
    energy = jnp.sum(tx_sq * jnp.sum(masked * masked, axis=1))
    return y_dense, energy


def client_sumsq_ref(updates: jnp.ndarray) -> jnp.ndarray:
    """Per-client squared l2 norms, (r,) — pass 1 of the clip."""
    u = updates.astype(jnp.float32)
    return jnp.sum(u * u, axis=1)
