"""Pluggable transmit-scheme registry for the FL round (DESIGN.md §8).

Each :class:`Algorithm` entry supplies the three points where the paper's
schemes actually differ — support selection (which coordinates are
transmitted), β-design (the per-round power/alignment coefficient), and
aggregation — plus the per-round privacy spend charged to the in-graph
ledger. The round body in ``repro.fl.rounds._build_cohort_core`` is
otherwise uniform: local training, error feedback, the AirComp machinery
(unfused / fused Pallas / sharded cohort), metrics, and the server update
are shared by every entry, so a new transmit scheme is a
``register_algorithm`` call, not another ``cfg.algorithm`` branch.

Built-in entries reproduce the paper: ``pfels`` (Alg. 2 + Thm 5),
``wfl_p`` (Eq. 36), ``wfl_pdp`` (Eq. 37), ``dp_fedavg`` (paper Alg. 1),
``fedavg``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax.numpy as jnp

from repro.configs.base import PFELSConfig
from repro.core import (aggregation, channels, compressors, power_control,
                        privacy)


@dataclass(frozen=True)
class Algorithm:
    """One transmit scheme.

    ``aircomp=True`` routes the round through the analog AirComp path
    (support selection -> β-design -> MAC superposition, with the
    config-selected execution strategy: unfused reference, fused Pallas
    kernel, or sharded cohort psum); the entry must then provide
    ``select_support`` and ``design_beta``. ``aircomp=False`` means digital
    server-side aggregation; the entry must provide ``server_aggregate``.

    Hooks (all trace-safe):
      select_support(cfg, d, k, prev_delta, key)
          -> repro.core.compressors.Support
          the transmitted coordinate set omega_t (static-width ``idx``
          plus an optional 0/1 ``active`` live-slot column, DESIGN.md
          §13); ``prev_delta`` is the previous round's reconstructed
          update (zeros on cold start) for server-guided schemes.
          Sparsifying schemes (pfels) delegate to the configured
          ``repro.core.compressors`` registry entry.
      design_beta(cfg, gains, power_limits, d, k_used, *, epsilon=None,
                  c1_scale=1.0) -> scalar beta
          the per-round alignment coefficient from the GLOBAL (r,) gains
          and the selected clients' power limits. ``k_used`` may be a
          traced live-support count; ``epsilon`` overrides the per-round
          budget (the "budget" CompressionSchedule); ``c1_scale`` is the
          compressor's static sensitivity multiplier on C1 (DESIGN.md
          §13) — both caps are linear in C1, so it tightens the power
          AND privacy constraints consistently.
      server_aggregate(cfg, flat_updates, noise_key, *, d, r) -> (d,)
          digital aggregation of the (r, d) update batch.
      privacy_spend(cfg, beta, d=None) -> scalar eps
          per-round (eps, cfg.resolved_delta())-DP charge for the realized
          beta, accumulated by the in-graph ledger; ``d`` feeds
          dimension-dependent compressor sensitivity (stoch_quant).
          None = the scheme carries no per-round DP guarantee and is
          never ledgered.

    ``sparsifies_transmit`` tells the error-feedback memory whether the
    transmitted signal was restricted to the support (residual = the
    untransmitted coordinates) or dense.
    """
    name: str
    aircomp: bool
    select_support: Optional[Callable] = None
    design_beta: Optional[Callable] = None
    server_aggregate: Optional[Callable] = None
    privacy_spend: Optional[Callable] = None
    sparsifies_transmit: bool = False


_REGISTRY: Dict[str, Algorithm] = {}


def register_algorithm(name: str, alg: Algorithm, *,
                       overwrite: bool = False) -> Algorithm:
    """Add a transmit scheme under ``PFELSConfig.algorithm == name``."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"algorithm {name!r} already registered "
                         f"(pass overwrite=True to replace)")
    if alg.aircomp and (alg.select_support is None or alg.design_beta is None):
        raise ValueError(f"aircomp algorithm {name!r} needs select_support "
                         f"and design_beta hooks")
    if not alg.aircomp and alg.server_aggregate is None:
        raise ValueError(f"non-aircomp algorithm {name!r} needs a "
                         f"server_aggregate hook")
    _REGISTRY[name] = alg
    return alg


def unregister_algorithm(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_algorithm(name: str) -> Algorithm:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown algorithm {name!r}; registered: "
            f"{sorted(_REGISTRY)} (add new schemes via "
            f"repro.fl.algorithms.register_algorithm)") from None


def list_algorithms():
    return sorted(_REGISTRY)


# ------------------------------------------------------- built-in schemes

def _dp_epsilon_spend(cfg: PFELSConfig, beta, d=None, *,
                      compressed: bool = True):
    """Per-round eps actually consumed (Thm 3 inverse) for the realized
    beta, capped at the configured budget — Theorem 5 already enforces
    ``C2 * beta <= eps``, so the cap only absorbs fp rounding (and matches
    the host-side ``PrivacyLedger`` convention). C2 is built
    from the channel model's POST-COMBINING noise std (DESIGN.md §11):
    a multi-antenna receiver changes the intrinsic noise the guarantee
    rides on, and the ledger must charge against that operating point —
    and, for compressed (sparsifying) schemes, from ``C1`` scaled by the
    compressor's static sensitivity factor (DESIGN.md §13): C2 is linear
    in C1, so a norm-inflating transform (stoch_quant) costs
    proportionally more budget per unit beta. ``d`` feeds
    dimension-dependent factors; rand_k's factor is 1.0, making this
    bit-identical to the pre-registry spend."""
    s = compressors.sensitivity_factor(cfg, d) if compressed else 1.0
    c2 = privacy.c2_coefficient(
        cfg.local_lr, cfg.local_steps, cfg.clip * s, cfg.clients_per_round,
        cfg.num_clients, cfg.resolved_delta(),
        channels.effective_noise_std(cfg.channel))
    return jnp.minimum(jnp.float32(c2) * beta, jnp.float32(cfg.epsilon))


def _dp_epsilon_spend_dense(cfg: PFELSConfig, beta, d=None):
    """The spend for full-update (non-sparsifying) DP schemes (wfl_pdp):
    no compressor in the transmit path, so no sensitivity factor."""
    return _dp_epsilon_spend(cfg, beta, d, compressed=False)


def _pfels_support(cfg: PFELSConfig, d: int, k: int, prev_delta, key):
    """Sparsifying support omega_t — delegated to the configured
    ``repro.core.compressors`` registry entry (DESIGN.md §13). The
    paper's rand-k draw (incl. ``randk_mode="server_topk"``) lives in
    the ``rand_k`` entry, bit-exact with the pre-registry code."""
    comp = compressors.get_compressor(cfg.compressor)
    return comp.select_support(cfg, d, k, prev_delta, key)


def _full_support(cfg: PFELSConfig, d: int, k: int, prev_delta, key):
    """Full-update baselines transmit every coordinate (k = d)."""
    return compressors.Support(jnp.arange(d))


def _pfels_beta(cfg: PFELSConfig, gains, power_limits, d: int, k, *,
                epsilon=None, c1_scale: float = 1.0):
    """``gains`` are the channel model's EFFECTIVE observed gains (the
    design view of DESIGN.md §11); the privacy cap inside Theorem 5 uses
    the post-combining noise std for the same reason as the ledger.
    ``epsilon`` may be the schedule's traced per-round ceiling and ``k``
    a traced live-support count; ``c1_scale`` is the compressor's
    sensitivity factor — C1·s in the power cap keeps E||x_i||^2 <= P_i
    when the encoded signal's norm inflates, and in the privacy cap
    keeps beta <= eps/C2' consistent with the ledger's charge."""
    eps = cfg.epsilon if epsilon is None else epsilon
    return power_control.beta_pfels(
        gains, power_limits, d=d, k=k, c1=cfg.clip * c1_scale,
        eta=cfg.local_lr, tau=cfg.local_steps, epsilon=eps,
        r=cfg.clients_per_round, n=cfg.num_clients,
        delta=cfg.resolved_delta(),
        sigma0=channels.effective_noise_std(cfg.channel))


def _wfl_p_beta(cfg: PFELSConfig, gains, power_limits, d: int, k, *,
                epsilon=None, c1_scale: float = 1.0):
    return power_control.beta_wfl_p(
        gains, power_limits, c1=cfg.clip, eta=cfg.local_lr,
        tau=cfg.local_steps)


def _wfl_pdp_beta(cfg: PFELSConfig, gains, power_limits, d: int, k, *,
                  epsilon=None, c1_scale: float = 1.0):
    eps = cfg.epsilon if epsilon is None else epsilon
    return power_control.beta_wfl_pdp(
        gains, power_limits, c1=cfg.clip, eta=cfg.local_lr,
        tau=cfg.local_steps, epsilon=eps,
        r=cfg.clients_per_round, n=cfg.num_clients,
        delta=cfg.resolved_delta(),
        sigma0=channels.effective_noise_std(cfg.channel))


def _dp_fedavg_aggregate(cfg: PFELSConfig, flat_updates, noise_key, *,
                         d: int, r: int):
    return aggregation.dp_fedavg_aggregate(
        flat_updates, cfg.clip, cfg.dp_fedavg_sigma, noise_key, r=r)


def _dp_fedavg_spend(cfg: PFELSConfig, beta, d=None):
    """Per-round eps of the server-side Gaussian mechanism (Thm 1
    inverted). ``dp_fedavg_aggregate`` releases the clipped-update mean —
    client-level l2-sensitivity C/r — carrying noise std C*sigma/sqrt(r),
    i.e. noise multiplier z = sigma*sqrt(r), so
    eps = sqrt(2 ln(1.25/delta)) / z. Static config only (``beta`` plays
    no role in the digital baseline), hence a trace-safe constant.

    Found by replint RL301: the scheme injected DP noise every round but
    never charged the in-graph ledger, so reported budgets stayed (0, 0)
    — exactly the accounting drift arXiv 2304.04164 warns about."""
    z = cfg.dp_fedavg_sigma * math.sqrt(cfg.clients_per_round)
    eps = math.sqrt(2.0 * math.log(1.25 / cfg.resolved_delta())) / z
    # no cfg.epsilon cap here: unlike Thm 5 there is no design constraint
    # keeping this under budget, and a capped report would under-charge
    return jnp.float32(eps)


def _fedavg_aggregate(cfg: PFELSConfig, flat_updates, noise_key, *,
                      d: int, r: int):
    return aggregation.fedavg_aggregate(flat_updates)


register_algorithm("pfels", Algorithm(
    name="pfels", aircomp=True, select_support=_pfels_support,
    design_beta=_pfels_beta, privacy_spend=_dp_epsilon_spend,
    sparsifies_transmit=True))

register_algorithm("wfl_p", Algorithm(
    name="wfl_p", aircomp=True, select_support=_full_support,
    design_beta=_wfl_p_beta))

register_algorithm("wfl_pdp", Algorithm(
    name="wfl_pdp", aircomp=True, select_support=_full_support,
    design_beta=_wfl_pdp_beta, privacy_spend=_dp_epsilon_spend_dense))

register_algorithm("dp_fedavg", Algorithm(
    name="dp_fedavg", aircomp=False, server_aggregate=_dp_fedavg_aggregate,
    privacy_spend=_dp_fedavg_spend))

register_algorithm("fedavg", Algorithm(
    name="fedavg", aircomp=False, server_aggregate=_fedavg_aggregate))
