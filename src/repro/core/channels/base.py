"""ChannelModel registry — the pluggable wireless-scenario axis
(DESIGN.md §11).

Mirrors ``repro.fl.algorithms``: a :class:`ChannelModel` entry supplies the
points where wireless scenarios actually differ — per-round gain
generation (possibly stateful across rounds), the observed-gain (CSI)
view, an optional transmit mask, and the post-combining receiver noise
level — while the round body in ``repro.fl.rounds._build_cohort_core``
stays uniform. ``ChannelConfig.model`` selects the entry; new scenarios
are ``register_channel_model`` calls, not round-body branches.

State contract (DESIGN.md §11): a model's cross-round state is an
arbitrary pytree ``carry`` (``None`` for stateless models). It lives in
``TrainState.chan``, is carried through ``Trainer.run``'s ``lax.scan``
(resident bank) and the host loop (streamed bank) with the same update
ops and PRNG lanes — which is why the two backends stay bit-identical —
and checkpoints with the rest of ``TrainState``.

PRNG contract (DESIGN.md §5): ``step`` receives exactly the round's
``gains`` lane (``ks[2]``) and ``csi`` lane (``ks[6]``); models needing
extra draws (the dropout Bernoulli) must derive them by ``fold_in`` on
the gains lane rather than widening the 7-lane split.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional

import jax.numpy as jnp

from repro.configs.base import ChannelConfig

# finite stand-in for "this client does not constrain beta" — kept out of
# inf so beta stays finite (inf * masked-zero signals would produce NaNs)
# while any real gain times sqrt(P) stays orders of magnitude below the
# resulting per-client cap
DESIGN_GAIN_BIG = 1e12


class ChannelRound(NamedTuple):
    """One round's channel realization, as the round body consumes it.

    ``gains``: (r,) true *effective* per-client gains (what the MAC
    applies — post-combining for multi-antenna models). ``gains_obs``:
    the gains the devices observe and precompensate with (``None`` means
    perfect CSI: observed == true, and the aggregation paths skip the
    estimate division entirely — the seed-exact fast path). ``tx_mask``:
    (r,) 0/1 float transmit indicator, or ``None`` when every sampled
    client transmits (again the seed-exact fast path). ``gains_ant``:
    optional (r, M) per-antenna true magnitudes (mimo_mrc) — when set,
    the fused kernel consumes the matrix and performs the all-ones-beam
    MRC combine IN-TILE (DESIGN.md §12); ``gains`` must then equal
    ``sum_m gains_ant[:, m]`` (the effective view the β design and the
    unfused oracle keep using).
    """
    gains: jnp.ndarray
    gains_obs: Optional[jnp.ndarray] = None
    tx_mask: Optional[jnp.ndarray] = None
    gains_ant: Optional[jnp.ndarray] = None


@dataclass(frozen=True)
class ChannelModel:
    """One wireless scenario.

    Hooks (all trace-safe):
      init(key, n, cfg) -> carry
          cross-round channel state for an n-client population (``None``
          for stateless models; the Trainer stores it in
          ``TrainState.chan``).
      step(carry, cfg, r, sel, gains_key, csi_key) -> (carry, ChannelRound)
          one round's realization for the sampled cohort ``sel`` (r,).
          ``gains_key``/``csi_key`` are the round's ks[2]/ks[6] lanes.
      noise_std(cfg) -> float
          POST-COMBINING receiver noise std sigma_eff — consumed by the
          noise draw, the Theorem-5 privacy cap, and the ledger's per-round
          ε spend in place of the raw ``cfg.noise_std``.
      stateful(cfg) -> bool
          whether ``init`` returns real state (a config-static property;
          ``TrainState.chan`` carries that state across rounds).
      may_mask(cfg) -> bool
          whether ``step`` can return a non-None ``tx_mask`` (config-
          static, so maskless configs trace the exact seed code path).
    """
    name: str
    init: Callable
    step: Callable
    noise_std: Callable
    stateful: Callable = lambda cfg: False
    may_mask: Callable = lambda cfg: False


_REGISTRY: Dict[str, ChannelModel] = {}


def register_channel_model(name: str, model: ChannelModel, *,
                           overwrite: bool = False) -> ChannelModel:
    """Add a scenario under ``ChannelConfig.model == name``."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"channel model {name!r} already registered "
                         f"(pass overwrite=True to replace)")
    if model.init is None or model.step is None or model.noise_std is None:
        raise ValueError(f"channel model {name!r} needs init, step and "
                         f"noise_std hooks")
    _REGISTRY[name] = model
    return model


def unregister_channel_model(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_channel_model(name: str) -> ChannelModel:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown channel model {name!r}; registered: "
            f"{sorted(_REGISTRY)} (add new scenarios via "
            f"repro.core.channels.register_channel_model)") from None


def list_channel_models():
    return sorted(_REGISTRY)


# ------------------------------------------------------------ shared views

def effective_noise_std(cfg: ChannelConfig) -> float:
    """sigma_eff of the configured model — the one value the β privacy
    cap, the ledger ε spend, and the receiver noise draw must agree on."""
    return float(get_channel_model(cfg.model).noise_std(cfg))


def observed_gains(cr: ChannelRound) -> jnp.ndarray:
    """The gains the devices precompensate with (true gains under perfect
    CSI)."""
    return cr.gains if cr.gains_obs is None else cr.gains_obs


def design_gains(cr: ChannelRound) -> jnp.ndarray:
    """The (r,) gains β-design should min over: the OBSERVED gains
    (ISSUE 4 — the power cap must hold for the precompensation the
    devices actually apply), with dropped-out clients lifted to
    ``DESIGN_GAIN_BIG`` so they never bind the min (they transmit
    nothing, so no power constraint applies) — the r-realized-vs-
    r-nominal path of the β design."""
    g = observed_gains(cr)
    if cr.tx_mask is None:
        return g
    return jnp.where(cr.tx_mask > 0, g, jnp.float32(DESIGN_GAIN_BIG))


def realized_cohort_size(cr: ChannelRound, r: int) -> jnp.ndarray:
    """f32 count of clients that actually transmitted this round (== r
    unless the model masks transmissions)."""
    if cr.tx_mask is None:
        return jnp.asarray(float(r), jnp.float32)
    return jnp.sum(cr.tx_mask).astype(jnp.float32)
