"""ClientBank — population-scale per-client persistent state (DESIGN.md §10).

The paper samples r clients from a population of n per round (Alg. 2
line 2); everything the server must REMEMBER about individual clients
between their (rare) participations lives here, behind one interface:

  - the error-feedback residual memory ``e_i`` [28-30], ``(n, d)``;
  - the per-client PRNG lane keys (the round's ``ks[5]`` bank lane folded
    with the client id — the documented hook for client-local
    stochasticity such as dropout or local DP noise, DESIGN.md §5);
  - the per-client participation counts (Thm 2 subsampling bookkeeping).

Two backends share the ``ClientBank`` interface:

  - ``resident`` — dense device arrays, carried through ``lax.scan`` as
    part of ``TrainState``; bit-identical to the pre-bank behavior. The
    right choice while ``(n, d)`` fits device memory.
  - ``streamed`` — the bank stays host-side (numpy); only the sampled
    r-client cohort slice moves on/off device each round through the
    Trainer's donated gather/scatter buffers. Device memory is then
    independent of n (``benchmarks/population_scale.py`` trains
    n = 100_000), and the two backends are bit-identical at any n under
    the same key (``tests/test_bank.py``).

``BankState`` is the data (a registered pytree, so it checkpoints and
scan-carries); the backend objects are stateless policy — ``gather`` /
``scatter`` are traceable jnp ops for ``resident`` and in-place numpy for
``streamed`` (the Trainer clones the state at each ``run`` entry, so
caller-held states stay valid).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

BACKENDS = ("resident", "streamed")

# lane keys have the shape/dtype of a raw threefry key (conftest pins
# x64 off)
_KEY_SHAPE = tuple(jax.random.PRNGKey(0).shape)
_KEY_DTYPE = jax.random.PRNGKey(0).dtype


@dataclass
class BankState:
    """All per-client persistent state, one registered pytree.

    ``residuals`` is ``None`` unless ``cfg.error_feedback``; ``lanes``
    holds each client's latest bank-lane key (zeros until first
    participation); ``counts`` is the participation tally. Leaves are
    device arrays under the ``resident`` backend and host numpy under
    ``streamed`` — the structure (and checkpoint layout) is identical.
    """
    residuals: Optional[Any]    # (n, d) f32 error-feedback memory or None
    lanes: Any                  # (n,) + key shape, per-client PRNG lanes
    counts: Any                 # (n,) i32 participation counts


jax.tree_util.register_dataclass(
    BankState, data_fields=["residuals", "lanes", "counts"], meta_fields=[])


def cohort_lane_keys(bank_key, sel):
    """The round's per-client bank lanes: ``fold_in(ks[5], client_id)``
    for each selected client — key identity is pinned by the lane
    contract test (DESIGN.md §5)."""
    return jax.vmap(lambda i: jax.random.fold_in(bank_key, i))(sel)


class ClientBank:
    """Backend interface. ``gather``/``scatter`` move the sampled cohort's
    slice of the bank; everything else in the round never touches
    ``(n, d)`` state."""

    backend: str

    def __init__(self, n: int, d: int, error_feedback: bool):
        self.n, self.d, self.error_feedback = n, d, error_feedback

    def init(self) -> BankState:
        raise NotImplementedError

    def gather(self, bank: BankState, sel):
        """-> (r, d) residual slice for the cohort, or None without EF."""
        raise NotImplementedError

    def scatter(self, bank: BankState, sel, new_residuals, lanes
                ) -> BankState:
        """Write back the cohort's updated residual slice + this round's
        lane keys, and bump the participation counts."""
        raise NotImplementedError

    def clone(self, bank: BankState) -> BankState:
        """A state safe to mutate without invalidating the caller's copy
        (no-op for functional backends)."""
        return bank


class ResidentBank(ClientBank):
    """Dense device-array backend — jnp gather/scatter, traceable inside
    jit/scan. Bit-identical to the pre-bank dense residual arrays."""

    backend = "resident"

    def init(self) -> BankState:
        return BankState(
            residuals=(jnp.zeros((self.n, self.d), jnp.float32)
                       if self.error_feedback else None),
            lanes=jnp.zeros((self.n,) + _KEY_SHAPE, _KEY_DTYPE),
            counts=jnp.zeros((self.n,), jnp.int32))

    def gather(self, bank: BankState, sel):
        if bank.residuals is None:
            return None
        return bank.residuals[sel]

    def scatter(self, bank: BankState, sel, new_residuals, lanes
                ) -> BankState:
        res = bank.residuals
        if res is not None and new_residuals is not None:
            res = res.at[sel].set(new_residuals)
        return BankState(residuals=res,
                         lanes=bank.lanes.at[sel].set(lanes),
                         counts=bank.counts.at[sel].add(1))


class StreamedBank(ClientBank):
    """Host-side numpy backend: the ``(n, d)`` residual bank never leaves
    host memory; ``gather`` hands out the (r, d) cohort slice (the Trainer
    device-puts it into a donated buffer) and ``scatter`` writes the
    updated slice back IN PLACE — callers own a ``clone`` per run."""

    backend = "streamed"

    def init(self) -> BankState:
        return BankState(
            residuals=(np.zeros((self.n, self.d), np.float32)
                       if self.error_feedback else None),
            lanes=np.zeros((self.n,) + _KEY_SHAPE, _KEY_DTYPE),
            counts=np.zeros((self.n,), np.int32))

    def gather(self, bank: BankState, sel):
        if bank.residuals is None:
            return None
        return bank.residuals[np.asarray(sel)]

    def scatter(self, bank: BankState, sel, new_residuals, lanes
                ) -> BankState:
        sel = np.asarray(sel)
        if bank.residuals is not None and new_residuals is not None:
            bank.residuals[sel] = np.asarray(new_residuals)
        bank.lanes[sel] = np.asarray(lanes)
        bank.counts[sel] += 1
        return bank

    def clone(self, bank: BankState) -> BankState:
        return BankState(
            residuals=(None if bank.residuals is None
                       else np.array(bank.residuals)),
            lanes=np.array(bank.lanes), counts=np.array(bank.counts))


def make_bank(backend: str, n: int, d: int, error_feedback: bool
              ) -> ClientBank:
    """Backend factory keyed by ``PFELSConfig.bank_backend``."""
    if backend == "resident":
        return ResidentBank(n, d, error_feedback)
    if backend == "streamed":
        return StreamedBank(n, d, error_feedback)
    raise ValueError(f"unknown bank backend {backend!r}; "
                     f"choose from {BACKENDS}")


def to_host(bank: BankState) -> BankState:
    """Device -> host copy (resident state into streamed layout)."""
    return BankState(
        residuals=(None if bank.residuals is None
                   else np.asarray(bank.residuals)),
        lanes=np.asarray(bank.lanes), counts=np.asarray(bank.counts))


def to_device(bank: BankState) -> BankState:
    """Host -> device copy (streamed state into resident layout)."""
    return BankState(
        residuals=(None if bank.residuals is None
                   else jnp.asarray(bank.residuals)),
        lanes=jnp.asarray(bank.lanes), counts=jnp.asarray(bank.counts))
