"""The public FL training API: ``Trainer`` + ``TrainState`` (DESIGN.md §8).

One object owns the compiled round machinery (``Trainer``) and one
registered pytree owns ALL loop state (``TrainState``): params as a pytree
(ravel/unravel is an internal detail), the per-client ``ClientBank``
(error-feedback residuals, PRNG lanes, participation counts —
``repro.fl.bank``, DESIGN.md §10), the previous round's reconstructed
update ``prev_delta`` (the server_topk support source), the per-device
power limits, the PRNG key, the round counter, and the in-graph privacy
ledger (``repro.core.privacy.LedgerState``), whose (ε, δ) accumulators are
updated INSIDE the compiled program from the realized per-round β — so
``Trainer.run`` (the ``lax.scan`` path) returns exact budget totals
without T host round-trips, and chunked resume carries the ledger
automatically.

``cfg.bank_backend`` selects where the bank lives: ``resident`` (dense
device arrays in the scan carry — the bit-exact reference) or
``streamed`` (host-side bank + double-buffer-prefetched cohort slices;
device memory independent of ``num_clients``). The two are bit-identical
under the same key; ``run``/``step`` signatures do not change.

``Trainer.step(state, data_x, data_y) -> (state, metrics)`` and
``Trainer.run(state, data_x, data_y, rounds=T) -> (state, stacked_metrics)``
have one fixed signature and return shape regardless of config — no
``error_feedback`` 3-tuples, no ``delta_hat`` metrics key. Algorithms come
from the ``repro.fl.algorithms`` registry, so new transmit schemes plug in
as entries, not branches.

PRNG contract (also in DESIGN.md §8): ``state.key`` is the key the next
call consumes. ``step`` consumes it whole as the round key and advances it
by ``fold_in(key, 1)``. ``run(T)`` uses ``jax.random.split(state.key, T)``
as its T round keys and advances by ``fold_in(key, T)``. So ``run(T)``
equals T ``step`` calls fed those round keys, not T plain ``step`` calls
(tests/test_trainer_api.py::test_run_matches_step_loop).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh

from repro.configs.base import PFELSConfig
from repro.core import channels, compressors, privacy
from repro.data import loader
from repro.fl import algorithms, rounds
from repro.fl import bank as bank_lib

# init derives the round-key stream by folding this tag into the init key,
# so power-limit sampling and the training stream never share a key
_RUN_STREAM_TAG = 0x5047  # "PG"
# ...and the channel model's init state gets its own fork for the same
# reason (markov_fading's stationary start must not alias either stream)
_CHAN_STREAM_TAG = 0x4348  # "CH"


@dataclass
class TrainState:
    """All state of the Alg. 2 server loop, as one registered pytree.

    Donate-safe and scan-carry-safe: every field is an array (or params
    pytree), so checkpointing, ``lax.scan``, and chunked resume carry the
    whole loop — including the privacy ledger and the per-client
    ``ClientBank`` state — with no host-side bookkeeping. ``bank`` holds
    ALL per-client persistent state (error-feedback residuals, PRNG
    lanes, participation counts; DESIGN.md §10) — device arrays under the
    ``resident`` backend, host numpy under ``streamed``. ``prev_delta``
    starts at zeros (the documented server_topk cold start). ``chan`` is
    the channel model's cross-round carry (DESIGN.md §11) — ``None`` for
    stateless models (block_fading, mimo_mrc), the population's latent
    fading state for markov_fading — always device-resident, under both
    bank backends.
    """
    params: Any                       # model pytree
    power_limits: jnp.ndarray         # (N,) P_i, fixed per device
    bank: bank_lib.BankState          # per-client state (DESIGN.md §10)
    prev_delta: jnp.ndarray           # (d,) last reconstructed Delta_hat
    key: jnp.ndarray                  # PRNG key the NEXT step/run consumes
    round: jnp.ndarray                # i32 scalar, rounds completed
    ledger: privacy.LedgerState       # in-graph (eps, delta) accumulators
    chan: Any = None                  # channel-model carry (DESIGN.md §11;
    #                                   None for stateless models)

    @property
    def residuals(self) -> Optional[jnp.ndarray]:
        """(N, d) error-feedback memory (None unless
        ``cfg.error_feedback``) — lives in the bank; kept as a read alias
        for the pre-bank field."""
        return self.bank.residuals


jax.tree_util.register_dataclass(
    TrainState,
    data_fields=["params", "power_limits", "bank", "prev_delta",
                 "key", "round", "ledger", "chan"],
    meta_fields=[])


class Trainer:
    """Compiled Alg. 2 server loop over a registry algorithm.

    ``Trainer(cfg, loss_fn, params_template, mesh=None)``:

    - ``cfg``: :class:`PFELSConfig`; ``cfg.algorithm`` is resolved through
      ``repro.fl.algorithms.get_algorithm``.
    - ``loss_fn(params, {"x","y"}) -> (loss, aux)``.
    - ``params_template``: a concrete params pytree — defines the flat
      dimension ``d`` and the unravel mapping internally, and is the
      default initial params for :meth:`init`.
    - ``mesh``: cohort mesh for ``cfg.client_sharding="cohort"``
      (``None`` builds ``make_cohort_mesh(cfg.clients_per_round)``).

    ``step`` is a jitted callable attribute (so ``trainer.step.lower(...)``
    works for dry-runs); ``run`` jits one program per distinct T.
    """

    def __init__(self, cfg: PFELSConfig, loss_fn: Callable,
                 params_template: Any, mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.algorithm = algorithms.get_algorithm(cfg.algorithm)
        self.channel_model = channels.get_channel_model(cfg.channel.model)
        flat, unravel = ravel_pytree(params_template)
        self.d = int(flat.shape[0])
        self.unravel = unravel
        self._params_template = params_template
        self.mesh = rounds._resolve_cohort_mesh(cfg, mesh)
        # carry-compressors (top_k_ef) force the bank's error-feedback
        # residual memory on even with cfg.error_feedback=False
        # (DESIGN.md §13) — mirrors the round body's ``ef_on`` static
        ef_on = cfg.error_feedback or (
            self.algorithm.aircomp and self.algorithm.sparsifies_transmit
            and compressors.carry_required(cfg))
        self.bank = bank_lib.make_bank(cfg.bank_backend, cfg.num_clients,
                                       self.d, ef_on)
        if self.bank.backend == "streamed" and self.mesh is not None:
            raise ValueError(
                "bank_backend='streamed' is host-driven and does not "
                "compose with client_sharding='cohort' yet — stream the "
                "bank OR shard the cohort (DESIGN.md §10)")
        self._cohort_core = rounds._build_cohort_core(
            cfg, loss_fn, self.d, unravel, self.mesh)
        self.step = (self._streamed_step_api
                     if self.bank.backend == "streamed"
                     else jax.jit(self._step_impl))
        self._run_cache: Dict[int, Callable] = {}
        self._cohort_step_jit: Optional[Callable] = None

    # ------------------------------------------------------------- state

    def init(self, key, params: Any = None) -> TrainState:
        """Fresh TrainState: power limits drawn from ``key``
        (:func:`repro.fl.rounds.init_power_limits`), zeroed
        ledger/bank/prev_delta, the channel model's cross-round state
        initialized from its own fork of ``key`` (None for stateless
        models), and the round-key stream forked off ``key`` (never
        reusing the power-limit draw). The first ``step`` consumes
        ``state.key`` whole; ``run(T)`` uses ``split(state.key, T)``."""
        params = self._params_template if params is None else params
        return TrainState(
            params=params,
            power_limits=rounds.init_power_limits(key, self.cfg, self.d),
            bank=self.bank.init(),
            prev_delta=jnp.zeros((self.d,), jnp.float32),
            key=jax.random.fold_in(key, _RUN_STREAM_TAG),
            round=jnp.zeros((), jnp.int32),
            ledger=privacy.ledger_init(),
            chan=self.channel_model.init(
                jax.random.fold_in(key, _CHAN_STREAM_TAG),
                self.cfg.num_clients, self.cfg.channel))

    def _advance(self, state: TrainState, n: int, params, bank,
                 prev_delta, ledger, chan) -> TrainState:
        with jax.named_scope("pfels.cohort"):
            return TrainState(
                params=params, power_limits=state.power_limits,
                bank=bank, prev_delta=prev_delta,
                key=jax.random.fold_in(state.key, n),
                round=state.round + n, ledger=ledger, chan=chan)

    def _spend(self, ledger, metrics):
        """Ledger update + the uniform ``eps_round`` metric. Whether the
        algorithm spends budget is static config, so non-DP schemes carry
        the ledger through untouched (their totals stay (0.0, 0.0) — the
        empty-ledger contract)."""
        with jax.named_scope("pfels.ledger"):
            if self.algorithm.privacy_spend is None:
                eps_round = jnp.zeros((), jnp.float32)
            else:
                eps_round = jnp.asarray(
                    self.algorithm.privacy_spend(self.cfg, metrics["beta"],
                                                 self.d),
                    jnp.float32)
                ledger = privacy.ledger_spend(ledger, eps_round)
        return ledger, dict(metrics, eps_round=eps_round)

    # ------------------------------------------------------------- loops

    def _bank_round(self, params, power_limits, bank, prev_delta, chan,
                    data_x, data_y, round_key, t, eps_spent):
        """One round against the in-graph (resident) bank: sample the
        cohort, gather its slices, run the cohort core (which also evolves
        the channel-model carry, DESIGN.md §11), scatter the residual
        slice + this round's bank lanes back (DESIGN.md §10). ``t`` (the
        absolute round counter) and ``eps_spent`` (the ledger's running
        sum) feed the CompressionSchedule inside the compiled body
        (DESIGN.md §13) — traced scalars, never a host round-trip."""
        with jax.named_scope("pfels.cohort"):
            ks = rounds.split_round_key(round_key)
            sel = rounds.sample_cohort(
                ks[rounds.ROUND_KEY_LANES["selection"]],
                self.cfg.num_clients, self.cfg.clients_per_round)
            res_sel = self.bank.gather(bank, sel)
            p_sel, cx, cy = power_limits[sel], data_x[sel], data_y[sel]
        new_params, metrics, new_res_sel, delta_hat, new_chan = \
            self._cohort_core(params, p_sel, cx, cy, ks, res_sel,
                              prev_delta, chan, sel, t, eps_spent)
        with jax.named_scope("pfels.bank"):
            lanes = bank_lib.cohort_lane_keys(
                ks[rounds.ROUND_KEY_LANES["bank"]], sel)
            new_bank = self.bank.scatter(bank, sel, new_res_sel, lanes)
        return new_params, metrics, new_bank, delta_hat, new_chan

    def _step_impl(self, state: TrainState, data_x, data_y):
        new_params, metrics, new_bank, delta_hat, new_chan = \
            self._bank_round(
                state.params, state.power_limits, state.bank,
                state.prev_delta, state.chan, data_x, data_y, state.key,
                state.round, state.ledger.eps_sum)
        ledger, metrics = self._spend(state.ledger, metrics)
        return self._advance(state, 1, new_params, new_bank, delta_hat,
                             ledger, new_chan), metrics

    def run(self, state: TrainState, data_x, data_y=None,
            rounds: Optional[int] = None):
        """T rounds (T defaults to ``cfg.rounds``). Returns
        ``(state, metrics)`` with every metrics leaf stacked over the T
        rounds (leading axis T). Chunked resume is just calling ``run``
        again with the returned state — the bank (EF residuals, lanes,
        counts), server_topk support, PRNG stream, and the privacy ledger
        all carry in ``TrainState``.

        Under the ``resident`` bank this is ONE ``lax.scan`` program over
        device-resident population tensors. Under ``streamed`` it is the
        host-driven cohort loop (DESIGN.md §10): ``data_x``/``data_y`` may
        be host arrays or a :class:`repro.data.loader.CohortSource`, the
        per-round cohorts are double-buffer prefetched, and only
        ``(r, ...)`` slices ever reach the device — both backends are
        bit-identical under the same key."""
        t = self.cfg.rounds if rounds is None else int(rounds)
        if self.bank.backend == "streamed":
            return self._run_streamed(state, data_x, data_y, t)
        return self.run_program(t)(state, data_x, data_y)

    def run_program(self, rounds: int):
        """The jitted ``trainer_run(state, data_x, data_y)`` program that
        ``run(rounds=rounds)`` calls under the resident bank, so that
        ``trainer.run_program(T).lower(state, x, y).compile()`` gives its
        compiled text ahead of time (as ``trainer.step.lower`` does)."""
        if self.bank.backend == "streamed":
            raise ValueError("the streamed bank runs a host loop of "
                             "rounds, not one program")
        t = int(rounds)
        fn = self._run_cache.get(t)
        if fn is None:
            # named, so compile logs and profiles can find the program
            def trainer_run(s, x, y):
                return self._run_impl(s, x, y, t)
            fn = jax.jit(trainer_run)
            self._run_cache[t] = fn
        return fn

    def _run_impl(self, state: TrainState, data_x, data_y, t_rounds: int):
        def body(carry, xs):
            round_key, t = xs
            p, bank, prev, ledger, chan = carry
            p2, metrics, bank2, delta_hat, chan2 = self._bank_round(
                p, state.power_limits, bank, prev, chan, data_x, data_y,
                round_key, t, ledger.eps_sum)
            ledger, metrics = self._spend(ledger, metrics)
            return (p2, bank2, delta_hat, ledger, chan2), metrics

        with jax.named_scope("pfels.cohort"):
            keys = jax.random.split(state.key, t_rounds)
            # absolute round counters, so chunked resume anneals the
            # CompressionSchedule from where the last chunk stopped
            ts = state.round + jnp.arange(t_rounds, dtype=jnp.int32)
        (p_f, bank_f, delta_f, ledger_f, chan_f), metrics = jax.lax.scan(
            body, (state.params, state.bank, state.prev_delta,
                   state.ledger, state.chan), (keys, ts))
        return self._advance(state, t_rounds, p_f, bank_f, delta_f,
                             ledger_f, chan_f), metrics

    # ------------------------------------------------- streamed execution

    def _cohort_step(self):
        """The jitted streamed round: pure cohort slices in, cohort slices
        out. The ``res_sel`` gather buffer is donated — XLA reuses it for
        the ``new_res_sel`` output, so the (r, d) scatter staging buffer
        is recycled across rounds instead of accumulating (DESIGN.md §10).
        ``cx``/``cy`` are not donated: no output shares their shape, so
        donation could never be honored."""
        if self._cohort_step_jit is None:
            # ``t`` rides at the END so res_sel keeps position 6 for the
            # donate_argnums contract; the schedule's eps_spent comes from
            # the ledger argument INSIDE the jitted step (same traced
            # value the resident scan reads from its carry)
            def step_fn(params, p_sel, cx, cy, ks, sel, res_sel,
                        prev_delta, ledger, chan, t):
                new_params, metrics, new_res_sel, delta_hat, new_chan = \
                    self._cohort_core(params, p_sel, cx, cy, ks, res_sel,
                                      prev_delta, chan, sel, t,
                                      ledger.eps_sum)
                ledger, metrics = self._spend(ledger, metrics)
                with jax.named_scope("pfels.bank"):
                    lanes = bank_lib.cohort_lane_keys(
                        ks[rounds.ROUND_KEY_LANES["bank"]], sel)
                return (new_params, metrics, new_res_sel, lanes, delta_hat,
                        ledger, new_chan)

            self._cohort_step_jit = jax.jit(step_fn, donate_argnums=(6,))
        return self._cohort_step_jit

    def _streamed_rounds(self, state: TrainState, source, round_keys):
        """Drive ``len(round_keys)`` rounds with the bank host-side: only
        the sampled cohort's data/residual slices move on/off device.

        Clones the host bank ONCE per call (callers keep their states
        valid), so the O(n·d) memcpy amortizes over the rounds of a
        ``run`` — prefer ``run(rounds=T)`` over a ``step`` loop with the
        streamed backend."""
        cfg = self.cfg
        n, r = cfg.num_clients, cfg.clients_per_round
        if getattr(source, "n", n) != n:
            raise ValueError(
                f"cohort source serves {source.n} clients but "
                f"cfg.num_clients={n}: Alg. 2 line 2 samples from "
                f"cfg.num_clients, so a mismatched source silently "
                f"truncates the population (and the Thm 2 r/n "
                f"accounting)")
        # one host span per step, in the profiler's trace when it records:
        # ``round`` is the call's round the step serves (the call's set-up
        # counts to round 0, stacking the metrics to its last round),
        # ``bytes`` what the step moves between host and device
        span = jax.profiler.TraceAnnotation
        with span("streamed.cohorts", round=0):
            ks_all = jax.vmap(rounds.split_round_key)(round_keys)  # (T,7,·)
            sels = jax.vmap(lambda ks: rounds.sample_cohort(
                ks[rounds.ROUND_KEY_LANES["selection"]], n, r))(ks_all)
            sels_np = np.asarray(sels)
        step_fn = self._cohort_step()

        with span("streamed.bank_clone", round=0):
            bank = self.bank.clone(state.bank)   # callers keep their state
        params, prev_delta, ledger = state.params, state.prev_delta, \
            state.ledger
        chan = state.chan                    # device-resident model carry
        per_round = []
        prefetch = loader.prefetch_cohorts(source, sels_np)
        for ti, sel in enumerate(sels_np):
            with span("streamed.prefetch_wait", round=ti):
                cx, cy = next(prefetch)
            with span("streamed.gather", round=ti) as sp:
                res_sel = self.bank.gather(bank, sel)
                if res_sel is not None:
                    res_sel = jnp.asarray(res_sel)
                    sp.set_metadata(bytes=res_sel.nbytes)
            with span("streamed.dispatch", round=ti):
                params, metrics, new_res_sel, lanes, prev_delta, ledger, \
                    chan = step_fn(
                        params, jnp.asarray(state.power_limits)[sel],
                        cx, cy, ks_all[ti], jnp.asarray(sel), res_sel,
                        prev_delta, ledger, chan,
                        state.round + jnp.asarray(ti, jnp.int32))
            with span("streamed.scatter", round=ti, bytes=lanes.nbytes + (
                    0 if new_res_sel is None else new_res_sel.nbytes)):
                bank = self.bank.scatter(bank, sel, new_res_sel, lanes)
            per_round.append(metrics)
        prefetch.close()
        with span("streamed.stack_metrics", round=len(sels_np) - 1):
            stacked = {k: np.stack([np.asarray(m[k]) for m in per_round])
                       for k in per_round[0]}
        return params, stacked, bank, prev_delta, ledger, chan

    def _run_streamed(self, state: TrainState, data_x, data_y, t: int):
        if t < 1:
            raise ValueError(
                "run(rounds=0) is not meaningful with the streamed bank "
                "(the metric structure comes from executed rounds); call "
                "with rounds >= 1")
        source = loader.as_cohort_source(data_x, data_y)
        keys = jax.random.split(state.key, t)
        params, metrics, bank, prev_delta, ledger, chan = \
            self._streamed_rounds(state, source, keys)
        return self._advance(state, t, params, bank, prev_delta,
                             ledger, chan), metrics

    def _streamed_step_api(self, state: TrainState, data_x, data_y=None):
        """Streamed ``step``: consumes ``state.key`` whole as the round
        key (the resident schedule), not ``split(key, 1)``."""
        source = loader.as_cohort_source(data_x, data_y)
        params, metrics, bank, prev_delta, ledger, chan = \
            self._streamed_rounds(state, source, state.key[None])
        metrics = {k: v[0] for k, v in metrics.items()}
        return self._advance(state, 1, params, bank, prev_delta,
                             ledger, chan), metrics

    # ------------------------------------------------------- conveniences

    def ledger_totals(self, state: TrainState,
                      delta_prime: float = 1e-6) -> Dict[str, Any]:
        """Host-side (eps_T, delta_T) report from the in-graph ledger,
        matching the host-side ``PrivacyLedger`` contract."""
        delta = self.cfg.resolved_delta()
        return {
            "basic": privacy.ledger_totals_basic(state.ledger, delta),
            "advanced": privacy.ledger_totals_advanced(state.ledger, delta,
                                                       delta_prime),
            "eps_max_round": float(state.ledger.eps_max),
            "spends": int(state.ledger.spends),
        }

    def evaluate(self, state: TrainState, xt, yt, batch: int = 256):
        """(test_loss, test_accuracy) of ``state.params`` — thin wrapper
        over :func:`repro.fl.rounds.evaluate`."""
        return rounds.evaluate(state.params, self.loss_fn, xt, yt,
                               batch=batch)


def replace(state: TrainState, **kw) -> TrainState:
    """``dataclasses.replace`` re-export for ergonomic state surgery
    (tests pin ``key=``; checkpoint restore swaps ``params=``)."""
    return dataclasses.replace(state, **kw)
