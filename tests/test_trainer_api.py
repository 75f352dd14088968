"""The unified Trainer/TrainState API (DESIGN.md §8): ``run(T)`` equals T
``step`` calls fed its round keys, in-graph ledger totals vs the host-side
``PrivacyLedger``, uniform signatures, chunked resume, and
algorithm-registry round-trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs import ChannelConfig, CompressionSchedule, PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.core import privacy
from repro.data import make_federated_classification
from repro.fl import (Algorithm, Trainer, register_algorithm,
                      round_epsilon_spent, rounds, unregister_algorithm)
from repro.fl.api import replace
from repro.models import cnn

BASE = dict(num_clients=20, clients_per_round=4, local_steps=2,
            local_lr=0.05, compression_ratio=0.3, epsilon=2.0, rounds=2)


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=20, per_client=20, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, flat.shape[0], unravel, (x, y, xt, yt), loss_fn


def _flat(p):
    return ravel_pytree(p)[0]


def _trainer_state(cfg, problem):
    params, _, _, _, loss_fn = problem
    trainer = Trainer(cfg, loss_fn, params)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(2))
    return trainer, state


PARITY_CASES = {
    "base": {},
    "error_feedback": dict(error_feedback=True, transmit_clip=0.5),
    "server_topk": dict(randk_mode="server_topk"),
    "fused_kernel": dict(use_fused_kernel=True),
    "wfl_p": dict(algorithm="wfl_p"),
    "wfl_pdp": dict(algorithm="wfl_pdp"),
    "dp_fedavg": dict(algorithm="dp_fedavg"),
    "fedavg": dict(algorithm="fedavg"),
}


# PARITY_CASES plus the configurations whose state crosses rounds in a
# carry of its own: the channel model's (markov_fading), the round counter
# and the ledger's running spend that feed the schedule (budget_schedule),
# and the bank residuals a carry compressor forces on (top_k_ef).
RUN_CASES = {
    **PARITY_CASES,
    "markov_fading": dict(channel=ChannelConfig(model="markov_fading")),
    "budget_schedule": dict(schedule=CompressionSchedule(
        mode="budget", k_end_ratio=0.5, power_end=0.5)),
    "top_k_ef": dict(compressor="top_k_ef"),
}


def _assert_same(got, want, what):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert bool(jnp.array_equal(g, w)), what


@pytest.mark.parametrize("case", sorted(RUN_CASES))
def test_run_matches_step_loop(problem, case):
    """Trainer.run(T=3) == three Trainer.step calls fed
    ``split(state.key, 3)``, bit-for-bit: params, prev_delta, residuals,
    channel carry, every stacked metric, the ledger and the round
    counter."""
    params, d, _, (x, y, _, _), loss_fn = problem
    cfg = PFELSConfig(**BASE, **RUN_CASES[case])
    trainer, state = _trainer_state(cfg, problem)
    assert bool(jnp.array_equal(
        trainer.init(jax.random.PRNGKey(1)).power_limits,
        rounds.init_power_limits(jax.random.PRNGKey(1), cfg, d)))

    run_state, run_metrics = trainer.run(state, x, y, rounds=3)
    s, step_metrics = state, []
    for round_key in jax.random.split(state.key, 3):
        s, m = trainer.step(replace(s, key=round_key), x, y)
        step_metrics.append(m)

    _assert_same(run_state.params, s.params, "params")
    _assert_same(run_state.prev_delta, s.prev_delta, "prev_delta")
    _assert_same(run_state.bank, s.bank, "bank")
    _assert_same(run_state.chan, s.chan, "chan")
    _assert_same(run_state.ledger, s.ledger, "ledger")
    assert set(run_metrics) == set(step_metrics[0])
    for k in run_metrics:
        _assert_same(run_metrics[k],
                     jnp.stack([m[k] for m in step_metrics]), k)
    assert int(run_state.round) == int(s.round) == 3
    assert bool(jnp.array_equal(run_state.key,
                                jax.random.fold_in(state.key, 3)))


def test_uniform_signature_and_no_metrics_leak(problem):
    """One return shape regardless of config: always (state, metrics), no
    'delta_hat' metrics key, identical metric-key sets across algorithms;
    server_topk support state is explicit TrainState.prev_delta."""
    x, y = problem[3][0], problem[3][1]
    keysets = set()
    for case, extra in PARITY_CASES.items():
        cfg = PFELSConfig(**BASE, **extra)
        trainer, state = _trainer_state(cfg, problem)
        state, metrics = trainer.step(state, x, y)
        assert "delta_hat" not in metrics, case
        keysets.add(frozenset(metrics))
        if extra.get("randk_mode") == "server_topk":
            state, _ = trainer.step(state, x, y)
            k = max(int(round(cfg.compression_ratio * trainer.d)), 1)
            assert int(jnp.sum(state.prev_delta != 0)) <= k
    assert len(keysets) == 1   # the fixed metrics contract


def test_in_graph_ledger_matches_host_ledger(problem):
    """Trainer.run's compiled (eps, delta) accumulators equal the Python
    PrivacyLedger fed the same per-round betas, to fp32 tolerance."""
    params, d, unravel, (x, y, _, _), loss_fn = problem
    for alg in ("pfels", "wfl_pdp"):
        cfg = PFELSConfig(**BASE, **({} if alg == "pfels"
                                     else {"algorithm": alg}))
        trainer, state = _trainer_state(cfg, problem)
        t = 6
        end, metrics = trainer.run(state, x, y, rounds=t)

        host = privacy.PrivacyLedger(n=cfg.num_clients,
                                     delta=cfg.resolved_delta())
        for beta in np.asarray(metrics["beta"]):
            host.spend(min(round_epsilon_spent(cfg, float(beta)),
                           cfg.epsilon))
        totals = trainer.ledger_totals(end)
        np.testing.assert_allclose(totals["basic"], host.total_basic(),
                                   rtol=1e-5)
        np.testing.assert_allclose(totals["advanced"],
                                   host.total_advanced(), rtol=1e-5)
        assert totals["spends"] == t
        # eps_round metric is what the ledger saw, round for round
        np.testing.assert_allclose(np.asarray(metrics["eps_round"]),
                                   host.eps_rounds, rtol=1e-6)


def test_non_dp_algorithms_keep_empty_ledger(problem):
    """wfl_p/fedavg carry no per-round guarantee: the ledger must stay at
    the empty-ledger contract (0.0, 0.0), not accumulate zero-eps rounds."""
    x, y = problem[3][0], problem[3][1]
    for alg in ("wfl_p", "fedavg"):
        cfg = PFELSConfig(**BASE, algorithm=alg)
        trainer, state = _trainer_state(cfg, problem)
        end, _ = trainer.run(state, x, y, rounds=3)
        totals = trainer.ledger_totals(end)
        assert totals["basic"] == (0.0, 0.0)
        assert totals["advanced"] == (0.0, 0.0)
        assert totals["spends"] == 0


def test_chunked_resume_carries_all_state(problem):
    """run(3); run(3) continues the ledger, the round counter, the PRNG
    stream, and the error-feedback memory without host bookkeeping."""
    x, y = problem[3][0], problem[3][1]
    cfg = PFELSConfig(**BASE, error_feedback=True)
    trainer, state = _trainer_state(cfg, problem)
    s1, m1 = trainer.run(state, x, y, rounds=3)
    s2, m2 = trainer.run(s1, x, y, rounds=3)
    assert int(s2.round) == 6
    assert int(s2.ledger.spends) == 6
    np.testing.assert_allclose(
        float(s2.ledger.eps_sum),
        float(jnp.sum(m1["eps_round"]) + jnp.sum(m2["eps_round"])),
        rtol=1e-6)
    assert not bool(jnp.array_equal(s1.key, s2.key))
    assert float(jnp.sum(jnp.abs(s2.residuals))) > 0


def test_trainstate_is_a_pytree(problem):
    """TrainState round-trips jax.tree flatten/unflatten (scan/donate/
    checkpoint safe)."""
    cfg = PFELSConfig(**BASE, error_feedback=True)
    trainer, state = _trainer_state(cfg, problem)
    leaves, treedef = jax.tree.flatten(state)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert bool(jnp.array_equal(_flat(rebuilt.params), _flat(state.params)))
    assert bool(jnp.array_equal(rebuilt.ledger.eps_sum,
                                state.ledger.eps_sum))


def test_registry_round_trip(problem):
    """Registering a toy digital scheme makes it a first-class
    cfg.algorithm value: two Trainer rounds run, params move, the ledger
    stays empty (no privacy_spend hook)."""
    from repro.core import aggregation

    def sign_aggregate(cfg, flat_updates, noise_key, *, d, r):
        return 0.01 * jnp.sign(aggregation.fedavg_aggregate(flat_updates))

    register_algorithm("toy_signsgd", Algorithm(
        name="toy_signsgd", aircomp=False,
        server_aggregate=sign_aggregate))
    try:
        x, y = problem[3][0], problem[3][1]
        cfg = PFELSConfig(**BASE, algorithm="toy_signsgd")
        trainer, state = _trainer_state(cfg, problem)
        end, metrics = trainer.run(state, x, y, rounds=2)
        assert jnp.all(jnp.isfinite(metrics["train_loss"]))
        assert not bool(jnp.array_equal(_flat(end.params),
                                        _flat(state.params)))
        assert trainer.ledger_totals(end)["spends"] == 0
        assert int(metrics["subcarriers"][0]) == trainer.d
    finally:
        unregister_algorithm("toy_signsgd")


def test_registry_validation():
    with pytest.raises(KeyError, match="unknown algorithm"):
        from repro.fl import get_algorithm
        get_algorithm("no_such_scheme")
    with pytest.raises(ValueError, match="already registered"):
        register_algorithm("pfels", Algorithm(
            name="pfels", aircomp=False, server_aggregate=lambda *a, **k: 0))
    with pytest.raises(ValueError, match="needs select_support"):
        register_algorithm("half_aircomp", Algorithm(
            name="half_aircomp", aircomp=True))
    with pytest.raises(ValueError, match="needs a"):
        register_algorithm("no_agg", Algorithm(
            name="no_agg", aircomp=False))
