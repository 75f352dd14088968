"""The unified Trainer/TrainState API (DESIGN.md §8): golden parity against
the legacy ``make_round_fn``/``make_training_fn`` shims under identical
keys, in-graph ledger totals vs the host-side ``PrivacyLedger``, uniform
signatures, chunked resume, and algorithm-registry round-trip."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.core import privacy
from repro.data import make_federated_classification
from repro.fl import (Algorithm, Trainer, make_round_fn, make_training_fn,
                      register_algorithm, round_epsilon_spent, setup,
                      unregister_algorithm)
from repro.fl.api import replace
from repro.launch.mesh import make_cohort_mesh
from repro.models import cnn

MULTI = len(jax.devices()) >= 2
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


def _legacy(cfg, problem, mesh=None):
    """(round_fn, training_fn(T=3), legacy FLState) with warnings silenced
    — the shims are deprecated by design and these are the parity tests."""
    params, d, unravel, _, loss_fn = problem
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        fn = make_round_fn(cfg, loss_fn, d, unravel, mesh=mesh)
        tf = make_training_fn(cfg, loss_fn, d, unravel, rounds=3, mesh=mesh)
        st = setup(jax.random.PRNGKey(1), params, cfg, d)
    return fn, tf, st


def _trainer_state(cfg, problem, mesh=None):
    params, d, unravel, _, loss_fn = problem
    trainer = Trainer(cfg, loss_fn, params, mesh=mesh)
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


# dp_fedavg's server noise moves every parameter by O(1). The two
# separately jitted programs (Trainer.step and the legacy round) let XLA
# round the noisy update differently in the last bit, so their params may
# differ by a few ulps of the larger of each parameter's start and end
# magnitudes; every metric still matches bitwise.
ULP_CASES = {"dp_fedavg"}
MAX_ULP = 4


def _assert_params_match(case, got, want, start):
    got, want = np.asarray(_flat(got)), np.asarray(_flat(want))
    if case not in ULP_CASES:
        assert bool(np.array_equal(got, want))
        return
    scale = np.maximum(np.abs(np.asarray(_flat(start))), np.abs(want))
    assert np.all(np.abs(got - want) <= MAX_ULP * np.spacing(scale))


@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_step_and_run_match_legacy_bitwise(problem, case):
    """Trainer.step == legacy make_round_fn and Trainer.run == legacy
    make_training_fn, bit-for-bit under the same PRNG key, for every
    registered paper algorithm and execution option (params of the
    ``ULP_CASES`` to a few ulps, see above)."""
    cfg = PFELSConfig(**BASE, **PARITY_CASES[case])
    d = problem[1]
    x, y = problem[3][0], problem[3][1]
    fn, tf, legacy_st = _legacy(cfg, problem)
    trainer, state = _trainer_state(cfg, problem)

    # power limits: init(key) draws what setup(key) drew
    assert bool(jnp.array_equal(state.power_limits, legacy_st.power_limits))

    # single round: step consumes state.key exactly like round_fn(key=...)
    out = fn(state.params, legacy_st.power_limits, x, y,
             jax.random.PRNGKey(2), legacy_st.residuals,
             jnp.zeros((d,), jnp.float32))
    new_state, metrics = trainer.step(state, x, y)
    _assert_params_match(case, new_state.params, out[0], state.params)
    for k in ("train_loss", "beta", "energy", "subcarriers"):
        assert bool(jnp.array_equal(metrics[k], out[1][k])), k
    if cfg.error_feedback:
        assert bool(jnp.array_equal(new_state.residuals, out[2]))

    # T rounds: run splits state.key exactly like the legacy scan driver
    pT, mT, resT, deltaT = tf(state.params, legacy_st.power_limits, x, y,
                              jax.random.PRNGKey(2), legacy_st.residuals)
    run_state, run_metrics = trainer.run(state, x, y, rounds=3)
    _assert_params_match(case, run_state.params, pT, state.params)
    assert bool(jnp.array_equal(run_state.prev_delta, deltaT))
    assert bool(jnp.array_equal(run_metrics["train_loss"],
                                mT["train_loss"]))
    if cfg.error_feedback:
        assert bool(jnp.array_equal(run_state.residuals, resT))
    assert int(run_state.round) == 3


@pytest.mark.skipif(not MULTI, reason="needs >= 2 host devices (the CI "
                    "docs job forces 8)")
def test_trainer_matches_legacy_under_cohort_sharding(problem):
    """The sharded cohort path through the Trainer equals the sharded
    legacy path bitwise (both route the identical core)."""
    cfg = PFELSConfig(**BASE, client_sharding="cohort")
    mesh = make_cohort_mesh(cfg.clients_per_round)
    x, y = problem[3][0], problem[3][1]
    fn, _, legacy_st = _legacy(cfg, problem, mesh=mesh)
    trainer, state = _trainer_state(cfg, problem, mesh=mesh)
    pL, _ = fn(state.params, legacy_st.power_limits, x, y,
               jax.random.PRNGKey(2))
    new_state, _ = trainer.step(state, x, y)
    assert bool(jnp.array_equal(_flat(new_state.params), _flat(pL)))


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


def test_legacy_shims_warn_and_leak_behind_deprecation(problem):
    params, d, unravel, (x, y, _, _), loss_fn = problem
    cfg = PFELSConfig(**BASE, randk_mode="server_topk")
    with pytest.deprecated_call():
        fn = make_round_fn(cfg, loss_fn, d, unravel)
    with pytest.deprecated_call():
        st = setup(jax.random.PRNGKey(1), params, cfg, d)
    _, m = fn(params, st.power_limits, x, y, jax.random.PRNGKey(2))
    assert "delta_hat" in m   # seed-era contract, kept behind the warning


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
