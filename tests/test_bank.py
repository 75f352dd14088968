"""ClientBank (DESIGN.md §10): resident-vs-streamed bit parity under the
golden key, chunked resume with the bank carried in TrainState, the PRNG
key-lane contract (DESIGN.md §5), the streamed cohort data pipeline, and
TrainState+bank checkpointing."""
import dataclasses
import functools
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro import checkpoint
from repro.configs import ChannelConfig, PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.core import aggregation, channel, power_control, randk
from repro.data import (ArraySource, make_federated_classification,
                        make_population_source, prefetch_cohorts)
from repro.data.loader import ClientFnSource
from repro.fl import Trainer, make_bank, rounds
from repro.fl.api import replace
from repro.fl.bank import cohort_lane_keys
from repro.fl.client import local_train, model_update

BASE = dict(num_clients=20, clients_per_round=4, local_steps=2,
            local_lr=0.05, compression_ratio=0.3, epsilon=2.0, rounds=2)

PARITY_CASES = {
    "base": {},
    "error_feedback": dict(error_feedback=True, transmit_clip=0.5),
    "server_topk": dict(randk_mode="server_topk"),
    "fused_kernel": dict(use_fused_kernel=True),
    "imperfect_csi": dict(channel=ChannelConfig(csi_error=0.2)),
}


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    from repro.models import cnn
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=20, per_client=20, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, flat.shape[0], unravel, (x, y, xt, yt), loss_fn


def _flat(p):
    return ravel_pytree(p)[0]


def _trainer(cfg, problem):
    params, _, _, _, loss_fn = problem
    trainer = Trainer(cfg, loss_fn, params)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(2))
    return trainer, state


def _both_backends(case_cfg, problem):
    cfg_r = PFELSConfig(**BASE, **case_cfg)
    cfg_s = dataclasses.replace(cfg_r, bank_backend="streamed")
    return _trainer(cfg_r, problem), _trainer(cfg_s, problem)


def _assert_states_equal(sr, ss):
    """Bitwise equality of every TrainState leaf across backends."""
    assert bool(jnp.array_equal(_flat(sr.params), _flat(ss.params)))
    assert bool(jnp.array_equal(sr.prev_delta, jnp.asarray(ss.prev_delta)))
    if sr.bank.residuals is None:
        assert ss.bank.residuals is None
    else:
        assert bool(jnp.array_equal(sr.bank.residuals,
                                    jnp.asarray(ss.bank.residuals)))
    assert np.array_equal(np.asarray(sr.bank.counts),
                          np.asarray(ss.bank.counts))
    assert np.array_equal(np.asarray(sr.bank.lanes),
                          np.asarray(ss.bank.lanes))
    assert bool(jnp.array_equal(sr.ledger.eps_sum, ss.ledger.eps_sum))
    assert bool(jnp.array_equal(sr.ledger.eps_max, ss.ledger.eps_max))
    assert int(sr.ledger.spends) == int(ss.ledger.spends)
    assert int(sr.round) == int(ss.round)
    assert bool(jnp.array_equal(sr.key, ss.key))


# --------------------------------------------------- backend bit parity

@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_resident_streamed_bit_parity(problem, case):
    """The streamed bank (host-side state + prefetched cohort slices) is
    bit-identical to the resident scan at small n under the same key —
    params, EF residuals, server_topk prev_delta, ledger totals, lanes,
    counts, and every stacked metric."""
    (tr, sr), (ts, ss) = _both_backends(PARITY_CASES[case], problem)
    x, y = problem[3][0], problem[3][1]
    sr, mr = tr.run(sr, x, y, rounds=3)
    ss, ms = ts.run(ss, np.asarray(x), np.asarray(y), rounds=3)
    _assert_states_equal(sr, ss)
    assert set(mr) == set(ms)
    for k in mr:
        assert bool(jnp.array_equal(mr[k], jnp.asarray(ms[k]))), k


def test_streamed_step_matches_resident_step(problem):
    """step consumes state.key whole under both backends (the resident
    schedule, not split(key, 1))."""
    (tr, sr), (ts, ss) = _both_backends(
        dict(error_feedback=True), problem)
    x, y = problem[3][0], problem[3][1]
    sr1, mr = tr.step(sr, x, y)
    ss1, ms = ts.step(ss, np.asarray(x), np.asarray(y))
    _assert_states_equal(sr1, ss1)
    for k in mr:
        assert bool(jnp.array_equal(mr[k], jnp.asarray(ms[k]))), k


def test_chunked_resume_carries_bank(problem):
    """run(T1) then run(T2) with the bank carried in TrainState: both
    backends stay bit-identical through the chunk boundary, participation
    counts accumulate, and the resumed PRNG stream advances."""
    (tr, sr), (ts, ss) = _both_backends(
        dict(error_feedback=True, randk_mode="server_topk"), problem)
    x, y = problem[3][0], problem[3][1]
    xs, ys = np.asarray(x), np.asarray(y)
    sr1, _ = tr.run(sr, x, y, rounds=2)
    sr2, _ = tr.run(sr1, x, y, rounds=3)
    ss1, _ = ts.run(ss, xs, ys, rounds=2)
    ss2, _ = ts.run(ss1, xs, ys, rounds=3)
    _assert_states_equal(sr2, ss2)
    assert int(sr2.round) == 5
    assert int(np.asarray(sr2.bank.counts).sum()) \
        == 5 * BASE["clients_per_round"]
    # the streamed run must not mutate the caller's states in place
    assert int(np.asarray(ss.bank.counts).sum()) == 0
    assert int(np.asarray(ss1.bank.counts).sum()) \
        == 2 * BASE["clients_per_round"]


# --------------------------------------------------- key-lane contract

def test_key_lane_contract(problem):
    """Pins which of the 7 round-key lanes feeds which draw (DESIGN.md
    §5): the whole round is recomputed from the documented lanes with the
    same public primitives and must reproduce the Trainer's outputs —
    selection (0), client train keys (1), gains (2), support (3), channel
    noise (4), bank lanes (5), CSI estimation (6). A silent lane shift
    changes every recomputed quantity."""
    params, d, unravel, (x, y, _, _), loss_fn = problem
    chan = ChannelConfig(csi_error=0.3)
    cfg = PFELSConfig(**BASE, channel=chan)
    trainer, state = _trainer(cfg, problem)
    new_state, metrics = trainer.step(state, x, y)

    n, r = cfg.num_clients, cfg.clients_per_round
    k = max(int(round(cfg.compression_ratio * d)), 1)
    ks = rounds.split_round_key(state.key)

    # lane 0: selection; observable through the participation counts
    sel = rounds.sample_cohort(ks[0], n, r)
    counts = np.asarray(new_state.bank.counts)
    assert counts.sum() == r
    assert np.array_equal(np.sort(np.asarray(sel)),
                          np.flatnonzero(counts == 1))

    # lane 5: per-client bank lanes fold the client id into ks[5]
    lanes = np.asarray(new_state.bank.lanes)
    expect_lanes = np.asarray(cohort_lane_keys(ks[5], sel))
    assert np.array_equal(lanes[np.asarray(sel)], expect_lanes)

    # lanes 1-4 and 6: recompute the full round from the pinned lanes
    train = functools.partial(
        local_train, loss_fn=loss_fn, steps=cfg.local_steps,
        lr=cfg.local_lr, clip=cfg.clip, momentum=cfg.momentum)
    cx, cy = x[sel], y[sel]
    ck = jax.random.split(ks[1], r)                       # lane 1
    new_p, losses = jax.vmap(
        lambda cx_, cy_, k_: train(params, cx_, cy_, k_))(cx, cy, ck)
    updates = jax.vmap(lambda p_: model_update(params, p_))(new_p)
    flat_updates = jax.vmap(lambda u: ravel_pytree(u)[0])(updates)

    gains = channel.sample_gains(ks[2], r, chan)          # lane 2
    gains_est = channel.estimate_gains(ks[6], gains, chan)  # lane 6
    idx = randk.sample_indices(ks[3], d, k)               # lane 3
    p_sel = state.power_limits[sel]
    beta = power_control.beta_pfels(
        gains_est, p_sel, d=d, k=k, c1=cfg.clip, eta=cfg.local_lr,
        tau=cfg.local_steps, epsilon=cfg.epsilon, r=r, n=n,
        delta=cfg.resolved_delta(), sigma0=chan.noise_std)
    delta_hat, energy, _ = aggregation.aircomp_aggregate(
        flat_updates, idx, gains, beta, ks[4], d=d,       # lane 4
        sigma0=chan.noise_std, r=r, gains_est=gains_est)

    np.testing.assert_allclose(float(metrics["train_loss"]),
                               float(jnp.mean(losses)), rtol=1e-6)
    np.testing.assert_allclose(float(metrics["beta"]), float(beta),
                               rtol=1e-6)
    np.testing.assert_allclose(float(metrics["energy"]), float(energy),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new_state.prev_delta),
                               np.asarray(delta_hat), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(_flat(new_state.params)),
        np.asarray(_flat(params) + delta_hat), rtol=1e-5, atol=1e-7)


# ------------------------------------------------- streamed data pipeline

def test_cohort_source_matches_array_gather(problem):
    """ArraySource.cohort(sel) is exactly the resident data_x[sel]."""
    x, y = problem[3][0], problem[3][1]
    src = ArraySource(x, y)
    sel = np.array([3, 0, 17, 5])
    cx, cy = src.cohort(sel)
    assert np.array_equal(cx, np.asarray(x)[sel])
    assert np.array_equal(cy, np.asarray(y)[sel])


def test_streamed_run_accepts_source_and_arrays(problem):
    """Passing (x, y) arrays and passing an ArraySource are the same
    streamed run."""
    (_, _), (ts, ss) = _both_backends({}, problem)
    x, y = problem[3][0], problem[3][1]
    s_a, m_a = ts.run(ss, np.asarray(x), np.asarray(y), rounds=2)
    s_b, m_b = ts.run(ss, ArraySource(x, y), rounds=2)
    _assert_states_equal(s_a, s_b)
    for k in m_a:
        assert np.array_equal(m_a[k], m_b[k]), k


def test_population_source_deterministic_o_r():
    """make_population_source: same client -> same samples whenever it is
    sampled; only (r, ...) batches are materialized."""
    src, xt, yt = make_population_source(
        jax.random.PRNGKey(3), n_clients=50_000, per_client=6,
        num_classes=10, image_shape=(1, 8, 8))
    assert src.n == 50_000
    a = src.cohort(np.array([7, 49_999, 123]))
    b = src.cohort(np.array([123, 7]))
    assert a[0].shape == (3, 6, 1, 8, 8) and a[1].shape == (3, 6)
    np.testing.assert_array_equal(np.asarray(a[0][0]), np.asarray(b[0][1]))
    np.testing.assert_array_equal(np.asarray(a[0][2]), np.asarray(b[0][0]))
    np.testing.assert_array_equal(np.asarray(a[1][0]), np.asarray(b[1][1]))
    assert not np.array_equal(np.asarray(a[0][0]), np.asarray(a[0][1]))
    assert xt.shape[0] == yt.shape[0] >= 200


def test_prefetch_orders_and_propagates_errors():
    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    y = np.arange(10, dtype=np.int32)
    src = ArraySource(x, y)
    sels = [np.array([1, 2]), np.array([9, 0]), np.array([5, 5])]
    got = list(prefetch_cohorts(src, sels))
    assert len(got) == 3
    for sel, (cx, cy) in zip(sels, got):
        assert np.array_equal(np.asarray(cx), x[sel])
        assert np.array_equal(np.asarray(cy), y[sel])

    def boom(sel):
        raise RuntimeError("generator failed")

    bad = ClientFnSource(boom, 10)
    with pytest.raises(RuntimeError, match="generator failed"):
        list(prefetch_cohorts(bad, sels))

    # abandoning the generator mid-stream must release the worker thread
    # (it would otherwise block forever on the bounded queue)
    import threading
    gen = prefetch_cohorts(src, [np.array([0, 1])] * 50, depth=1)
    next(gen)
    gen.close()
    deadline = 50
    while deadline and any(t.name == "cohort-prefetch" and t.is_alive()
                           for t in threading.enumerate()):
        import time
        time.sleep(0.1)
        deadline -= 1
    assert not any(t.name == "cohort-prefetch" and t.is_alive()
                   for t in threading.enumerate())


def test_streamed_rejects_mismatched_source_and_zero_rounds(problem):
    (_, _), (ts, ss) = _both_backends({}, problem)
    src, _, _ = make_population_source(
        jax.random.PRNGKey(0), n_clients=99, per_client=4,
        num_classes=10, image_shape=(1, 8, 8))
    with pytest.raises(ValueError, match="cfg.num_clients"):
        ts.run(ss, src, rounds=2)
    x, y = problem[3][0], problem[3][1]
    with pytest.raises(ValueError, match="rounds >= 1"):
        ts.run(ss, np.asarray(x), np.asarray(y), rounds=0)


def test_streamed_trains_on_population_source(problem):
    """End-to-end: streamed bank + on-demand population source at an n
    where a resident (n, samples, ...) tensor would be pointless."""
    params, d, _, _, loss_fn = problem
    cfg = PFELSConfig(**{**BASE, "num_clients": 5_000},
                      error_feedback=True, bank_backend="streamed")
    src, xt, yt = make_population_source(
        jax.random.PRNGKey(5), n_clients=5_000, per_client=8,
        num_classes=10, image_shape=(1, 8, 8))
    trainer = Trainer(cfg, loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    state, m = trainer.run(state, src, rounds=2)
    assert np.isfinite(np.asarray(m["train_loss"])).all()
    assert state.bank.residuals.shape == (5_000, d)
    assert isinstance(state.bank.residuals, np.ndarray)  # host-side
    assert int(np.asarray(state.bank.counts).sum()) \
        == 2 * cfg.clients_per_round


# ------------------------------------------------------- checkpointing

@pytest.mark.parametrize("backend", ["resident", "streamed"])
def test_checkpoint_roundtrip_with_bank(problem, backend):
    """save_train_state/restore_train_state carry the bank; resuming from
    the checkpoint equals resuming from the live state, bitwise."""
    cfg = PFELSConfig(**BASE, error_feedback=True, bank_backend=backend)
    trainer, state = _trainer(cfg, problem)
    x, y = problem[3][0], problem[3][1]
    s1, _ = trainer.run(state, x, y, rounds=2)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ck")
        checkpoint.save_train_state(path, s1, backend=backend)
        meta = checkpoint.load_meta(path)
        assert meta["bank_backend"] == backend
        assert meta["round"] == 2
        restored = checkpoint.restore_train_state(
            path, trainer.init(jax.random.PRNGKey(1)))
    if backend == "streamed":
        assert isinstance(restored.bank.residuals, np.ndarray)
    a, _ = trainer.run(s1, x, y, rounds=2)
    b, _ = trainer.run(restored, x, y, rounds=2)
    _assert_states_equal(a, b)


# ------------------------------------------------------------ validation

def test_bank_validation(problem):
    with pytest.raises(ValueError, match="unknown bank backend"):
        make_bank("ram", 10, 4, False)
    cfg = PFELSConfig(**BASE, bank_backend="streamed",
                      client_sharding="cohort")
    with pytest.raises(ValueError, match="streamed"):
        Trainer(cfg, problem[4], problem[0])
