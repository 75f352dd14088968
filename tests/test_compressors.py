"""ISSUE 7 property sweep: every entry in the compressor registry
(DESIGN.md §13), across the execution-path matrix.

Three property families, each swept over ``list_compressors()``:

  1. fused == unfused fp32 parity through a full Trainer round, on both
     bank backends (vmapped resident / streamed host loop; the sharded
     cohort path is covered when >= 2 devices are visible) × error
     feedback on/off — the compressor threading (Support.active column,
     encode hook, EF residual via ``compressors.sparsify``) must not
     open a gap between the Pallas kernel path and the reference.
  2. the Theorem-5 per-device energy cap: with the compressor's
     sensitivity factor threaded through β design as C1·s, the expected
     per-device energy (β/g_i^obs)² (k_used/d) (η τ C1 s)² stays <= P_i
     for every registered compressor (Eq. 34c is an expectation
     constraint — the paper's E||x_i||² <= P_i).
  3. the in-graph ledger's ε spend matches a host ``PrivacyLedger``
     recomputation from the realized betas through
     ``round_epsilon_spent`` — which consumes the same sensitivity hook,
     so the power and privacy accounting agree on one C1·s.

Plus registry-contract units (error messages, carry-forced error
feedback, schedule algebra).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs import CompressionSchedule, PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.core import channel, compressors, privacy
from repro.core.compressors import schedules
from repro.data import make_federated_classification
from repro.fl import Trainer, round_epsilon_spent
from repro.fl.api import replace
from repro.models import cnn

MULTI = len(jax.devices()) >= 2
ALL_COMPRESSORS = compressors.list_compressors()
BACKENDS = ["resident", "streamed"]

BASE = dict(num_clients=12, clients_per_round=4, local_steps=2,
            local_lr=0.05, compression_ratio=0.3, epsilon=2.0, rounds=3)


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=12, per_client=16, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, (x, y), loss_fn


def _cfg(**kw):
    merged = dict(BASE)
    merged.update(kw)
    return PFELSConfig(**merged)


def _state(trainer):
    return replace(trainer.init(jax.random.PRNGKey(1)),
                   key=jax.random.PRNGKey(2))


def _flat(p):
    return np.asarray(ravel_pytree(p)[0])


# ------------------------------------------------ 1. fused/unfused parity

@pytest.mark.parametrize("ef", [False, True], ids=["ef0", "ef1"])
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("comp", ALL_COMPRESSORS)
def test_fused_matches_unfused_through_round(problem, comp, backend, ef):
    """One Trainer.step, fused Pallas kernel vs unfused reference, same
    key: delta_hat, params, energy, β, ε spend, and (with EF) the bank
    residuals agree to fp32 accumulation order — for every compressor on
    both bank backends."""
    params, (x, y), loss_fn = problem
    if backend == "streamed":
        x, y = np.asarray(x), np.asarray(y)
    outs = []
    for fused in (False, True):
        cfg = _cfg(compressor=comp, bank_backend=backend,
                   error_feedback=ef, transmit_clip=0.5 if ef else None,
                   use_fused_kernel=fused)
        tr = Trainer(cfg, loss_fn, params)
        st, m = tr.step(_state(tr), x, y)
        outs.append((st, m))
    (st0, m0), (st1, m1) = outs
    np.testing.assert_allclose(np.asarray(st1.prev_delta),
                               np.asarray(st0.prev_delta),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(_flat(st1.params), _flat(st0.params),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m1["energy"]), float(m0["energy"]),
                               rtol=1e-5)
    assert float(m1["beta"]) == float(m0["beta"])   # same gains, same min
    np.testing.assert_allclose(float(m1["eps_round"]),
                               float(m0["eps_round"]), rtol=1e-6)
    assert float(m1["subcarriers"]) == float(m0["subcarriers"])
    res0, res1 = st0.bank.residuals, st1.bank.residuals
    if res0 is not None:
        np.testing.assert_allclose(np.asarray(res1), np.asarray(res0),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices")
@pytest.mark.parametrize("comp", ALL_COMPRESSORS)
def test_sharded_cohort_matches_vmapped(problem, comp):
    """The shard_map cohort path reproduces the vmapped round for every
    compressor (the psum superposition + replicated Support columns)."""
    params, (x, y), loss_fn = problem
    outs = []
    for sharding in ("none", "cohort"):
        cfg = _cfg(compressor=comp, error_feedback=True,
                   transmit_clip=0.5, client_sharding=sharding)
        tr = Trainer(cfg, loss_fn, params)
        st, m = tr.step(_state(tr), x, y)
        outs.append((st, m))
    (st0, m0), (st1, m1) = outs
    np.testing.assert_allclose(np.asarray(st1.prev_delta),
                               np.asarray(st0.prev_delta),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m1["energy"]), float(m0["energy"]),
                               rtol=1e-5)


# --------------------------------------------- 2. per-device energy cap

@pytest.mark.parametrize("comp", ALL_COMPRESSORS)
def test_property_energy_cap_per_compressor(comp):
    """Eq. 34c with the sensitivity factor: the β the registry designs
    keeps every device's EXPECTED energy
    (β/g_i)² (k_used/d) (η τ C1 s)² <= P_i — for stoch_quant the
    transmitted norm really inflates by s, so dropping the factor from
    the power cap would violate P_i by s²."""
    from repro.fl import algorithms
    d, k, r = 4000, 1200, 8
    cfg = _cfg(compressor=comp, quant_bits=4, compression_ratio=k / d)
    alg = algorithms.get_algorithm("pfels")
    s = compressors.sensitivity_factor(cfg, d)
    ete = cfg.local_lr * cfg.local_steps * cfg.clip * s
    for seed in range(5):
        kg, kp = jax.random.split(jax.random.PRNGKey(seed))
        gains = jnp.abs(0.5 + 0.5 * jax.random.normal(kg, (r,))) + 0.05
        p = channel.sample_power_limits(kp, r, d, cfg.channel)
        beta = alg.design_beta(cfg, gains, p, d, k, c1_scale=s)
        energy = (np.asarray(beta) / np.asarray(gains)) ** 2 \
            * (k / d) * ete ** 2
        assert np.all(energy <= np.asarray(p) * (1 + 1e-5)), comp
    if comp == "stoch_quant":
        # the factor is load-bearing: with the privacy cap out of the way
        # (huge epsilon => power-bound design), dropping c1_scale makes
        # the expected energy of the binding device overshoot its P_i by
        # the s^2 the quantizer really inflates the transmitted norm by
        cfg_hi = _cfg(compressor=comp, quant_bits=4,
                      compression_ratio=k / d, epsilon=1e6)
        beta_raw = alg.design_beta(cfg_hi, gains, p, d, k, c1_scale=1.0)
        energy = (float(beta_raw) / np.asarray(gains)) ** 2 \
            * (k / d) * ete ** 2
        assert np.any(energy > np.asarray(p)), \
            "s=1 design should overshoot P_i for stoch_quant"


# --------------------------------------------- 3. ledger host recompute

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("comp", ALL_COMPRESSORS)
def test_ledger_matches_host_recomputation(problem, comp, backend):
    """state.ledger after run(3) == a host PrivacyLedger fed
    min(round_epsilon_spent(cfg, β_t, d), ε) — round_epsilon_spent
    applies the compressor's sensitivity hook, so this pins the in-graph
    C2' = C2(C1·s) against an independent float64 recomputation."""
    params, (x, y), loss_fn = problem
    if backend == "streamed":
        x, y = np.asarray(x), np.asarray(y)
    cfg = _cfg(compressor=comp, bank_backend=backend, transmit_clip=0.5)
    tr = Trainer(cfg, loss_fn, params)
    st, m = tr.run(_state(tr), x, y, rounds=3)
    host = privacy.PrivacyLedger(cfg.num_clients, cfg.resolved_delta())
    for b in np.asarray(m["beta"]):
        host.spend(min(round_epsilon_spent(cfg, float(b), tr.d),
                       cfg.epsilon))
    eps_host, delta_host = host.total_basic()
    np.testing.assert_allclose(float(st.ledger.eps_sum), eps_host,
                               rtol=1e-5)
    assert int(st.ledger.spends) == 3
    np.testing.assert_allclose(np.asarray(m["eps_round"]),
                               np.asarray(host.eps_rounds), rtol=1e-5)
    if comp == "stoch_quant":
        # the dimension-dependent factor really reached the ledger: the
        # charged eps is s x the rand_k-coefficient recomputation at the
        # same realized beta (capped at cfg.epsilon), with s > 1
        s = compressors.sensitivity_factor(cfg, tr.d)
        assert s > 1.0
        base_cfg = PFELSConfig(**BASE, transmit_clip=0.5)
        for b, er in zip(np.asarray(m["beta"]),
                         np.asarray(m["eps_round"])):
            base = round_epsilon_spent(base_cfg, float(b))
            np.testing.assert_allclose(
                er, min(base * s, cfg.epsilon), rtol=1e-5)


# ----------------------------------------------- registry + schedule units

def test_registry_contract():
    with pytest.raises(KeyError, match="unknown compressor 'nope'"):
        compressors.get_compressor("nope")
    with pytest.raises(ValueError, match="already registered"):
        compressors.register_compressor(
            "rand_k", compressors.get_compressor("rand_k"))
    tmp = compressors.Compressor(
        name="tmp", select_support=lambda cfg, d, k, prev, key:
        compressors.Support(jnp.arange(k)))
    compressors.register_compressor("tmp", tmp)
    try:
        assert "tmp" in compressors.list_compressors()
    finally:
        compressors.unregister_compressor("tmp")
    assert "tmp" not in compressors.list_compressors()


def test_support_helpers():
    d, k = 10, 4
    sup = compressors.Support(jnp.array([1, 3, 5, 7]))
    assert compressors.support_size(sup) == k
    act = jnp.array([1.0, 0.0, 1.0, 0.0])
    sup2 = compressors.and_active(sup, act)
    assert float(compressors.support_size(sup2)) == 2.0
    u = jnp.arange(10, dtype=jnp.float32)
    sp = compressors.sparsify(u, sup2, d)
    np.testing.assert_allclose(
        np.asarray(sp), [0, 1, 0, 0, 0, 5, 0, 0, 0, 0])
    mask = compressors.dense_mask(sup2, d)
    assert float(mask.sum()) == 2.0 and float(mask[1]) == 1.0


def test_carry_compressor_forces_bank_residuals(problem):
    """top_k_ef turns the bank's EF memory on even with
    cfg.error_feedback=False — and actually populates it."""
    params, (x, y), loss_fn = problem
    cfg = _cfg(compressor="top_k_ef", error_feedback=False)
    tr = Trainer(cfg, loss_fn, params)
    st = _state(tr)
    assert st.bank.residuals is not None
    st, _ = tr.step(st, x, y)
    assert float(jnp.abs(st.bank.residuals).sum()) > 0.0


def test_schedule_algebra():
    cfg = _cfg(rounds=5,
               schedule=CompressionSchedule(mode="linear", k_end_ratio=0.5,
                                            power_end=0.6))
    sched = cfg.schedule
    ka0 = schedules.k_active(sched, cfg, 100, 0)
    ka4 = schedules.k_active(sched, cfg, 100, 4)
    assert float(ka0.sum()) == 100.0 and float(ka4.sum()) == 50.0
    np.testing.assert_allclose(float(schedules.power_scale(sched, cfg, 4)),
                               0.6, rtol=1e-6)
    assert schedules.epsilon_round(sched, cfg, 0, 0.0) is None  # not budget
    b = _cfg(rounds=4, epsilon=2.0,
             schedule=CompressionSchedule(mode="budget", eps_floor=0.1))
    # untouched budget paces to eps_total/rounds; the ceiling never
    # exceeds cfg.epsilon and never drops below the floor
    assert float(schedules.epsilon_round(b.schedule, b, 0, 0.0)) == 2.0
    np.testing.assert_allclose(
        float(schedules.epsilon_round(b.schedule, b, 2, 7.0)),
        0.5, rtol=1e-6)
    np.testing.assert_allclose(
        float(schedules.epsilon_round(b.schedule, b, 3, 8.0)),
        0.1, rtol=1e-6)   # floor


def test_budget_schedule_paces_total(problem):
    """mode='budget': the ledger never exceeds ε·rounds, and the
    per-round spend never exceeds the per-round ε (Thm 3 cap intact)."""
    params, (x, y), loss_fn = problem
    cfg = _cfg(rounds=4,
               schedule=CompressionSchedule(mode="budget", eps_floor=0.05))
    tr = Trainer(cfg, loss_fn, params)
    st, m = tr.run(_state(tr), x, y, rounds=4)
    assert np.all(np.asarray(m["eps_round"]) <= cfg.epsilon + 1e-6)
    assert float(st.ledger.eps_sum) <= cfg.epsilon * cfg.rounds + 1e-5


def test_k_anneal_reaches_design_and_receiver(problem):
    """mode='linear' with k_end_ratio<1: the live-slot column shrinks the
    subcarriers metric, relaxes β (sqrt(k) in Eq. 34c), and zeroes the
    reconstruction off the live support."""
    params, (x, y), loss_fn = problem
    cfg = _cfg(rounds=3,
               schedule=CompressionSchedule(mode="linear", k_end_ratio=0.4))
    tr = Trainer(cfg, loss_fn, params)
    st, m = tr.run(_state(tr), x, y, rounds=3)
    sub = np.asarray(m["subcarriers"])
    assert sub[0] > sub[1] > sub[2]
    k_budget = max(int(round(cfg.compression_ratio * tr.d)), 1)
    assert sub[-1] == pytest.approx(0.4 * k_budget, rel=0.01)
    # fewer live subcarriers => weakly larger β under the same gains is
    # not directly comparable across rounds (gains differ); instead the
    # reconstruction must be k_used-sparse
    assert int(np.count_nonzero(np.asarray(st.prev_delta))) <= int(sub[-1])


def test_threshold_compressor_prunes_support(problem):
    """threshold: warm rounds deactivate below-threshold budget slots —
    subcarriers < k budget, delta_hat sparse to the live count."""
    params, (x, y), loss_fn = problem
    cfg = _cfg(compressor="threshold", threshold_frac=0.5)
    tr = Trainer(cfg, loss_fn, params)
    st, m = tr.run(_state(tr), x, y, rounds=3)
    k_budget = max(int(round(cfg.compression_ratio * tr.d)), 1)
    sub = np.asarray(m["subcarriers"])
    assert sub[0] == k_budget          # cold start: all slots live
    assert np.all(sub[1:] < k_budget)  # warm: pruned
    assert np.all(sub >= 1)
    assert int(np.count_nonzero(np.asarray(st.prev_delta))) <= int(sub[-1])


def test_stoch_quant_validation():
    with pytest.raises(ValueError, match="quant_bits"):
        compressors.get_compressor("stoch_quant").sensitivity(
            _cfg(quant_bits=1), 100)
    with pytest.raises(ValueError, match="dimension-dependent"):
        compressors.sensitivity_factor(_cfg(compressor="stoch_quant"),
                                       None)
    # rand_k stays dimension-independent (host callers pass d=None)
    assert compressors.sensitivity_factor(_cfg(), None) == 1.0


def test_stoch_quant_encode_unbiased_and_bounded():
    cfg = _cfg(compressor="stoch_quant", quant_bits=4)
    enc = compressors.get_compressor("stoch_quant").encode
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 256))
    keys = jax.random.split(jax.random.PRNGKey(7), 4096)
    qs = jax.vmap(lambda k: enc(cfg, u, k[None]))(keys)[:, 0, :]
    # unbiased: the mean over rounding draws approaches u (per-draw
    # rounding sd is ||u||/levels/2 ~ 1.1, so se of the mean ~ 0.018)
    np.testing.assert_allclose(np.asarray(qs.mean(0)), np.asarray(u[0]),
                               atol=0.12)
    # deterministic worst-case norm inflation <= the sensitivity factor
    s = compressors.sensitivity_factor(cfg, 256)
    norms = np.linalg.norm(np.asarray(qs), axis=1)
    assert np.all(norms <= s * float(jnp.linalg.norm(u)) * (1 + 1e-5))
