"""Fused PFELS transmit pipeline: Pallas kernel == ref.py == the unfused
aircomp_aggregate path (same PRNG key => bit-identical noise draw), across
odd d, k=1, k=d, r=1 edge cases; plus the round-level wiring behind
cfg.use_fused_kernel and the lax.scan multi-round driver."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.core import aggregation, randk
from repro.data import make_federated_classification
from repro.fl import Trainer
from repro.fl.api import replace
from repro.kernels.pfels_transmit import ref as tref
from repro.kernels.pfels_transmit.ops import fused_transmit
from repro.models import cnn

CASES = [
    (3, 40, 10),     # generic
    (1, 37, 1),      # r=1, odd d, k=1
    (4, 37, 37),     # k=d, odd d
    (2, 128, 64),    # lane-aligned d
    (5, 301, 17),    # odd everything
]


def _problem(r, d, k, seed=0):
    key = jax.random.PRNGKey(seed)
    updates = jax.random.normal(key, (r, d))
    gains = (jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (r,)))
             * 0.05 + 0.01)
    idx = randk.sample_indices(jax.random.fold_in(key, 2), d, k)
    noise_key = jax.random.fold_in(key, 3)
    return updates, gains, idx, noise_key


@pytest.mark.parametrize("r,d,k", CASES)
@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas", "jax_ref"])
def test_fused_matches_unfused(r, d, k, use_kernel):
    updates, gains, idx, nk = _problem(r, d, k)
    beta, sigma0 = 0.7, 0.3
    dh0, e0, y0 = aggregation.aircomp_aggregate(
        updates, idx, gains, beta, nk, d=d, sigma0=sigma0, r=r)
    dh1, e1, y1 = fused_transmit(
        updates, idx, gains, beta, nk, d=d, sigma0=sigma0, r=r,
        use_kernel=use_kernel)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


def test_noise_draw_bit_identical():
    """Same PRNG key => the fused path consumes the exact same channel-noise
    realization as the unfused path: with the superposition zeroed out
    (zero updates) the received payloads agree bit-for-bit."""
    r, d, k = 3, 64, 16
    _, gains, idx, nk = _problem(r, d, k)
    zeros = jnp.zeros((r, d))
    _, _, y0 = aggregation.aircomp_aggregate(
        zeros, idx, gains, 1.0, nk, d=d, sigma0=0.9, r=r)
    for use_kernel in (True, False):
        _, _, y1 = fused_transmit(zeros, idx, gains, 1.0, nk, d=d,
                                  sigma0=0.9, r=r, use_kernel=use_kernel)
        assert bool(jnp.all(y0 == y1))


def _two_scatter_columns(idx, noise_key, sigma0, d, active=None):
    """The dense columns as two scatters into zero columns: the oracle
    for the one-scatter ``dense_noise_and_mask``."""
    noise = sigma0 * jax.random.normal(noise_key, (idx.shape[0],))
    ones = jnp.ones(idx.shape, jnp.float32)
    if active is not None:
        noise, ones = noise * active, active
    mask = jnp.zeros((d,), jnp.float32).at[idx].set(ones)
    z_dense = jnp.zeros((d,), jnp.float32).at[idx].set(noise)
    return mask, z_dense


@pytest.mark.parametrize("sigma0", [0.7, 0.0])     # 0.0: signed zeros
@pytest.mark.parametrize("with_active", [False, True],
                         ids=["active_none", "active_col"])
@pytest.mark.parametrize("d,k", [(301, 90), (301, 1), (301, 301),
                                 (1024, 307)])
def test_dense_columns_match_two_scatters(d, k, with_active, sigma0):
    key = jax.random.PRNGKey(11)
    idx = randk.sample_indices(jax.random.fold_in(key, 1), d, k)
    nk = jax.random.fold_in(key, 2)
    active = (jax.random.bernoulli(jax.random.fold_in(key, 3), 0.5, (k,))
              .astype(jnp.float32) if with_active else None)
    # both compiled, as in the round (eager and compiled noise draws may
    # differ in the last bit)
    got, want = (jax.jit(f, static_argnums=(2, 3))(idx, nk, sigma0, d,
                                                    active)
                 for f in (tref.dense_noise_and_mask, _two_scatter_columns))
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype == np.float32
        assert np.array_equal(g, w)
        assert np.array_equal(np.signbit(g), np.signbit(w))
    if sigma0 == 0.0 and k > 1:     # negative zeros on omega are tested
        assert np.signbit(np.asarray(want[1])).any()


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas", "jax_ref"])
def test_fused_clip_matches_preclipped_unfused(use_kernel):
    """transmit_clip == pre-clipping the updates then running unfused."""
    r, d, k = 4, 50, 20
    updates, gains, idx, nk = _problem(r, d, k, seed=7)
    updates = 3.0 * updates
    clip, beta, sigma0 = 1.5, 0.9, 0.2
    norms = jnp.linalg.norm(updates, axis=1, keepdims=True)
    pre = updates * jnp.minimum(1.0, clip / norms)
    dh0, e0, y0 = aggregation.aircomp_aggregate(
        pre, idx, gains, beta, nk, d=d, sigma0=sigma0, r=r)
    dh1, e1, y1 = fused_transmit(
        updates, idx, gains, beta, nk, d=d, sigma0=sigma0, r=r, clip=clip,
        use_kernel=use_kernel)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


def test_unfused_clip_arg_matches_manual():
    """The new clip= arg on aircomp_aggregate == manual pre-clip."""
    r, d, k = 3, 30, 9
    updates, gains, idx, nk = _problem(r, d, k, seed=9)
    updates = 5.0 * updates
    norms = jnp.linalg.norm(updates, axis=1, keepdims=True)
    pre = updates * jnp.minimum(1.0, 2.0 / norms)
    a = aggregation.aircomp_aggregate(pre, idx, gains, 1.1, nk, d=d,
                                      sigma0=0.1, r=r)
    b = aggregation.aircomp_aggregate(updates, idx, gains, 1.1, nk, d=d,
                                      sigma0=0.1, r=r, clip=2.0)
    for x, y in zip(a, b):
        np.testing.assert_allclose(y, x, rtol=1e-6)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas", "jax_ref"])
def test_fused_imperfect_csi_and_rescale(use_kernel):
    """gains_est precompensation and unbiased_rescale flow through fused."""
    r, d, k = 4, 45, 15
    updates, gains, idx, nk = _problem(r, d, k, seed=3)
    gains_est = gains * 1.07
    kw = dict(d=d, sigma0=0.25, r=r, gains_est=gains_est,
              unbiased_rescale=True)
    dh0, e0, y0 = aggregation.aircomp_aggregate(
        updates, idx, gains, 0.8, nk, **kw)
    dh1, e1, y1 = fused_transmit(updates, idx, gains, 0.8, nk,
                                 use_kernel=use_kernel, **kw)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


def test_client_sumsq_kernel_matches_ref():
    """Pass-1 Pallas reduction == the ref.py sumsq oracle (zero column
    padding is norm-neutral)."""
    from repro.kernels.pfels_transmit.kernel import client_sumsq
    r, d = 5, 300
    u = jax.random.normal(jax.random.PRNGKey(13), (r, d))
    u_pad = jnp.pad(u, ((0, 0), (0, 512 - d)))
    out = client_sumsq(u_pad, block=128, interpret=True)
    np.testing.assert_allclose(out[:, 0], tref.client_sumsq_ref(u),
                               rtol=1e-6)


def test_kernel_matches_ref_module():
    """Pallas kernel == the ref.py oracle on the dense formulation."""
    r, d = 3, 200
    key = jax.random.PRNGKey(11)
    u = jax.random.normal(key, (r, d))
    idx = randk.sample_indices(key, d, 60)
    mask = jnp.zeros((d,)).at[idx].set(1.0)
    z = jnp.zeros((d,)).at[idx].set(0.1)
    scales = tref.clip_scales(u, 1.0)
    tx, rx = tref.transmit_coeffs(jnp.full((r,), 0.05), 0.9, scales)
    y_ref, e_ref = tref.pfels_transmit_ref(u, mask, z, rx, tx ** 2)
    dh_k, e_k, _ = fused_transmit(u, idx, jnp.full((r,), 0.05), 0.9,
                                  jax.random.PRNGKey(0), d=d, sigma0=0.0,
                                  r=r, clip=1.0, use_kernel=True)
    # sigma0=0 => z contribution differs; compare the noiseless parts
    dh_ref = (y_ref - z) / (r * 0.9)
    np.testing.assert_allclose(dh_k, dh_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e_k, e_ref, rtol=1e-5)


# ------------------------------------- fused-default scenario sweep (PR 6)
# use_fused_kernel=True is the DEFAULT execution mode for every registered
# channel model on both execution paths; these properties pin fused ==
# unfused-oracle fp32 parity for the in-tile transmit mask (dropout), the
# in-tile MRC combine (gains_ant matrix), and their interaction with
# clip / imperfect CSI — plus the all-dropped-round realized-r floor.

def _scenario_problem(r, d, k, *, M=None, dropped=0, seed=5):
    key = jax.random.PRNGKey(seed)
    updates = jax.random.normal(key, (r, d))
    if M is not None:
        gains_ant = (jnp.abs(jax.random.normal(
            jax.random.fold_in(key, 1), (r, M))) * 0.05 + 0.01)
        gains = jnp.sum(gains_ant, axis=1)       # the effective MRC view
    else:
        gains_ant = None
        gains = (jnp.abs(jax.random.normal(jax.random.fold_in(key, 1),
                                           (r,))) * 0.05 + 0.01)
    tx_mask = None
    if dropped:
        tx_mask = jnp.ones((r,)).at[
            jnp.arange(dropped)].set(0.0).astype(jnp.float32)
    idx = randk.sample_indices(jax.random.fold_in(key, 2), d, k)
    nk = jax.random.fold_in(key, 3)
    return updates, gains, gains_ant, tx_mask, idx, nk


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas", "jax_ref"])
def test_fused_tx_mask_matches_unfused(use_kernel):
    """In-tile masking (per-client coefficient fold) == the oracle's
    (r, d) pre-mask + realized-r unscale."""
    r, d, k = 5, 80, 24
    updates, gains, _, tx_mask, idx, nk = _scenario_problem(
        r, d, k, dropped=2)
    kw = dict(d=d, sigma0=0.3, r=r, tx_mask=tx_mask)
    dh0, e0, y0 = aggregation.aircomp_aggregate(
        updates, idx, gains, 0.8, nk, **kw)
    dh1, e1, y1 = aggregation.aircomp_aggregate_fused(
        updates, idx, gains, 0.8, nk, use_kernel=use_kernel, **kw)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas", "jax_ref"])
def test_fused_mrc_gains_matrix_matches_effective(use_kernel):
    """The kernel's in-tile all-ones-beam combine over a (r, M) gains_ant
    matrix == the oracle on the pre-combined effective gains, and == the
    fused path fed the effective (r,) view directly."""
    r, d, k, M = 4, 70, 21, 4
    updates, gains, gains_ant, _, idx, nk = _scenario_problem(
        r, d, k, M=M)
    kw = dict(d=d, sigma0=0.25, r=r)
    dh0, e0, y0 = aggregation.aircomp_aggregate(
        updates, idx, gains, 0.9, nk, **kw)
    dh1, e1, y1 = aggregation.aircomp_aggregate_fused(
        updates, idx, gains, 0.9, nk, gains_ant=gains_ant,
        use_kernel=use_kernel, **kw)
    dh2, e2, _ = aggregation.aircomp_aggregate_fused(
        updates, idx, gains, 0.9, nk, use_kernel=use_kernel, **kw)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dh1, dh2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e2, rtol=1e-5)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas", "jax_ref"])
def test_fused_mask_mrc_csi_clip_combined(use_kernel):
    """Everything at once: (r, M) gains, transmit mask, transmit clip,
    imperfect-CSI precompensation and unbiased rescale."""
    r, d, k, M = 6, 90, 30, 3
    updates, gains, gains_ant, tx_mask, idx, nk = _scenario_problem(
        r, d, k, M=M, dropped=2, seed=11)
    updates = 3.0 * updates
    kw = dict(d=d, sigma0=0.2, r=r, tx_mask=tx_mask, clip=1.0,
              gains_est=gains * 1.07, unbiased_rescale=True)
    dh0, e0, y0 = aggregation.aircomp_aggregate(
        updates, idx, gains, 0.7, nk, **kw)
    dh1, e1, y1 = aggregation.aircomp_aggregate_fused(
        updates, idx, gains, 0.7, nk, gains_ant=gains_ant,
        use_kernel=use_kernel, **kw)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas", "jax_ref"])
def test_fused_all_dropped_round_is_finite(use_kernel):
    """tx_mask all zero: the realized-r floor (max(sum mask, 1)) keeps the
    reconstruction finite — delta_hat is exactly noise/beta on the
    support, energy is exactly zero — on oracle and fused paths alike."""
    r, d, k = 4, 60, 15
    updates, gains, _, _, idx, nk = _scenario_problem(r, d, k)
    tx_mask = jnp.zeros((r,), jnp.float32)
    kw = dict(d=d, sigma0=0.5, r=r, tx_mask=tx_mask)
    dh0, e0, _ = aggregation.aircomp_aggregate(
        updates, idx, gains, 0.8, nk, **kw)
    dh1, e1, _ = aggregation.aircomp_aggregate_fused(
        updates, idx, gains, 0.8, nk, use_kernel=use_kernel, **kw)
    for dh, e in ((dh0, e0), (dh1, e1)):
        assert bool(jnp.all(jnp.isfinite(dh)))
        np.testing.assert_allclose(e, 0.0, atol=1e-12)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-6, atol=1e-7)
    # floor divisor is 1, so the support carries the raw noise over beta
    _, z = tref.dense_noise_and_mask(idx, nk, 0.5, d)
    np.testing.assert_allclose(np.asarray(dh0)[np.asarray(idx)],
                               np.asarray(z)[np.asarray(idx)] / 0.8,
                               rtol=1e-5, atol=1e-7)


def test_realized_r_floor():
    assert aggregation.realized_r(None, 7) == 7
    assert float(aggregation.realized_r(jnp.zeros((5,)), 5)) == 1.0
    assert float(aggregation.realized_r(
        jnp.array([1.0, 0.0, 1.0]), 3)) == 2.0


_SCENARIO_KW = {"markov_fading": dict(markov_rho=0.9),
                "mimo_mrc": dict(num_antennas=4),
                "dropout": dict(dropout_prob=0.4)}
_VARIANTS = {"default": {},
             "ef_clip": dict(error_feedback=True, transmit_clip=0.5),
             "csi": {}}  # csi flips channel.csi_error below


def _channel_model_names():
    from repro.core.channels import list_channel_models
    return list_channel_models()


@pytest.mark.slow
@pytest.mark.parametrize("variant", sorted(_VARIANTS))
@pytest.mark.parametrize("model", _channel_model_names())
def test_fused_default_round_parity_all_models(problem, model, variant):
    """Trainer-level sweep: for EVERY registered channel model, the
    fused-default round == the unfused escape hatch to fp32 tolerance,
    under error feedback + transmit clip and under imperfect CSI —
    2 Trainer.run rounds, same keys."""
    from repro.configs import ChannelConfig

    x, y = problem[3]
    chan_kw = dict(_SCENARIO_KW.get(model, {}))
    if variant == "csi":
        chan_kw["csi_error"] = 0.1
    outs = []
    for fused in (True, False):
        cfg = PFELSConfig(num_clients=30, clients_per_round=4,
                          local_steps=2, rounds=2, use_fused_kernel=fused,
                          **_VARIANTS[variant])
        import dataclasses
        cfg = dataclasses.replace(
            cfg, channel=ChannelConfig(model=model, **chan_kw))
        trainer, state = _trainer(cfg, problem)
        outs.append(trainer.run(state, x, y, rounds=2))
    (s1, m1), (s0, m0) = outs
    flat1 = ravel_pytree(s1.params)[0]
    flat0 = ravel_pytree(s0.params)[0]
    np.testing.assert_allclose(flat1, flat0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1["energy"]),
                               np.asarray(m0["energy"]), rtol=1e-4,
                               atol=1e-8)
    np.testing.assert_allclose(np.asarray(m1["beta"]),
                               np.asarray(m0["beta"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m1["eps_round"]),
                               np.asarray(m0["eps_round"]), rtol=1e-5)


needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 host devices (CI runs the fast tier on 8)")


@needs_devices
def test_sharded_fused_mask_and_mrc_matches_global_oracle():
    """aircomp_aggregate_sharded(use_kernel=True) with a transmit mask
    AND a (r_local, M) per-antenna gains shard == the single-device
    unfused oracle on the full cohort — the psum path of the fused
    default."""
    import numpy as onp
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = len(jax.devices())
    r, d, k, M = n_dev, 96, 32, 4
    updates, gains, gains_ant, tx_mask, idx, nk = _scenario_problem(
        r, d, k, M=M, dropped=max(1, r // 4), seed=21)
    kw = dict(d=d, sigma0=0.3, r=r)
    dh0, e0, y0 = aggregation.aircomp_aggregate(
        updates, idx, gains, 0.8, nk, tx_mask=tx_mask, **kw)

    mesh = Mesh(onp.asarray(jax.devices()), ("c",))
    fn = jax.shard_map(
        lambda u, g, m: aggregation.aircomp_aggregate_sharded(
            u, idx, g, 0.8, nk, axis_name="c", use_kernel=True,
            tx_mask_local=m, **kw),
        mesh=mesh, in_specs=(P("c"), P("c"), P("c")),
        out_specs=(P(), P(), P()), check_vma=False)
    dh1, e1, y1 = fn(updates, gains_ant, tx_mask)
    np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e0, rtol=1e-5)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- round-level wiring

@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=30, per_client=30, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, flat.shape[0], unravel, (x, y), loss_fn


def _trainer(cfg, problem, key=2):
    """A Trainer on the module problem and its fresh state, power limits
    drawn from PRNGKey(1) and the next round key pinned to ``key``."""
    params, _, _, _, loss_fn = problem
    trainer = Trainer(cfg, loss_fn, params)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(key))
    return trainer, state


def test_round_fused_flag_parity(problem):
    """Trainer.step(use_fused_kernel=True) == the unfused round, same key."""
    x, y = problem[3]
    outs = []
    for fused in (False, True):
        cfg = PFELSConfig(num_clients=30, clients_per_round=4,
                          local_steps=3, rounds=1, use_fused_kernel=fused)
        trainer, state = _trainer(cfg, problem)
        state, m = trainer.step(state, x, y)
        outs.append((state.params, m))
    (p0, m0), (p1, m1) = outs
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m1["energy"], m0["energy"], rtol=1e-5)
    np.testing.assert_allclose(m1["beta"], m0["beta"], rtol=1e-6)


def test_training_fn_matches_python_loop(problem):
    """The lax.scan driver (Trainer.run) reproduces T sequential
    Trainer.step calls when fed the same per-round keys."""
    x, y = problem[3]
    cfg = PFELSConfig(num_clients=30, clients_per_round=4, local_steps=2,
                      rounds=3)
    T = 3
    trainer, state = _trainer(cfg, problem, key=7)
    end, ms = trainer.run(state, x, y, rounds=T)
    s = state
    loop_losses = []
    for k in jax.random.split(state.key, T):
        s, m = trainer.step(replace(s, key=k), x, y)
        loop_losses.append(float(m["train_loss"]))
    for a, b in zip(jax.tree.leaves(end.params), jax.tree.leaves(s.params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert ms["train_loss"].shape == (T,)
    np.testing.assert_allclose(np.asarray(ms["train_loss"]), loop_losses,
                               rtol=1e-5)


def test_training_fn_error_feedback_carries_residuals(problem):
    d, (x, y) = problem[1], problem[3]
    cfg = PFELSConfig(num_clients=30, clients_per_round=4, local_steps=2,
                      rounds=2, error_feedback=True)
    trainer, state = _trainer(cfg, problem, key=8)
    end, _ = trainer.run(state, x, y, rounds=2)
    res = end.residuals
    assert res.shape == (30, d)
    assert float(jnp.sum(jnp.abs(res))) > 0        # memory accumulated
    assert not bool(jnp.any(jnp.isnan(res)))


@pytest.mark.parametrize("alg", ["wfl_p", "dp_fedavg", "fedavg"])
def test_training_fn_baselines_run(problem, alg):
    x, y = problem[3]
    cfg = PFELSConfig(num_clients=30, clients_per_round=4, local_steps=2,
                      rounds=2, algorithm=alg)
    trainer, state = _trainer(cfg, problem, key=4)
    end, ms = trainer.run(state, x, y, rounds=2)
    assert bool(jnp.all(jnp.isfinite(ms["train_loss"])))
    assert not any(bool(jnp.any(jnp.isnan(l)))
                   for l in jax.tree.leaves(end.params))


def test_training_fn_fused_server_topk(problem):
    """server_topk carries delta_hat through the scan; fused path works."""
    x, y = problem[3]
    cfg = PFELSConfig(num_clients=30, clients_per_round=4, local_steps=2,
                      rounds=3, randk_mode="server_topk",
                      use_fused_kernel=True)
    trainer, state = _trainer(cfg, problem, key=5)
    _, ms = trainer.run(state, x, y, rounds=3)
    assert bool(jnp.all(jnp.isfinite(ms["train_loss"])))


def test_error_feedback_retains_clipped_mass(problem):
    """With transmit_clip ~ 0 nothing is actually transmitted, so the
    error-feedback residual must keep (almost) the whole update — on-idx
    coordinates included — rather than treating the unclipped on-idx mass
    as sent."""
    x, y = problem[3]
    outs = {}
    for clip in (None, 1e-9):
        cfg = PFELSConfig(num_clients=30, clients_per_round=4,
                          local_steps=3, rounds=1, error_feedback=True,
                          transmit_clip=clip)
        trainer, state = _trainer(cfg, problem)
        state, _ = trainer.step(state, x, y)
        outs[clip] = float(jnp.linalg.norm(state.residuals))
    # clipped-to-zero transmission leaves strictly more in the memory than
    # the unclipped round (which really did send the on-idx mass)
    assert outs[1e-9] > outs[None] * 1.1, outs


def test_training_fn_server_topk_cold_start_is_uniform(problem):
    """Round 1 of a cold server_topk scan (zero prev_delta) must equal the
    same round under plain rand-k (randk_mode="exact") bit-for-bit: top_k
    over |zeros| would otherwise deterministically bias the support to
    coords 0..k/2."""
    x, y = problem[3]
    outs = []
    for mode in ("server_topk", "exact"):
        cfg = PFELSConfig(num_clients=30, clients_per_round=4,
                          local_steps=2, rounds=1, randk_mode=mode)
        trainer, state = _trainer(cfg, problem, key=3)
        outs.append(trainer.run(state, x, y, rounds=1)[0].params)
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        assert bool(jnp.all(a == b))


def test_training_fn_stateful_scan_matches_loop_and_resumes(problem):
    """With server_topk + error feedback: (a) run(4) == four Trainer.step
    calls threading (residuals, prev_delta) with the same round keys from
    the same zero-initialized state; (b) resuming from run(4) differs from
    a cold restart with prev_delta zeroed (the carried state is consumed,
    so chunked training does not silently reset the top-k support)."""
    x, y = problem[3]
    cfg = PFELSConfig(num_clients=30, clients_per_round=4, local_steps=2,
                      rounds=4, randk_mode="server_topk",
                      error_feedback=True)
    trainer, state = _trainer(cfg, problem, key=6)
    full, _ = trainer.run(state, x, y, rounds=4)

    s = state
    for k in jax.random.split(state.key, 4):
        s, _ = trainer.step(replace(s, key=k), x, y)
    for a, b in zip(jax.tree.leaves(full.params), jax.tree.leaves(s.params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(full.prev_delta, s.prev_delta, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(full.residuals, s.residuals, rtol=1e-5,
                               atol=1e-6)

    # (b) resuming with the carried delta differs from a cold restart
    resumed = replace(full, key=jax.random.PRNGKey(8))
    warm, _ = trainer.run(resumed, x, y, rounds=2)
    cold, _ = trainer.run(
        replace(resumed, prev_delta=jnp.zeros_like(full.prev_delta)),
        x, y, rounds=2)
    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree.leaves(warm.params),
                   jax.tree.leaves(cold.params)))
    assert diff > 0.0  # top-k support came from prev_delta, not zeros
