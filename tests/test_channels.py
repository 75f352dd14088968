"""The pluggable wireless-scenario registry (DESIGN.md §11): registry
round-trip and validation, the block_fading extraction, Gauss–Markov
round-to-round correlation, multi-antenna MRC combining + post-combining
noise, Bernoulli dropout (realized-r β design, error-feedback retention),
cross-backend state carriage, and checkpointing of stateful models."""
import dataclasses
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
from repro.core import channel, channels
from repro.core.channels import (ChannelModel, ChannelRound, design_gains,
                                 effective_noise_std, get_channel_model,
                                 list_channel_models, observed_gains,
                                 realized_cohort_size,
                                 register_channel_model,
                                 unregister_channel_model)
from repro.fl import Trainer
from repro.fl.api import replace
from repro.models import cnn

BASE = dict(num_clients=20, clients_per_round=4, local_steps=2,
            local_lr=0.05, compression_ratio=0.3, epsilon=2.0, rounds=2)


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    from repro.data import make_federated_classification
    x, y, _, _ = make_federated_classification(
        key, n_clients=20, per_client=20, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, (x, y), loss_fn


def _trainer(cfg, problem):
    params, _, loss_fn = problem
    trainer = Trainer(cfg, loss_fn, params)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(2))
    return trainer, state


def _flat(p):
    return ravel_pytree(p)[0]


# ------------------------------------------------------------- registry

def test_builtin_models_registered():
    assert set(list_channel_models()) >= {
        "block_fading", "markov_fading", "mimo_mrc", "dropout"}


def test_registry_unknown_and_duplicate():
    with pytest.raises(KeyError, match="unknown channel model"):
        get_channel_model("no_such_channel")
    with pytest.raises(ValueError, match="already registered"):
        register_channel_model(
            "block_fading", get_channel_model("block_fading"))
    with pytest.raises(ValueError, match="needs init, step"):
        register_channel_model("broken", ChannelModel(
            name="broken", init=None, step=None, noise_std=None))


def test_registry_round_trip(problem):
    """A registered toy scenario is a first-class ChannelConfig.model:
    constant gains make β deterministic through a full Trainer round."""
    const_gain = 0.05

    def _step(carry, cfg, r, sel, gains_key, csi_key):
        return carry, ChannelRound(
            gains=jnp.full((r,), const_gain, jnp.float32))

    register_channel_model("toy_constant", ChannelModel(
        name="toy_constant",
        init=lambda key, n, cfg: None,
        step=_step,
        noise_std=lambda cfg: cfg.noise_std))
    try:
        cfg = PFELSConfig(**BASE, channel=ChannelConfig(
            model="toy_constant"))
        trainer, state = _trainer(cfg, problem)
        x, y = problem[1]
        end, m = trainer.run(state, x, y, rounds=2)
        assert bool(jnp.all(jnp.isfinite(m["train_loss"])))
        # beta = min_i g sqrt(d P_i)/(C1 eta tau sqrt(k)) capped by eps/C2
        from repro.core import power_control, privacy
        d = trainer.d
        k = max(int(round(cfg.compression_ratio * d)), 1)
        cap_pop = float(power_control.beta_power_cap(
            jnp.full((cfg.num_clients,), const_gain), state.power_limits,
            d, k, cfg.clip, cfg.local_lr, cfg.local_steps))
        cap_priv = privacy.beta_privacy_cap(
            cfg.epsilon, cfg.local_lr, cfg.local_steps, cfg.clip,
            cfg.clients_per_round, cfg.num_clients, cfg.resolved_delta(),
            cfg.channel.noise_std)
        assert float(m["beta"][0]) >= min(cap_pop, cap_priv) - 1e-5
        assert float(m["beta"][0]) <= cap_priv + 1e-5
    finally:
        unregister_channel_model("toy_constant")


# ---------------------------------------------------------- block_fading

def test_block_fading_step_is_the_extracted_sampler():
    """The registry entry draws exactly what the pre-registry round body
    drew: sample_gains on the gains lane, estimate_gains on the csi lane
    (skipped — obs None — under perfect CSI)."""
    model = get_channel_model("block_fading")
    gk, ck = jax.random.split(jax.random.PRNGKey(7))
    cfg = ChannelConfig()
    carry, cr = model.step(None, cfg, 16, None, gk, ck)
    assert carry is None and cr.tx_mask is None and cr.gains_obs is None
    assert bool(jnp.array_equal(cr.gains,
                                channel.sample_gains(gk, 16, cfg)))
    cfg_csi = ChannelConfig(csi_error=0.3)
    _, cr2 = model.step(None, cfg_csi, 16, None, gk, ck)
    assert bool(jnp.array_equal(
        cr2.gains_obs, channel.estimate_gains(ck, cr2.gains, cfg_csi)))
    assert effective_noise_std(cfg) == cfg.noise_std


# --------------------------------------------------------- markov_fading

def test_markov_marginal_matches_block_fading_law():
    """The Gaussian-copula construction keeps the paper's per-round
    marginal: clipped Exp(gain_mean), same mean as block_fading."""
    cfg = ChannelConfig(model="markov_fading", markov_rho=0.8)
    model = get_channel_model("markov_fading")
    n = 20000
    carry = model.init(jax.random.PRNGKey(0), n, cfg)
    carry, cr = model.step(carry, cfg, n, jnp.arange(n),
                           jax.random.PRNGKey(1), jax.random.PRNGKey(2))
    g = cr.gains
    assert float(g.min()) >= cfg.gain_clip[0] * (1 - 1e-5)
    assert float(g.max()) <= cfg.gain_clip[1] * (1 + 1e-5)
    assert abs(float(g.mean()) - cfg.gain_mean) < 0.005


@pytest.mark.parametrize("rho", [0.0, 0.5, 0.95])
def test_markov_round_to_round_correlation(rho):
    """Consecutive-round gains correlate ~rho (0 -> i.i.d. like
    block_fading); correlation is monotone in markov_rho."""
    cfg = ChannelConfig(model="markov_fading", markov_rho=rho)
    model = get_channel_model("markov_fading")
    n = 8000
    sel = jnp.arange(n)
    carry = model.init(jax.random.PRNGKey(0), n, cfg)
    carry, cr1 = model.step(carry, cfg, n, sel,
                            jax.random.PRNGKey(1), jax.random.PRNGKey(2))
    carry, cr2 = model.step(carry, cfg, n, sel,
                            jax.random.PRNGKey(3), jax.random.PRNGKey(4))
    c = np.corrcoef(np.asarray(cr1.gains), np.asarray(cr2.gains))[0, 1]
    # the copula transform attenuates the latent correlation a bit
    assert c == pytest.approx(rho, abs=0.12)


def test_markov_state_lives_in_trainstate_and_checkpoints(problem):
    """The (n,) latent carry joins TrainState, advances across run() and
    chunked resume, survives pytree flatten and checkpoint round-trip."""
    cfg = PFELSConfig(**BASE, channel=ChannelConfig(
        model="markov_fading", markov_rho=0.9))
    trainer, state = _trainer(cfg, problem)
    x, y = problem[1]
    assert state.chan.shape == (cfg.num_clients,)
    s1, _ = trainer.run(state, x, y, rounds=2)
    assert not bool(jnp.array_equal(s1.chan, state.chan))
    # resume: run(2)+run(1) == run(3) is NOT required (independent key
    # schedules), but the carry must keep evolving
    s2, _ = trainer.run(s1, x, y, rounds=1)
    assert not bool(jnp.array_equal(s2.chan, s1.chan))
    # pytree + checkpoint round-trip
    leaves, treedef = jax.tree.flatten(s1)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert bool(jnp.array_equal(rebuilt.chan, s1.chan))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ck")
        checkpoint.save_train_state(path, s1)
        restored = checkpoint.restore_train_state(path, trainer.init(
            jax.random.PRNGKey(1)))
        assert bool(jnp.array_equal(restored.chan, s1.chan))
        # and training continues bit-identically from the restored state
        a, _ = trainer.run(s1, x, y, rounds=1)
        b, _ = trainer.run(restored, x, y, rounds=1)
        assert bool(jnp.array_equal(_flat(a.params), _flat(b.params)))


# ------------------------------------------------------------- mimo_mrc

def test_mimo_combining_and_array_gain():
    """Effective gain = sum over per-antenna magnitudes; M=1 reduces
    bitwise to block_fading; the mean effective gain scales ~M."""
    model = get_channel_model("mimo_mrc")
    gk, ck = jax.random.split(jax.random.PRNGKey(3))
    cfg1 = ChannelConfig(model="mimo_mrc", num_antennas=1)
    _, cr1 = model.step(None, cfg1, 4096, None, gk, ck)
    assert bool(jnp.allclose(cr1.gains,
                             channel.sample_gains(gk, 4096, cfg1)))
    cfg8 = ChannelConfig(model="mimo_mrc", num_antennas=8)
    _, cr8 = model.step(None, cfg8, 4096, None, gk, ck)
    ratio = float(cr8.gains.mean()) / float(cr1.gains.mean())
    assert ratio == pytest.approx(8.0, rel=0.1)


def test_mimo_post_combining_noise_feeds_privacy():
    """sigma_eff = sqrt(M) sigma_0, and the per-round ε ledger charge is
    computed against it (Thm 3's C2 is ∝ 1/sigma)."""
    from repro.fl.rounds import round_epsilon_spent

    cfg1 = PFELSConfig(**BASE)
    cfg8 = dataclasses.replace(cfg1, channel=ChannelConfig(
        model="mimo_mrc", num_antennas=8))
    assert effective_noise_std(cfg8.channel) == pytest.approx(
        np.sqrt(8.0) * cfg8.channel.noise_std)
    beta = 3.0
    # same beta costs sqrt(M)x LESS epsilon under the combined noise
    assert round_epsilon_spent(cfg8, beta) == pytest.approx(
        round_epsilon_spent(cfg1, beta) / np.sqrt(8.0), rel=1e-6)


# -------------------------------------------------------------- dropout

def test_dropout_mask_and_realized_r():
    model = get_channel_model("dropout")
    cfg = ChannelConfig(model="dropout", dropout_prob=0.5)
    gk, ck = jax.random.split(jax.random.PRNGKey(5))
    _, cr = model.step(None, cfg, 4096, None, gk, ck)
    m = np.asarray(cr.tx_mask)
    assert set(np.unique(m)) <= {0.0, 1.0}
    assert m.mean() == pytest.approx(0.5, abs=0.05)
    assert float(realized_cohort_size(cr, 4096)) == m.sum()
    # the base model's gain stream is untouched by the mask draw
    base_cfg = ChannelConfig()
    assert bool(jnp.array_equal(
        cr.gains, channel.sample_gains(gk, 4096, base_cfg)))


def test_dropout_design_gains_lift_dropped_clients():
    """β-design mins over REALIZED transmitters only: a dropped client
    with the worst channel must not bind the power cap."""
    cr = ChannelRound(gains=jnp.array([1e-4, 0.05, 0.08]),
                      tx_mask=jnp.array([0.0, 1.0, 1.0]))
    g = design_gains(cr)
    assert float(g[0]) == float(np.float32(channels.DESIGN_GAIN_BIG))
    assert bool(jnp.array_equal(g[1:], cr.gains[1:]))
    assert float(realized_cohort_size(cr, 3)) == 2.0
    # observed_gains without CSI is the true gains
    assert bool(jnp.array_equal(observed_gains(cr), cr.gains))


def test_dropout_error_feedback_keeps_dropped_update(problem):
    """A dropped client transmitted nothing, so with error feedback its
    ENTIRE (pre-sparsification) update stays in its residual memory —
    verified exactly by recomputing the cohort's updates and the
    Bernoulli mask from the documented PRNG lanes."""
    import functools

    from repro.fl import rounds as rounds_mod
    from repro.fl.client import local_train, model_update
    from repro.core.channels import dropout as dropout_mod

    params, (x, y), loss_fn = problem
    cfg = PFELSConfig(**BASE, error_feedback=True,
                      channel=ChannelConfig(model="dropout",
                                            dropout_prob=0.5))
    trainer, state = _trainer(cfg, problem)
    r = cfg.clients_per_round
    for _ in range(8):
        ks = rounds_mod.split_round_key(state.key)
        new_state, m = trainer.step(state, x, y)
        r_real = float(m["r_realized"])
        assert 0.0 <= r_real <= r
        if r_real in (0.0, float(r)):
            state = new_state
            continue
        # recompute the round from the pinned lanes (DESIGN.md §5/§11)
        sel = np.asarray(rounds_mod.sample_cohort(ks[0],
                                                  cfg.num_clients, r))
        ck = jax.random.split(ks[1], r)
        keep = np.asarray(jax.random.bernoulli(
            jax.random.fold_in(ks[2], dropout_mod._MASK_TAG),
            1.0 - cfg.channel.dropout_prob, (r,)))
        train = functools.partial(
            local_train, loss_fn=loss_fn, steps=cfg.local_steps,
            lr=cfg.local_lr, clip=cfg.clip, momentum=cfg.momentum)
        new_p, _ = jax.vmap(lambda cx, cy, k: train(state.params, cx, cy,
                                                    k))(x[sel], y[sel], ck)
        flat = np.asarray(jax.vmap(
            lambda p_: _flat(model_update(state.params, p_)))(new_p))
        # EF adds the previous residual before sparsification
        flat = flat + np.asarray(state.bank.residuals)[sel]
        res = np.asarray(new_state.bank.residuals)[sel]
        for i in range(r):
            if keep[i]:
                # a kept client transmitted its (sparse) support: its
                # residual lost something relative to the full update
                assert np.linalg.norm(res[i] - flat[i]) > 1e-8, i
            else:
                # a dropped client keeps its ENTIRE update
                np.testing.assert_allclose(res[i], flat[i], rtol=1e-5,
                                           atol=1e-8, err_msg=str(i))
        return
    pytest.skip("no partially-dropped round sampled in 8 tries")


def test_dropout_all_dropped_round_is_finite(problem):
    """Even an all-dropped round (r_realized = 0) reconstructs a finite
    (noise-only) update — the realized-r floor and the finite design
    lift."""
    cfg = PFELSConfig(**BASE, channel=ChannelConfig(
        model="dropout", dropout_prob=0.97))
    trainer, state = _trainer(cfg, problem)
    x, y = problem[1]
    end, m = trainer.run(state, x, y, rounds=4)
    assert float(np.asarray(m["r_realized"]).min()) == 0.0
    assert bool(jnp.all(jnp.isfinite(_flat(end.params))))
    assert bool(jnp.all(jnp.isfinite(m["beta"])))


def test_dropout_all_dropped_digital_round_is_a_noop(problem):
    """An all-dropped round under a DIGITAL scheme received nothing and
    must apply NO update — in particular dp_fedavg must not take an
    r-fold noise-amplified pure-noise step."""
    cfg = PFELSConfig(**BASE, algorithm="dp_fedavg",
                      channel=ChannelConfig(model="dropout",
                                            dropout_prob=0.97))
    trainer, state = _trainer(cfg, problem)
    x, y = problem[1]
    seen_empty = False
    for _ in range(5):
        before = _flat(state.params)
        state, m = trainer.step(state, x, y)
        if float(m["r_realized"]) == 0.0:
            assert bool(jnp.array_equal(_flat(state.params), before))
            seen_empty = True
    assert seen_empty


def test_dropout_fused_matches_unfused_division(problem):
    """The fused aggregate under a mask divides by realized r directly
    (not a post-correction), so fused-vs-unfused stays within the usual
    fp32 accumulation-order tolerance under dropout too."""
    from repro.core import aggregation, randk

    r, d, k = 4, 512, 128
    key = jax.random.PRNGKey(8)
    u = jax.random.normal(key, (r, d))
    gains = jnp.array([0.02, 0.05, 0.03, 0.04])
    mask = jnp.array([1.0, 0.0, 1.0, 1.0])
    idx = randk.sample_indices(key, d, k)
    kw = dict(d=d, sigma0=1.0, r=r, tx_mask=mask)
    d_ref, e_ref, _ = aggregation.aircomp_aggregate(
        u, idx, gains, 2.0, key, **kw)
    d_fus, e_fus, _ = aggregation.aircomp_aggregate_fused(
        u, idx, gains, 2.0, key, use_kernel=False, **kw)
    # the fp32 accumulation-order tier (DESIGN.md §5) — the same bound
    # the maskless fused-vs-unfused pair meets
    np.testing.assert_allclose(np.asarray(d_fus), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(e_fus), float(e_ref), rtol=1e-6)


def test_dropout_digital_aggregation_averages_realized_cohort(problem):
    """fedavg under dropout must average over the updates actually
    RECEIVED (realized r), not the nominal cohort — recomputed from the
    documented PRNG lanes (DESIGN.md §5/§11)."""
    import functools

    from repro.fl import rounds as rounds_mod
    from repro.fl.client import local_train, model_update
    from repro.core.channels import dropout as dropout_mod

    params, (x, y), loss_fn = problem
    cfg = PFELSConfig(**BASE, algorithm="fedavg",
                      channel=ChannelConfig(model="dropout",
                                            dropout_prob=0.5))
    trainer, state = _trainer(cfg, problem)
    for _ in range(6):
        prev_params = state.params
        ks = rounds_mod.split_round_key(state.key)
        state, m = trainer.step(state, x, y)
        r = cfg.clients_per_round
        r_real = float(m["r_realized"])
        if r_real in (0.0, float(r)):
            continue
        # recompute the masked mean from the pinned lanes
        sel = rounds_mod.sample_cohort(ks[0], cfg.num_clients, r)
        ck = jax.random.split(ks[1], r)
        train = functools.partial(
            local_train, loss_fn=loss_fn, steps=cfg.local_steps,
            lr=cfg.local_lr, clip=cfg.clip, momentum=cfg.momentum)
        new_p, _ = jax.vmap(lambda cx, cy, k: train(prev_params, cx, cy,
                                                    k))(x[sel], y[sel], ck)
        flat = jax.vmap(lambda p_: _flat(model_update(prev_params, p_)))(
            new_p)
        keep = jax.random.bernoulli(
            jax.random.fold_in(ks[2], dropout_mod._MASK_TAG),
            1.0 - cfg.channel.dropout_prob, (r,)).astype(jnp.float32)
        expect = jnp.sum(flat * keep[:, None], axis=0) / jnp.sum(keep)
        got = _flat(state.params) - _flat(prev_params)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   rtol=1e-5, atol=1e-7)
        return
    pytest.skip("no partially-dropped round sampled in 6 tries")


def test_dropout_wraps_stateful_base(problem):
    """dropout over markov_fading composes: carry evolves, mask applies,
    and the wrapper reports the base's statefulness."""
    chan = ChannelConfig(model="dropout", dropout_base="markov_fading",
                         dropout_prob=0.3, markov_rho=0.9)
    assert get_channel_model("dropout").stateful(chan)
    cfg = PFELSConfig(**BASE, channel=chan)
    trainer, state = _trainer(cfg, problem)
    x, y = problem[1]
    assert state.chan.shape == (cfg.num_clients,)
    end, m = trainer.run(state, x, y, rounds=2)
    assert not bool(jnp.array_equal(end.chan, state.chan))
    assert bool(jnp.all(jnp.isfinite(m["train_loss"])))


def test_dropout_cannot_wrap_itself():
    with pytest.raises(ValueError, match="self-nesting"):
        ChannelConfig(model="dropout", dropout_base="dropout")


# ------------------------------------------- cross-model Trainer contract

@pytest.mark.parametrize("model", sorted(
    set(channels.list_channel_models())))
def test_every_model_runs_both_backends_bit_identically(model, problem):
    """Resident-scan vs streamed-host-loop bit parity for EVERY registered
    model — the channel carry takes the same lanes and ops under both
    (DESIGN.md §11 parity rule)."""
    chan = ChannelConfig(model=model)
    cfg_r = PFELSConfig(**BASE, channel=chan)
    cfg_s = dataclasses.replace(cfg_r, bank_backend="streamed")
    tr, sr = _trainer(cfg_r, problem)
    ts, ss = _trainer(cfg_s, problem)
    x, y = problem[1]
    sr, mr = tr.run(sr, x, y, rounds=2)
    ss, ms = ts.run(ss, np.asarray(x), np.asarray(y), rounds=2)
    assert bool(jnp.array_equal(_flat(sr.params), _flat(ss.params)))
    if sr.chan is None:
        assert ss.chan is None
    else:
        assert bool(jnp.array_equal(sr.chan, ss.chan))
    for k in mr:
        assert bool(jnp.array_equal(mr[k], jnp.asarray(ms[k]))), k


def test_metric_contract_uniform_across_models(problem):
    """Every channel model reports the same metric keys (r_realized
    included) — the fixed Trainer metrics contract extends over the
    scenario axis."""
    x, y = problem[1]
    keysets = set()
    for model in channels.list_channel_models():
        cfg = PFELSConfig(**BASE, channel=ChannelConfig(model=model))
        trainer, state = _trainer(cfg, problem)
        _, m = trainer.step(state, x, y)
        keysets.add(frozenset(m))
        if model != "dropout":
            assert float(m["r_realized"]) == cfg.clients_per_round
    assert len(keysets) == 1
