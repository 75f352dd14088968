"""End-to-end behaviour tests for the paper's system.

Validates the paper's principal empirical claims at CPU scale:
  1. PFELS trains to useful accuracy under a fixed per-round DP budget.
  2. PFELS uses fewer subcarriers (communication) than the full-update
     baselines (Table 2/3).
  3. PFELS consumes less transmit energy than WFL-P (Fig. 7).
  4. The production (pod-client) train step runs numerically and the
     PFELS transform keeps the model finite.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs import PFELSConfig, reduced_config
from repro.configs.paper_models import BENCH_MLP
from repro.data import make_federated_classification
from repro.fl import Trainer, evaluate
from repro.fl.api import replace
from repro.models import cnn, transformer as T

pytestmark = pytest.mark.slow  # multi-round training / production steps


@pytest.fixture(scope="module")
def fl_setting():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=40, per_client=40, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, flat.shape[0], unravel, (x, y, xt, yt), loss_fn


def _run(alg, fl_setting, rounds=20, p=0.3, eps=2.0, seed=11):
    params, d, unravel, (x, y, xt, yt), loss_fn = fl_setting
    cfg = PFELSConfig(num_clients=40, clients_per_round=8, local_steps=5,
                      local_lr=0.05, compression_ratio=p, epsilon=eps,
                      rounds=rounds, momentum=0.9, algorithm=alg)
    trainer = Trainer(cfg, loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    energy, subc = 0.0, 0
    for t in range(rounds):
        state, m = trainer.step(
            replace(state, key=jax.random.PRNGKey(seed * 1000 + t)), x, y)
        energy += float(m["energy"])
        subc = int(m["subcarriers"])
    _, acc = evaluate(state.params, loss_fn, xt, yt)
    return acc, energy, subc


def test_pfels_trains_under_dp(fl_setting):
    acc, energy, subc = _run("pfels", fl_setting)
    assert acc > 0.45
    assert energy > 0


def test_pfels_fewer_subcarriers_than_baselines(fl_setting):
    _, _, sub_pfels = _run("pfels", fl_setting, rounds=2)
    _, _, sub_wflp = _run("wfl_p", fl_setting, rounds=2)
    d = fl_setting[1]
    assert sub_pfels == int(round(0.3 * d))
    assert sub_wflp == d
    assert sub_pfels < sub_wflp


def test_pfels_energy_below_wfl_p(fl_setting):
    """Fig. 7: PFELS transmits k < d coordinates -> lower energy than WFL-P
    at the same number of rounds (statistically; fixed seeds here)."""
    _, e_pfels, _ = _run("pfels", fl_setting, rounds=6, seed=3)
    _, e_wflp, _ = _run("wfl_p", fl_setting, rounds=6, seed=3)
    assert e_pfels < e_wflp


def test_production_step_numerics():
    """The pod-scale PFELS train step (single-client path) on a reduced
    arch: params stay finite and loss is reasonable."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_pfels_train_step
    cfg = reduced_config("phi3-mini-3.8b")
    mesh = make_host_mesh((1, 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params, _ = T.init_params(key, cfg)
    d = sum(x.size for x in jax.tree.leaves(params))
    pfels = PFELSConfig(num_clients=100, clients_per_round=1,
                        compression_ratio=0.3, epsilon=2.0, local_lr=0.05,
                        local_steps=1)
    step = make_pfels_train_step(cfg, pfels, d, mesh)
    batch = {
        "tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
    }
    with jax.set_mesh(mesh):
        step_j = jax.jit(step)
        p2, m = step_j(params, batch, jax.random.fold_in(key, 1))
        p3, m2 = step_j(p2, batch, jax.random.fold_in(key, 2))
    assert jnp.isfinite(m["loss"]) and jnp.isfinite(m2["loss"])
    assert float(m["energy"]) > 0
    assert not any(bool(jnp.any(jnp.isnan(x))) for x in jax.tree.leaves(p3))


def test_production_grad_accum_equivalence():
    """grad_accum=2 gives the same update direction as accum=1 (same data,
    sigma0~0, p=1 so masking is dense)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_pfels_train_step
    from repro.configs.base import ChannelConfig
    cfg = dataclasses.replace(reduced_config("mamba2-130m"),
                              dtype="float32", param_dtype="float32")
    mesh = make_host_mesh((1, 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params, _ = T.init_params(key, cfg)
    d = sum(x.size for x in jax.tree.leaves(params))
    chan = ChannelConfig(noise_std=1e-9)
    base = dict(num_clients=100, clients_per_round=1, compression_ratio=1.0,
                epsilon=1e9, local_lr=0.05, local_steps=1, channel=chan)
    batch = {
        "tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
    }
    outs = []
    with jax.set_mesh(mesh):
        for accum in (1, 2):
            pf = PFELSConfig(grad_accum=accum, **base)
            step = jax.jit(make_pfels_train_step(cfg, pf, d, mesh))
            p2, m = step(params, batch, key)
            outs.append(ravel_pytree(p2)[0])
    diff = float(jnp.max(jnp.abs(outs[0] - outs[1])))
    assert diff < 5e-3, diff


def test_production_tau_local_steps():
    """tau > 1 production step (Alg. 2 lines 6-10 at pod scale): runs,
    stays finite, and the local update differs from the tau=1 gradient
    step (multiple sequential SGD steps)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_pfels_train_step
    from repro.configs.base import ChannelConfig
    cfg = dataclasses.replace(reduced_config("phi3-mini-3.8b"),
                              dtype="float32", param_dtype="float32")
    mesh = make_host_mesh((1, 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params, _ = T.init_params(key, cfg)
    d = sum(x.size for x in jax.tree.leaves(params))
    chan = ChannelConfig(noise_std=1e-9)
    batch = {
        "tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
    }
    outs = []
    with jax.set_mesh(mesh):
        for tau in (1, 4):
            pf = PFELSConfig(num_clients=100, clients_per_round=1,
                             compression_ratio=1.0, epsilon=1e9,
                             local_lr=0.05, local_steps=tau, channel=chan)
            step = jax.jit(make_pfels_train_step(cfg, pf, d, mesh))
            p2, m = step(params, batch, key)
            assert jnp.isfinite(m["loss"])
            outs.append(ravel_pytree(p2)[0])
    diff = float(jnp.max(jnp.abs(outs[0] - outs[1])))
    assert diff > 1e-6  # tau=4 takes a different (multi-step) trajectory
