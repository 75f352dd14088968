"""Full FL rounds (simulation mode): all algorithms run and PFELS learns."""

import jax
import jax.numpy as jnp
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.data import make_federated_classification
from repro.fl import Trainer, evaluate
from repro.fl.api import replace
from repro.models import cnn


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=30, per_client=30, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, flat.shape[0], unravel, (x, y, xt, yt), loss_fn


@pytest.mark.parametrize("alg", ["pfels", "wfl_p", "wfl_pdp", "dp_fedavg",
                                 "fedavg"])
def test_all_algorithms_run(problem, alg):
    params, d, unravel, (x, y, xt, yt), loss_fn = problem
    cfg = PFELSConfig(num_clients=30, clients_per_round=4, local_steps=3,
                      local_lr=0.05, compression_ratio=0.3, epsilon=2.0,
                      rounds=2, algorithm=alg)
    trainer = Trainer(cfg, loss_fn, params)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(2))
    state, m = trainer.step(state, x, y)
    p = state.params
    assert jnp.isfinite(m["train_loss"])
    assert not any(bool(jnp.any(jnp.isnan(l))) for l in jax.tree.leaves(p))
    if alg in ("pfels", "wfl_p", "wfl_pdp"):
        assert float(m["energy"]) > 0
    if alg == "pfels":
        assert int(m["subcarriers"]) == int(round(0.3 * d))
    else:
        assert int(m["subcarriers"]) in (d,)


@pytest.mark.slow
def test_pfels_learns(problem):
    params, d, unravel, (x, y, xt, yt), loss_fn = problem
    cfg = PFELSConfig(num_clients=30, clients_per_round=8, local_steps=5,
                      local_lr=0.05, compression_ratio=0.3, epsilon=2.0,
                      rounds=25, momentum=0.9)
    trainer = Trainer(cfg, loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    _, acc0 = evaluate(params, loss_fn, xt, yt)
    for t in range(cfg.rounds):
        state, m = trainer.step(
            replace(state, key=jax.random.PRNGKey(100 + t)), x, y)
    _, acc1 = evaluate(state.params, loss_fn, xt, yt)
    assert acc1 > acc0 + 0.2, (acc0, acc1)
