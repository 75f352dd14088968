"""Error-feedback option [28-30]: residual memory accumulates the
untransmitted mass and improves sparsified convergence."""
import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.data import make_federated_classification
from repro.fl import Trainer
from repro.fl.api import replace
from repro.models import cnn


def _problem():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=20, per_client=30, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, flat.shape[0], unravel, (x, y, xt, yt), loss_fn


def test_error_feedback_runs_and_accumulates():
    params, d, unravel, (x, y, xt, yt), loss_fn = _problem()
    cfg = PFELSConfig(num_clients=20, clients_per_round=4, local_steps=3,
                      local_lr=0.05, compression_ratio=0.2, epsilon=4.0,
                      rounds=3, error_feedback=True)
    trainer = Trainer(cfg, loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    assert state.residuals.shape == (20, d)
    for t in range(3):
        state, m = trainer.step(
            replace(state, key=jax.random.PRNGKey(10 + t)), x, y)
    # residual mass exists for the clients that participated
    assert float(jnp.sum(jnp.abs(state.residuals))) > 0
    assert jnp.isfinite(m["train_loss"])


def test_error_feedback_residual_is_untransmitted_mass():
    """For a participating client: residual = update - sparsified(update),
    i.e. exactly the coordinates outside omega."""
    params, d, unravel, (x, y, xt, yt), loss_fn = _problem()
    cfg = PFELSConfig(num_clients=20, clients_per_round=20, local_steps=2,
                      local_lr=0.05, compression_ratio=0.25, epsilon=4.0,
                      rounds=1, error_feedback=True)
    trainer = Trainer(cfg, loss_fn, params)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(0))
    state, m = trainer.step(state, x, y)
    res = state.residuals
    k = int(round(0.25 * d))
    # every client participated; each residual has exactly d-k nonzeros
    # (up to exact zero update coords)
    nz = jnp.sum(res != 0, axis=1)
    assert int(nz.max()) <= d - k
