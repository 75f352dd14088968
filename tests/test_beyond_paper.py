"""Beyond-paper extensions: imperfect CSI + server-guided top-k."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.configs import ChannelConfig, PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.core import aggregation, channel
from repro.data import make_federated_classification
from repro.fl import Trainer
from repro.fl.api import replace
from repro.models import cnn


def test_estimate_gains_unbiased_and_bounded():
    cfg = ChannelConfig(csi_error=0.1)
    g = channel.sample_gains(jax.random.PRNGKey(0), 5000, cfg)
    ge = channel.estimate_gains(jax.random.PRNGKey(1), g, cfg)
    ratio = ge / g
    assert abs(float(ratio.mean()) - 1.0) < 0.01
    assert abs(float(ratio.std()) - 0.1) < 0.01
    # csi_error=0 is the identity
    cfg0 = ChannelConfig(csi_error=0.0)
    np.testing.assert_array_equal(
        channel.estimate_gains(jax.random.PRNGKey(1), g, cfg0), g)


def test_imperfect_csi_misaligns_aggregate():
    """With estimation error the received aggregate deviates from the
    perfectly aligned one, in proportion to csi_error."""
    key = jax.random.PRNGKey(2)
    r, d, k = 4, 64, 64
    updates = jax.random.normal(key, (r, d))
    gains = channel.sample_gains(key, r, ChannelConfig())
    idx = jnp.arange(d)
    perfect, _, _ = aggregation.aircomp_aggregate(
        updates, idx, gains, 1.0, key, d=d, sigma0=0.0, r=r)
    errs = []
    for ce in (0.05, 0.2):
        cfg = ChannelConfig(csi_error=ce)
        ge = channel.estimate_gains(jax.random.PRNGKey(3), gains, cfg)
        noisy, _, _ = aggregation.aircomp_aggregate(
            updates, idx, gains, 1.0, key, d=d, sigma0=0.0, r=r,
            gains_est=ge)
        errs.append(float(jnp.linalg.norm(noisy - perfect)))
    assert 0 < errs[0] < errs[1]


def test_server_topk_round_runs_and_selects_topk():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    d = flat.shape[0]
    x, y, xt, yt = make_federated_classification(
        key, n_clients=20, per_client=20, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    cfg = PFELSConfig(num_clients=20, clients_per_round=4, local_steps=2,
                      compression_ratio=0.2, epsilon=2.0, rounds=2,
                      randk_mode="server_topk")
    trainer = Trainer(cfg, loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    assert not bool(jnp.any(state.prev_delta))
    for t in range(2):
        state, m = trainer.step(
            replace(state, key=jax.random.PRNGKey(t)), x, y)
        assert "delta_hat" not in m
    # the aggregated update is k-sparse on the selected coords
    k = int(round(0.2 * d))
    assert int(jnp.sum(state.prev_delta != 0)) <= k
