"""Sharded cohort execution (DESIGN.md §7): parity with the vmapped path.

The sharded round must match the single-device round — params, metrics,
residuals — to fp32 accumulation order, on a multi-device CPU mesh. The
inline tests run whenever the process already has >= 2 host devices (the
CI docs job forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
on a single-device session a subprocess fallback (marked slow) re-executes
this module under the forced 8-device platform, so the full tier-1 run
exercises the sharded path either way.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.data import make_federated_classification
from repro.fl import Trainer
from repro.fl.api import replace
from repro.launch.mesh import cohort_shape, make_cohort_mesh, make_mesh
from repro.models import cnn

MULTI = len(jax.devices()) >= 2
needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs >= 2 host devices (see subprocess fallback)")

BASE = dict(num_clients=30, clients_per_round=8, local_steps=2, rounds=2)


# ------------------------------------------------------------ mesh builder

def test_cohort_shape_divisors():
    assert cohort_shape(32, 8) == (2, 4)       # full mesh, pod <= data
    assert cohort_shape(8, 8) == (2, 4)
    assert cohort_shape(5, 8) == (1, 5)        # largest divisor of r
    assert cohort_shape(6, 4) == (1, 3)
    assert cohort_shape(7, 4) == (1, 1)        # nothing divides -> replicated
    assert cohort_shape(1, 8) == (1, 1)
    assert cohort_shape(9, 3) == (1, 3)


def test_cohort_mesh_single_device():
    mesh = make_cohort_mesh(8, devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"pod": 1, "data": 1}


@needs_devices
def test_cohort_mesh_multi_device():
    mesh = make_cohort_mesh(8)
    n = mesh.shape["pod"] * mesh.shape["data"]
    assert n > 1 and 8 % n == 0


# ------------------------------------------------------------ parity

def _make_problem():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    flat, unravel = ravel_pytree(params)
    x, y, _, _ = make_federated_classification(
        key, n_clients=30, per_client=30, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, flat.shape[0], unravel, (x, y), loss_fn


@pytest.fixture(scope="module")
def problem():
    return _make_problem()


def _run(problem, cfg, mesh=None, t_rounds=None):
    """One Trainer.step (or Trainer.run over ``t_rounds``) from the same
    fresh state: (params, metrics, residuals, prev_delta)."""
    params, _, _, (x, y), loss_fn = problem
    trainer = Trainer(cfg, loss_fn, params, mesh=mesh)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(2))
    if t_rounds is not None:
        state, metrics = trainer.run(state, x, y, rounds=t_rounds)
    else:
        state, metrics = trainer.step(state, x, y)
    return state.params, metrics, state.residuals, state.prev_delta


def _assert_parity(problem, extra, mesh, t_rounds=None, atol=5e-5):
    cfg_v = PFELSConfig(**BASE, **extra)
    cfg_s = dataclasses.replace(cfg_v, client_sharding="cohort")
    out_v = _run(problem, cfg_v, t_rounds=t_rounds)
    out_s = _run(problem, cfg_s, mesh=mesh, t_rounds=t_rounds)
    for lv, ls in zip(jax.tree.leaves(out_v), jax.tree.leaves(out_s)):
        np.testing.assert_allclose(np.asarray(lv, np.float32),
                                   np.asarray(ls, np.float32),
                                   atol=atol, rtol=5e-4)


@needs_devices
def test_sharded_round_parity(problem):
    _assert_parity(problem, {}, make_cohort_mesh(BASE["clients_per_round"]))


@needs_devices
def test_sharded_round_parity_fused_kernel(problem):
    _assert_parity(problem, dict(use_fused_kernel=True),
                   make_cohort_mesh(BASE["clients_per_round"]))


@needs_devices
def test_sharded_round_parity_error_feedback(problem):
    # the bank's residuals must match the vmapped scatter-back
    # client-for-client
    _assert_parity(problem, dict(error_feedback=True, transmit_clip=0.5),
                   make_cohort_mesh(BASE["clients_per_round"]))


@needs_devices
def test_sharded_training_fn_parity(problem):
    _assert_parity(problem, dict(error_feedback=True),
                   make_cohort_mesh(BASE["clients_per_round"]), t_rounds=2,
                   atol=1e-4)


@needs_devices
def test_nondivisible_cohort_falls_back_exact(problem):
    """r=5 on a 2- or 3-shard mesh (neither divides 5): the round must
    take the replicated (vmapped) path and match BITWISE."""
    n = min(3, len(jax.devices()))
    bad = make_mesh(np.array(jax.devices()[:n]).reshape(1, n),
                    ("pod", "data"))
    cfg_v = PFELSConfig(**{**BASE, "clients_per_round": 5})
    cfg_s = dataclasses.replace(cfg_v, client_sharding="cohort")
    out_v = _run(problem, cfg_v)
    out_s = _run(problem, cfg_s, mesh=bad)
    for lv, ls in zip(jax.tree.leaves(out_v), jax.tree.leaves(out_s)):
        assert bool(jnp.array_equal(lv, ls))


# ------------------------------------------------- single-device fallback

@pytest.mark.slow
@pytest.mark.skipif(MULTI, reason="inline tests already ran multi-device")
def test_parity_in_subprocess():
    """Re-run this module's parity checks under a forced 8-device host
    platform (XLA device count is fixed at process start, so a fresh
    interpreter is required)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "SHARDED PARITY OK" in proc.stdout


if __name__ == "__main__":
    # subprocess entry: run the core parity set with >= 2 devices
    assert len(jax.devices()) >= 2, "forced host device count did not apply"
    prob = _make_problem()
    mesh = make_cohort_mesh(BASE["clients_per_round"])
    _assert_parity(prob, {}, mesh)
    _assert_parity(prob, dict(use_fused_kernel=True), mesh)
    _assert_parity(prob, dict(error_feedback=True, transmit_clip=0.5), mesh)
    _assert_parity(prob, dict(error_feedback=True), mesh, t_rounds=2,
                   atol=1e-4)
    print("SHARDED PARITY OK")
