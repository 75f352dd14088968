"""The ViT classifier and local training in client chunks, on the CPU.

At the tiny ViT (32 px images upsampled to 64, patch 8, hidden 64, 2
heads, 2 blocks): the program's model against the benchmark's plain
reference (``bench/models/vit.py``), every chunk size of local training
against the vmapped cohort, the chunk choice against described devices,
and ``Trainer.run`` against ``bench/reference.py`` over three rounds.
"""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_VIT_TINY
from repro.fl import rounds
from repro.models import vit

BENCH = Path(__file__).resolve().parents[1] / "bench"
TINY = {"arch": "vit", "in_channels": 3, "image_size": 32, "resize_to": 64,
        "patch_size": 8, "hidden": 64, "heads": 2, "mlp_dim": 128,
        "depth": 2, "num_classes": 10}
R = 4


def _reference_model():
    spec = importlib.util.spec_from_file_location(
        "bench_vit", BENCH / "models" / "vit.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _loss(p, b):
    return vit.vit_loss(p, BENCH_VIT_TINY, b)


def test_config_matches_reference_description():
    cfg = BENCH_VIT_TINY
    assert {k: getattr(cfg, k) for k in TINY if k != "arch"} == {
        k: v for k, v in TINY.items() if k != "arch"}
    assert vit.num_tokens(cfg) == 65


def test_vit_matches_reference_in_loss_and_gradients():
    ref = _reference_model()
    key = jax.random.PRNGKey(11)
    params = vit.init_vit(key, BENCH_VIT_TINY)
    ref_params = ref.init_params(key, TINY)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(a, b)
    kx, ky = jax.random.split(jax.random.PRNGKey(12))
    x = jax.random.normal(kx, (6, 3, 32, 32))
    y = jax.random.randint(ky, (6,), 0, 10)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.jit(jax.value_and_grad(_loss, has_aux=True))(
            params, {"x": x, "y": y})
        ref_loss, ref_g = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, TINY, x, y)))(ref_params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        # f32 rounding of the two LayerNorm forms, over two blocks
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_published_widths_give_the_stated_size():
    from repro.configs.paper_models import PAPER_VIT_S16_CIFAR10 as cfg
    shapes = jax.eval_shape(lambda k: vit.init_vit(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 21_669_514
    assert vit.num_tokens(cfg) == 197


# ------------------------------------------------------------ chunks

@pytest.fixture(scope="module")
def cohort():
    cfg = PFELSConfig(num_clients=10, clients_per_round=R, local_steps=2,
                      momentum=0.9)
    params = vit.init_vit(jax.random.PRNGKey(0), BENCH_VIT_TINY)
    kx, ky, kk = jax.random.split(jax.random.PRNGKey(1), 3)
    cx = jax.random.normal(kx, (R, 60, 3, 32, 32))
    cy = jax.random.randint(ky, (R, 60), 0, 10)
    ck = jax.random.split(kk, R)
    return rounds.local_train_slice(cfg, _loss), params, cx, cy, ck


@pytest.mark.parametrize("chunk", [1, 2, R])
def test_every_chunk_gives_the_vmapped_updates(cohort, chunk):
    train_slice, params, cx, cy, ck = cohort
    flat, losses = jax.jit(rounds.train_in_chunks, static_argnums=(0, 5))(
        train_slice, params, cx, cy, ck, chunk)
    want_flat, want_losses = jax.jit(train_slice)(params, cx, cy, ck)
    assert flat.shape == want_flat.shape == (R, want_flat.shape[1])
    # an update is a difference of parameters: each side rounds it to
    # the parameters' float32 spacing, up to one ulp of the largest
    top = max(float(jnp.max(jnp.abs(a))) for a in jax.tree.leaves(params))
    np.testing.assert_allclose(flat, want_flat, rtol=1e-5,
                               atol=2 * np.spacing(np.float32(top)))
    np.testing.assert_allclose(losses, want_losses, rtol=1e-6)


def _specs(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def test_chunk_choice_follows_the_free_memory(cohort):
    train_slice, *args = cohort
    specs = _specs(tuple(args))
    need = {c: rounds.local_train_footprint(train_slice, *specs, c)
            for c in (1, 2, R)}
    assert need[1] < need[2] < need[R]
    chunk = lambda free: rounds.client_chunk(
        train_slice, *specs, {"bytes_limit": free + 100,
                              "bytes_in_use": 100})
    assert chunk(need[R]) == R
    assert chunk(need[R] - 1) == 2
    assert chunk(need[2] - 1) == 1
    assert chunk(0) == 1
    # a backend that keeps no statistics trains the cohort at once
    assert rounds.client_chunk(train_slice, *specs, None) == R


def test_chunk_choice_takes_the_smallest_chunk_that_fits(cohort):
    """A cohort of 8 on a device that holds chunks of 2 and of 4 but not
    the whole cohort trains 2 clients at a time: past the cohort, a larger
    chunk buys memory, not throughput."""
    train_slice, params, cx, cy, ck = cohort
    eight = _specs(tuple(jnp.concatenate([a, a]) for a in (cx, cy, ck)))
    specs = (_specs(params),) + eight
    need = {c: rounds.local_train_footprint(train_slice, *specs, c)
            for c in (2, 4, 2 * R)}
    assert need[2] < need[4] < need[2 * R]
    assert rounds.client_chunk(train_slice, *specs,
                               {"bytes_limit": need[4],
                                "bytes_in_use": 0}) == 2


# ------------------------------------------------------------ round

def _reference_harness():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    import harness
    return harness


@pytest.fixture(scope="module")
def spec():
    harness = _reference_harness()
    s = harness.load_spec("vit-s16-cifar10.paper")
    program = dict(s["config"]["program"],
                   config="repro.configs.paper_models:BENCH_VIT_TINY")
    s["config"] = dict(s["config"], program=program, model=TINY)
    s["config"]["d"] = sum(harness.leaf_sizes(s["config"]))
    s["traffic"] = dict(s["traffic"], num_clients=40, samples_per_client=20,
                        clients_per_round=R, local_steps=2)
    return s


def _first_call(spec, seed):
    harness = _reference_harness()
    cell = harness.build(spec, seed)
    state, m = harness.run_call(cell, cell["state"])
    return cell, harness.snapshot(cell["state"], state, m)


def test_trainer_run_agrees_with_the_reference(spec):
    """Three rounds of ``Trainer.run`` on the tiny ViT against the plain
    reference round, by the cell's own check and limits."""
    harness = _reference_harness()
    result = harness.measure(spec, 2 ** 33 + 5, 0.2, False, jax.devices(),
                             0.0, require_kernel=False)
    assert result["correct"], result["checks"]


def test_round_in_chunks_matches_the_vmapped_round(spec, monkeypatch):
    """A device too small for the cohort trains it in chunks; the round's
    results are those of the vmapped cohort, to float rounding."""
    _, whole = _first_call(spec, 21)
    chosen = []
    real = rounds.client_chunk

    def small_device(*a):
        chosen.append(real(*a))
        return chosen[-1]
    monkeypatch.setattr(rounds, "device_memory",
                        lambda: {"bytes_limit": 1, "bytes_in_use": 0})
    monkeypatch.setattr(rounds, "client_chunk", small_device)
    _, chunked = _first_call(spec, 21)
    assert chosen and set(chosen) == {1}
    for k in ("train_loss", "update_norm", "beta", "energy"):
        np.testing.assert_allclose(chunked["metrics"][k],
                                   whole["metrics"][k], rtol=1e-4)
    np.testing.assert_allclose(chunked["flat"], whole["flat"], rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_array_equal(chunked["lanes"], whole["lanes"])
