"""The check that decides ``correct``, at a size a CPU test run holds.

The cell is ``vgg11-cifar10.paper`` with its traffic and committed limits,
cut to the program's small VGG (width 1/8, 16x16 images) and 200 clients
of 20 samples. The chip look is skipped and the compiled kernel is not
asked for (the CPU interprets it); everything else is a real run: set-up,
the window, the reference and the comparison. A sound run must come out
correct, the control and each fault a single chip can have must not.
"""
import jax
import jax.numpy as jnp
import pytest

import compare
import harness
import reference

CELL = "vgg11-cifar10.paper"
SEED = 2 ** 31 + 2 ** 32 + 17   # a seed wider than 32 bits


@pytest.fixture(scope="module")
def spec():
    s = harness.load_spec(CELL)
    program = dict(s["config"]["program"],
                   config="repro.configs.paper_models:BENCH_CNN_CIFAR")
    s["config"] = dict(s["config"], program=program, model={
        "arch": "vgg", "in_channels": 3, "image_size": 16,
        "num_classes": 10, "width_mult": 0.125})
    s["config"]["d"] = sum(harness.leaf_sizes(s["config"]))
    s["traffic"] = dict(s["traffic"], num_clients=200, samples_per_client=20)
    return s


def run(spec):
    return harness.measure(spec, SEED, 0.2, False, jax.devices(), 0.0,
                           require_kernel=False)


def failed(result):
    return {k: c for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


def test_sound_run_is_correct(spec):
    result = run(spec)
    assert result["correct"], failed(result)


def test_control_is_not_correct(spec):
    """The reference in bfloat16, put in the program's place."""
    fl = harness.fl_params(spec["traffic"])
    cell = harness.build(spec, SEED)
    args = (cell["keys"], harness.net_of(spec["config"]),
            spec["config"]["model"], fl, cell["cohort"], cell["ref_data"],
            cell["rounds_per_call"])
    checks = compare.check(reference.run(*args, dtype=jnp.bfloat16),
                           reference.run(*args),
                           harness.leaf_sizes(spec["config"]),
                           spec["limits"], cell["rounds_per_call"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _state_unchanged(monkeypatch):
    from repro.fl.api import Trainer
    real = Trainer.run

    def run_(self, state, *a, **kw):
        return state, real(self, state, *a, **kw)[1]
    monkeypatch.setattr(Trainer, "run", run_)


def _half_cohort(monkeypatch):
    from repro.core import aggregation
    real = aggregation.aircomp_aggregate_fused

    def agg(updates, idx, gains, beta, key, *, r, **kw):
        h = r // 2
        return real(updates[:h], idx, gains[:h], beta, key, r=h, **kw)
    monkeypatch.setattr(aggregation, "aircomp_aggregate_fused", agg)


def _support_altered(monkeypatch):
    from repro.core import randk
    real = randk.sample_indices
    monkeypatch.setattr(randk, "sample_indices", lambda key, d, k: real(
        jax.random.fold_in(key, 1), d, k))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_cohort,
                                   _support_altered])
def test_fault_is_not_correct(spec, monkeypatch, fault):
    fault(monkeypatch)
    result = run(spec)
    assert not result["correct"], result["checks"]
