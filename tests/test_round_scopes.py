"""The round's layer names (PERF.md section 3): every layer of the compiled
round runs under one flat ``pfels.*`` name scope, which its ops keep as
op_name metadata through scan, vmap and grad; the streamed loop's host
steps write one profiler span each, carrying the round they serve."""
import re

import jax
import numpy as np
import pytest

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_MLP
from repro.fl import Trainer
from repro.models import cnn

SCOPES = {"pfels.cohort", "pfels.channel", "pfels.support", "pfels.beta",
          "pfels.local_train", "pfels.aircomp", "pfels.ledger",
          "pfels.bank", "pfels.server"}
HOST_SPANS = {"streamed.cohorts", "streamed.bank_clone",
              "streamed.prefetch_wait", "streamed.gather",
              "streamed.dispatch", "streamed.scatter",
              "streamed.stack_metrics", "loader.cohort"}
BASE = dict(num_clients=20, clients_per_round=4, local_steps=2,
            local_lr=0.05, compression_ratio=0.3, epsilon=2.0, rounds=2,
            use_fused_kernel=True)


@pytest.fixture(scope="module")
def problem():
    """20 clients of 20 random 1x8x8 samples (made in numpy: op-by-op
    device calls would each compile)."""
    params = jax.jit(cnn.init_cnn, static_argnums=1)(
        jax.random.PRNGKey(0), BENCH_MLP)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((20, 20, 1, 8, 8), np.float32)
    y = rng.integers(0, 10, (20, 20), np.int32)
    return params, x, y, lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)


@pytest.fixture(scope="module")
def compiled_text(problem):
    """The compiled 2-round run (fused kernel, rand_k support)."""
    params, x, y, loss_fn = problem
    trainer = Trainer(PFELSConfig(**BASE), loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    return trainer.run_program(2).lower(state, x, y).compile().as_text()


@pytest.fixture(scope="module")
def op_names(compiled_text):
    """The op_name of every instruction of the compiled 2-round run."""
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def _scopes(op_name):
    return set(re.findall(r"\bpfels\.[a-z_]+", op_name))


def test_every_layer_is_named(op_names):
    assert set().union(*map(_scopes, op_names)) == SCOPES


def test_no_op_carries_two_scopes(op_names):
    assert [o for o in op_names if len(_scopes(o)) > 1] == []


def test_local_training_backward_is_named(op_names):
    """grad's transposed ops keep the scope local training runs under;
    nothing else in the round differentiates. (A reducer's body keeps a
    relative op_name, without the program's path; it is not an op of
    the round.)"""
    backward = [o for o in op_names
                if o.startswith("jit(trainer_run)/") and "transpose(" in o]
    assert backward
    assert all(_scopes(o) == {"pfels.local_train"} for o in backward)


def test_aggregation_scatters_the_support_once(compiled_text):
    """The dense mask and noise columns come from one scatter of the
    support (DESIGN.md section 4); on a TPU each scatter adds a sort."""
    scatters = [line for line in compiled_text.splitlines()
                if re.search(r"= \S+ scatter\(", line)
                and "pfels.aircomp" in line]
    assert len(scatters) == 1, scatters


def test_run_program_is_the_resident_run(problem):
    params, x, y, loss_fn = problem
    trainer = Trainer(PFELSConfig(**BASE), loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    assert trainer.run_program(2) is trainer.run_program(2)
    a, _ = trainer.run(state, x, y, rounds=2)
    b, _ = trainer.run_program(2)(state, x, y)
    assert jax.tree.all(jax.tree.map(lambda u, v: bool((u == v).all()),
                                     a.params, b.params))
    streamed = Trainer(PFELSConfig(**BASE, bank_backend="streamed"),
                       loss_fn, params)
    with pytest.raises(ValueError, match="host loop"):
        streamed.run_program(2)


def test_streamed_host_spans_carry_their_round(problem, tmp_path):
    from jax.profiler import ProfileData

    params, x, y, loss_fn = problem
    trainer = Trainer(PFELSConfig(**BASE, bank_backend="streamed",
                                  error_feedback=True), loss_fn, params)
    state = trainer.init(jax.random.PRNGKey(1))
    trainer.run(state, x, y, rounds=2)     # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        out, _ = trainer.run(state, x, y, rounds=2)
        jax.block_until_ready(out.params)
    pb = next(tmp_path.glob("**/*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    spans.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(spans) == HOST_SPANS
    for name in ("streamed.prefetch_wait", "streamed.gather",
                 "streamed.dispatch", "streamed.scatter", "loader.cohort"):
        assert sorted(s["round"] for s in spans[name]) == [0, 1], name
    # the call's set-up counts to its first round, the stacking to its last
    assert [s["round"] for s in spans["streamed.cohorts"]] == [0]
    assert [s["round"] for s in spans["streamed.stack_metrics"]] == [1]
    # bytes moved: 4 clients x 20 samples of 1x8x8 f32 and i32 labels in;
    # the (4, d) residual slice in, and out with the lane keys
    d = sum(a.size for a in jax.tree.leaves(params))
    assert {s["bytes"] for s in spans["loader.cohort"]} == {
        4 * 20 * 64 * 4 + 4 * 20 * 4}
    assert {s["bytes"] for s in spans["streamed.gather"]} == {4 * d * 4}
    assert {s["bytes"] for s in spans["streamed.scatter"]} == {
        4 * d * 4 + 4 * 2 * 4}
