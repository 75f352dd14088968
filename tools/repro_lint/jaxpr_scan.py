"""Jaxpr-level trace-safety enforcement (RL206).

The AST rules (RL201-RL205) see the source; this pass sees what the
compiler sees. It lowers one representative round per execution path —
the fused (Pallas transmit kernel) and unfused paths of
``Trainer._step_impl`` plus a 2-round fused ``lax.scan`` via
``Trainer._run_impl`` — and walks every equation of the closed jaxpr
(recursing into scan/cond/pjit sub-jaxprs) looking for forbidden
primitives: host callbacks, host transfers, and non-static shapes. A
violation here means a hole in the compiled graph that no AST pattern
matched — the belt to the AST braces.
"""
from __future__ import annotations

from typing import List

from tools.repro_lint.findings import Finding

#: primitive names that must never appear inside a compiled round
FORBIDDEN_PRIMITIVES = {
    "pure_callback": "host callback in the compiled round body",
    "io_callback": "host io_callback in the compiled round body",
    "debug_callback": "debug callback left in the compiled round body",
    "callback": "host callback in the compiled round body",
    "device_put": "host transfer staged into the compiled round body",
    "infeed": "host infeed in the compiled round body",
    "outfeed": "host outfeed in the compiled round body",
}


def _iter_eqns(jaxpr):
    """Yield every equation of a jaxpr, recursing through sub-jaxprs
    (scan/while/cond bodies, pjit/closed_call callees, custom_* rules)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _iter_eqns(sub)


def _sub_jaxprs(value):
    from jax.extend import core as jcore
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def check_jaxpr(closed_jaxpr, label: str) -> List[Finding]:
    """Scan a ClosedJaxpr for forbidden primitives and non-static shapes.

    ``label`` names the lowered path (it becomes the pseudo-path of any
    finding, e.g. ``<jaxpr:step-fused>``), so baselines can target one
    execution path without blessing the others."""
    path = f"<jaxpr:{label}>"
    out: List[Finding] = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for eqn in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in FORBIDDEN_PRIMITIVES:
            out.append(Finding(
                rule="RL206", path=path, line=0, col=0,
                message=(f"primitive '{prim}': "
                         f"{FORBIDDEN_PRIMITIVES[prim]}"),
                source=prim, symbol=label))
            continue
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            shape = getattr(aval, "shape", ())
            if not all(isinstance(dim, int) for dim in shape):
                out.append(Finding(
                    rule="RL206", path=path, line=0, col=0,
                    message=(f"primitive '{prim}' has a non-static shape "
                             f"{shape}; dynamic shapes cannot be "
                             "golden-pinned"),
                    source=prim, symbol=label))
                break
    return out


def _tiny_problem():
    """A minimal-cost instance of the shared golden problem
    (tools/update_goldens.py): same model family and config surface, small
    enough that tracing both paths stays in single-digit seconds."""
    import jax

    from jax.flatten_util import ravel_pytree

    from repro.configs.paper_models import BENCH_MLP
    from repro.data import make_federated_classification
    from repro.models import cnn

    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    x, y, _, _ = make_federated_classification(
        key, n_clients=8, per_client=8, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)   # noqa: E731
    del ravel_pytree
    return params, x, y, loss_fn


def lint_lowered_rounds() -> List[Finding]:
    """RL206 over one representative round per execution path.

    Lowers ``Trainer._step_impl`` with the fused Pallas transmit kernel
    on and off (the two numerics paths the goldens pin), plus a 2-round
    fused ``_run_impl`` so the ``lax.scan`` body itself is swept."""
    import jax

    from repro.configs import PFELSConfig
    from repro.fl import Trainer
    from repro.fl.api import replace as state_replace

    params, x, y, loss_fn = _tiny_problem()
    base = dict(num_clients=8, clients_per_round=2, local_steps=1,
                local_lr=0.05, compression_ratio=0.3, epsilon=2.0,
                rounds=2)

    out: List[Finding] = []
    for label, fused in (("step-fused", True), ("step-unfused", False)):
        cfg = PFELSConfig(**base, use_fused_kernel=fused)
        trainer = Trainer(cfg, loss_fn, params)
        state = state_replace(trainer.init(jax.random.PRNGKey(1)),
                              key=jax.random.PRNGKey(2))
        closed = jax.make_jaxpr(trainer._step_impl)(state, x, y)
        out.extend(check_jaxpr(closed, label))

    cfg = PFELSConfig(**base, use_fused_kernel=True)
    trainer = Trainer(cfg, loss_fn, params)
    state = state_replace(trainer.init(jax.random.PRNGKey(1)),
                          key=jax.random.PRNGKey(2))
    closed = jax.make_jaxpr(
        lambda s: trainer._run_impl(s, x, y, 2))(state)
    out.extend(check_jaxpr(closed, "run-scan-fused"))
    return out
