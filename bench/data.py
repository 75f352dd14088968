"""Synthetic federated data for the benchmark, made on the device from a key.

The class-prototype family of ``repro.data.synthetic``, copied here so that
a change to the program's data module cannot move the yardstick: each
sample is its class's prototype image plus Gaussian noise of std 0.6, with
IID labels. Client ``i``'s samples come from ``fold_in(key, i)``, as in
``make_population_source``, for the resident federation too: made client
by client, the whole federation never needs more than one block of
clients' random draws at once, which is what lets 3,550 FEMNIST writers
(2.35 GiB) be made on one chip.

The prototypes and the client key are arguments of the jitted programs,
never constants baked into them, so every seed runs the same compiled
programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NOISE = 0.6
BLOCK = 64      # clients made per step of the resident federation


def _source(key, num_classes, image_shape):
    """(prototypes, client key): what every client's samples come from."""
    kp, kc = jax.random.split(key)
    return jax.random.normal(kp, (num_classes,) + tuple(image_shape),
                             jnp.float32), kc


def _client(source, cid, per_client):
    protos, kc = source
    kl, kn = jax.random.split(jax.random.fold_in(kc, cid))
    y = jax.random.randint(kl, (per_client,), 0, protos.shape[0])
    x = protos[y] + NOISE * jax.random.normal(kn, (per_client,)
                                              + protos.shape[1:])
    return x, y


@functools.partial(jax.jit, static_argnames=("n_clients", "per_client",
                                             "num_classes", "image_shape"))
def federated(key, *, n_clients, per_client, num_classes, image_shape):
    """(x (N, n, C, H, W) f32, y (N, n) i32): the whole federation, resident
    on the device, made in one jitted call."""
    source = _source(key, num_classes, image_shape)
    return jax.lax.map(lambda c: _client(source, c, per_client),
                       jnp.arange(n_clients), batch_size=BLOCK)


@functools.partial(jax.jit, static_argnames=("per_client",))
def cohort(source, sel, *, per_client):
    """(x (r, n, C, H, W), y (r, n)): the selected clients' samples, made
    on demand; client ``i`` gets row ``i`` of :func:`federated`."""
    return jax.vmap(lambda c: _client(source, c, per_client))(sel)


def population(key, *, num_classes, image_shape):
    """The ``source`` that :func:`cohort` makes a population's clients
    from; no tensor of the whole population ever exists."""
    return _source_jit(key, num_classes, tuple(image_shape))


_source_jit = jax.jit(_source, static_argnums=(1, 2))
