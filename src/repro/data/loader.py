"""Data pipelines for the FL loop.

Two regimes (DESIGN.md §10):

- **Resident**: the whole federated dataset is a device tensor
  ``(n, samples, ...)`` and the round gathers ``data_x[sel]`` in-graph —
  fine while n is small, and the bit-exact reference.
- **Streamed**: the population lives behind a :class:`CohortSource` and
  only the sampled r-client cohort batch ``(r, samples, ...)`` keyed by
  the round's ``sel`` is materialized, double-buffer prefetched onto the
  device by :func:`prefetch_cohorts` while the previous round computes.
  Device (and, with a generator-backed source, host) memory is then
  independent of the population size n.

Plus the stateless in-jit minibatch sampler used by per-client local
training (``sample_batch``).
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample_batch(key, x, y, batch_size: int):
    idx = jax.random.randint(key, (batch_size,), 0, x.shape[0])
    return {"x": x[idx], "y": y[idx]}


def epoch_batches(n: int, batch_size: int):
    """Static batch count for one epoch (paper runs tau epochs/round)."""
    return max(n // batch_size, 1)


# ------------------------------------------------------- cohort sources

class CohortSource:
    """A population of n clients addressable by cohort: ``cohort(sel)``
    returns the ``(r, samples, ...)`` data batch for the selected client
    ids — the streamed replacement for the in-graph ``data_x[sel]``
    gather. Implementations must be deterministic in ``sel`` (the same
    client always serves the same samples), which is what makes the
    streamed bank bit-identical to the resident path."""

    n: int

    def cohort(self, sel) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError


class ArraySource(CohortSource):
    """Host-array-backed source: the ``(n, samples, ...)`` tensors stay in
    host memory (numpy) and ``cohort`` is a row gather. The small-n /
    parity-testing source."""

    def __init__(self, x, y):
        self.x = np.asarray(x)
        self.y = np.asarray(y)
        self.n = int(self.x.shape[0])

    def cohort(self, sel):
        sel = np.asarray(sel)
        return self.x[sel], self.y[sel]


class ClientFnSource(CohortSource):
    """Generator-backed source for populations too large to materialize:
    ``cohort_fn(sel) -> (cx, cy)`` synthesizes (or fetches) the selected
    clients' samples on demand — O(r), never O(n), in any memory.
    ``repro.data.make_population_source`` builds the synthetic one."""

    def __init__(self, cohort_fn: Callable, n: int):
        self._cohort_fn = cohort_fn
        self.n = int(n)

    def cohort(self, sel):
        return self._cohort_fn(np.asarray(sel))


def as_cohort_source(data_x, data_y=None) -> CohortSource:
    """Normalize the Trainer's ``(data_x, data_y)`` arguments: pass a
    :class:`CohortSource` through, wrap array pairs in an
    :class:`ArraySource`."""
    if isinstance(data_x, CohortSource):
        if data_y is not None:
            raise ValueError("pass either (data_x, data_y) arrays or a "
                             "CohortSource, not both")
        return data_x
    if data_y is None:
        raise ValueError("data_y is required when data_x is an array")
    return ArraySource(data_x, data_y)


# ------------------------------------------------------------- prefetch

_STOP = object()


class _PrefetchError:
    def __init__(self, exc: BaseException):
        self.exc = exc


def prefetch_cohorts(source: CohortSource, sels: Iterable,
                     depth: int = 2,
                     device_put: Optional[Callable] = None):
    """Double-buffered host→device cohort pipeline (DESIGN.md §10).

    A background thread walks the per-round selections ``sels``, gathers
    each cohort from ``source`` and stages it on device, keeping up to
    ``depth`` cohorts in flight — so the host gather (and host→device
    copy) of round t+1 overlaps the device compute of round t. Yields
    ``(cx, cy)`` device arrays in round order; worker exceptions re-raise
    at the consuming round.
    """
    put = device_put if device_put is not None else (
        lambda a: jax.device_put(jnp.asarray(a)))
    q: queue.Queue = queue.Queue(maxsize=max(int(depth), 1))
    stop = threading.Event()

    def _put(item) -> bool:
        """Bounded put that gives up when the consumer is gone, so an
        abandoned generator (consumer raised mid-run) never leaves the
        worker blocked forever holding staged device cohorts."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for i, sel in enumerate(sels):
                if stop.is_set():
                    return
                # the profiler's span of one cohort: ``round`` is its
                # index in ``sels``, ``bytes`` what goes to the device
                with jax.profiler.TraceAnnotation("loader.cohort",
                                                  round=i) as span:
                    cx, cy = source.cohort(sel)
                    span.set_metadata(bytes=int(cx.nbytes + cy.nbytes))
                    staged = (put(cx), put(cy))
                if not _put(staged):
                    return
        except BaseException as e:      # surfaced on the consumer side
            _put(_PrefetchError(e))
            return
        _put(_STOP)

    threading.Thread(target=worker, daemon=True,
                     name="cohort-prefetch").start()
    try:
        while True:
            item = q.get()
            if item is _STOP:
                return
            if isinstance(item, _PrefetchError):
                raise item.exc
            yield item
    finally:
        stop.set()      # unblock + drain the worker on early exit
        while not q.empty():
            try:
                q.get_nowait()
            except queue.Empty:
                break
