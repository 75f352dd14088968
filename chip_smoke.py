"""Smoke run of the PFELS round on a TPU, at the paper's full VGG-11 width.

    python chip_smoke.py             # one chip: main phase + kernel parity
    python chip_smoke.py --chips 4   # four chips: cohort-sharded vs vmapped

One process, no children. It exits non-zero, and prints no result, when
JAX finds no TPU. Any failed phase raises, so the exit code is non-zero.
Earlier lines of standard output are one JSON object per phase (compile
seconds, steady rounds/s, peak device bytes: a smoke record, not a
benchmark). The last line is ``{"ok": true, "device": {...}}``.

Phases:

- main (one chip): ``PAPER_VGG11_CIFAR10`` through
  ``repro.launch.train.run_simulation`` with r=32 of N=100 clients, tau=5,
  batch 50, p=0.3, eps=1.5, the default block_fading / rand_k / resident
  bank / fused kernel, on synthetic 3x32x32 data made from ``--seed``.
  It calls ``Trainer.run(rounds=3)`` twice; both calls must run one
  compiled program. Loss, beta, energy and the ledger totals must be
  finite, and that program must hold the compiled Pallas kernel
  (``tpu_custom_call``), so an interpret-mode fallback cannot pass.
- parity (one chip): ``fused_transmit`` with the compiled kernel against
  the pure-JAX oracle on one (32, d) update batch and one noise key.
- cohort (``--chips 4`` only): one round with ``client_sharding="cohort"``
  on a 2x2 cohort mesh against the vmapped round on device 0, same key.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

R = 32            # cohort size r
ROUNDS = 3        # rounds per Trainer.run call
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# the name JAX gives the program Trainer.run compiles (resident bank)
RUN_PROGRAM = "jit(trainer_run)"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# norm-wise relative error allowed between the compiled kernel and the
# oracle. Both compute in f32 but sum in different orders: the kernel
# reduces each 4096-wide tile and accumulates the energy over ~2,250
# sequential tiles, XLA reduces as a tree. A random-walk bound for that
# many f32 additions is sqrt(2250) * 2^-24 ~ 3e-6; 1e-5 leaves 3x room
# and still catches any wrong mask, coefficient or noise column, which
# moves the result by O(1).
PARITY_RTOL = 1e-5
# parameter parity of the cohort-sharded round against the vmapped one:
# the tolerance tests/test_sharded_round.py uses (fp32 accumulation order)
COHORT_ATOL, COHORT_RTOL = 5e-5, 5e-4


class CompileLog:
    """Counts backend compiles of one named program, their seconds, and
    persistent-cache hits, through JAX's public monitoring listeners."""

    def __init__(self, fun_name):
        self.fun_name = fun_name
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        if event == BACKEND_COMPILE and fun_name == self.fun_name:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1


def tpu_or_exit():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (devices[0] is "
                 f"{dev.platform!r}); there is no CPU fallback")
    return dev


def emit(record):
    print(json.dumps(record), flush=True)


def train_argv(seed):
    from repro.configs.paper_models import PAPER_VGG11_CIFAR10
    # batch 50 is local_train's default; 500 samples per client is
    # CIFAR-10's 50,000 training images over N=100 clients
    return ["--model", PAPER_VGG11_CIFAR10.name, "--clients", "100",
            "--sampled", str(R), "--tau", "5", "--per-client", "500",
            "--p", "0.3", "--epsilon", "1.5", "--rounds", str(2 * ROUNDS),
            "--eval-every", str(ROUNDS), "--seed", str(seed)]


def assert_kernel_compiled(hlo_text, what):
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{what}: no tpu_custom_call in the lowered "
                             f"program; the transmit kernel did not compile")


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main_phase(seed):
    """``run_simulation`` for 2 x ROUNDS rounds: two
    ``Trainer.run(rounds=ROUNDS)`` calls, each followed by an evaluation.
    Both calls must run one compiled program."""
    from repro.launch import train
    args = train.build_parser().parse_args(train_argv(seed))
    log = CompileLog(RUN_PROGRAM)
    out, sim, state = train.run_simulation(args)
    cold, warm = out["history"]
    if log.compiles != 1:
        raise AssertionError(f"{2 * ROUNDS} rounds in two run(rounds="
                             f"{ROUNDS}) calls compiled {log.compiles} "
                             f"{RUN_PROGRAM} program(s); the second call "
                             f"must reuse the first call's program")
    # the program the two calls ran, lowered again from the same trainer
    run = jax.jit(functools.partial(sim.trainer.run, rounds=ROUNDS))
    assert_kernel_compiled(run.lower(sim.state, sim.x, sim.y).as_text(),
                           "Trainer.run")

    per_round = {k: cold["per_round"][k] + warm["per_round"][k]
                 for k in cold["per_round"]}
    for name, v in per_round.items():
        if not np.all(np.isfinite(v)):
            raise AssertionError(f"non-finite {name}: {v}")
    totals = sim.trainer.ledger_totals(state)
    ledger = [totals["eps_max_round"], *totals["basic"],
              *totals["advanced"]]
    if not all(math.isfinite(v) for v in ledger):
        raise AssertionError(f"non-finite ledger totals: {totals}")
    if totals["spends"] != 2 * ROUNDS:
        raise AssertionError(f"ledger charged {totals['spends']} rounds, "
                             f"ran {2 * ROUNDS}")
    emit({"phase": "main", "model": args.model, "d": sim.d,
          "r": R, "n": args.clients, "tau": args.tau,
          "compiles": log.compiles, "compile_s": log.seconds,
          "cache_hits": log.cache_hits, "cold_run_s": cold["run_s"],
          "steady_rounds_per_s": ROUNDS / warm["run_s"],
          **per_round,
          "test_acc": [h["test_acc"] for h in out["history"]],
          "eps_basic": totals["basic"][0],
          "peak_bytes_in_use": peak_bytes(jax.devices()[0])})
    return sim.d


def rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def parity_phase(d, seed):
    from repro.kernels.pfels_transmit.ops import fused_transmit
    ku, kg, ki, kn = jax.random.split(jax.random.PRNGKey(seed), 4)
    updates = 1e-3 * jax.random.normal(ku, (R, d), jnp.float32)
    gains = jax.random.uniform(kg, (R,), minval=0.01, maxval=0.1)
    # k column indices drawn with repeats: both sides build the same mask
    # and noise columns from them, and the draw avoids compiling rand_k's
    # permutation of d, whose sort takes minutes to compile for a TPU
    idx = jax.random.randint(ki, (int(0.3 * d),), 0, d)
    beta = jnp.float32(0.5)
    errs = {}
    # clip=None is the default round's single pass; a clip adds the
    # per-client sum-of-squares pass
    for clip in (None, 0.02):
        def transmit(use_kernel, clip=clip):
            return jax.jit(lambda u, g, i, b, k: fused_transmit(
                u, i, g, b, k, d=d, sigma0=1e-4, r=R, clip=clip,
                use_kernel=use_kernel))
        fast = transmit(True)
        assert_kernel_compiled(
            fast.lower(updates, gains, idx, beta, kn).as_text(),
            f"fused_transmit(clip={clip})")
        dh_k, e_k, _ = jax.block_until_ready(
            fast(updates, gains, idx, beta, kn))
        with jax.default_matmul_precision("highest"):
            dh_o, e_o, _ = jax.block_until_ready(
                transmit(False)(updates, gains, idx, beta, kn))
        tag = "noclip" if clip is None else "clip"
        errs[f"delta_hat_{tag}"] = rel_err(dh_k, dh_o)
        errs[f"energy_{tag}"] = rel_err(e_k, e_o)
    emit({"phase": "parity", "r": R, "d": d, "rtol": PARITY_RTOL,
          "rel_err": errs})
    bad = {k: v for k, v in errs.items() if not v <= PARITY_RTOL}
    if bad:
        raise AssertionError(f"kernel vs oracle beyond {PARITY_RTOL}: {bad}")


def cohort_phase(seed):
    from repro.fl import Trainer
    from repro.launch import train
    from repro.launch.mesh import make_cohort_mesh
    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    args = train.build_parser().parse_args(train_argv(seed))
    sim = train.build_simulation(args)
    mesh = make_cohort_mesh(R)
    if dict(mesh.shape) != {"pod": 2, "data": 2}:
        raise AssertionError(f"cohort mesh is {dict(mesh.shape)}, not 2x2")
    sharded = Trainer(dataclasses.replace(sim.cfg, client_sharding="cohort"),
                      sim.trainer.loss_fn, sim.state.params, mesh=mesh)

    # compile both rounds at once: each spends minutes in the TPU
    # compiler (rand_k's permutation sort), which overlaps across threads
    def compile_step(trainer):
        t0 = time.perf_counter()
        compiled = trainer.step.lower(sim.state, sim.x, sim.y).compile()
        return compiled, time.perf_counter() - t0

    with ThreadPoolExecutor(2) as pool:
        jobs = [pool.submit(compile_step, t) for t in (sim.trainer, sharded)]
        (ref_step, ref_compile_s), (sh_step, sh_compile_s) = [
            j.result() for j in jobs]

    def run(step):
        args = jax.device_put((sim.state, sim.x, sim.y),
                              step.input_shardings[0])
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(*args))
        return out, time.perf_counter() - t0

    (ref_state, ref_m), _ = run(ref_step)
    (st, m), sharded_s = run(sh_step)

    # placement: the vmapped round lives on device 0 alone; the sharded
    # round's outputs span all four chips, and every chip did its share
    ref_leaf = jax.tree.leaves(ref_state.params)[0]
    leaf = jax.tree.leaves(st.params)[0]
    if ref_leaf.sharding.device_set != {devices[0]}:
        raise AssertionError(f"vmapped round ran on "
                             f"{ref_leaf.sharding.device_set}")
    if leaf.sharding.device_set != set(devices):
        raise AssertionError(f"sharded round's params live on "
                             f"{leaf.sharding.device_set}, not all 4 chips")
    peaks = [peak_bytes(dv) for dv in devices]
    if None not in peaks and min(peaks[1:]) < 0.25 * max(peaks):
        raise AssertionError(f"uneven per-chip peak bytes {peaks}: the "
                             f"cohort did not spread over the mesh")

    flat = lambda p: np.concatenate(
        [np.asarray(x).ravel() for x in jax.tree.leaves(p)])
    p_ref, p_sh = flat(ref_state.params), flat(st.params)
    emit({"phase": "cohort", "mesh": {k: int(v)
                                      for k, v in mesh.shape.items()},
          "mesh_devices": [int(dv.id) for dv in mesh.devices.flat],
          "d": sim.d, "r": R,
          "vmapped_compile_s": ref_compile_s,
          "sharded_compile_s": sh_compile_s, "sharded_step_s": sharded_s,
          "max_abs_param_diff": float(np.max(np.abs(p_sh - p_ref))),
          "energy": [float(ref_m["energy"]), float(m["energy"])],
          "beta": [float(ref_m["beta"]), float(m["beta"])],
          "peak_bytes_in_use": peaks})
    np.testing.assert_allclose(p_sh, p_ref, atol=COHORT_ATOL,
                               rtol=COHORT_RTOL)
    np.testing.assert_allclose(float(m["energy"]), float(ref_m["energy"]),
                               rtol=COHORT_RTOL)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs only the cohort-sharded parity phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # libtpu otherwise writes its logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    dev = tpu_or_exit()

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    emit({"phase": "setup", "device_kind": dev.device_kind,
          "devices": len(jax.devices()), "jax": jax.__version__,
          "compile_cache": cache_dir})
    if args.chips == 4:
        cohort_phase(args.seed)
    else:
        d = main_phase(args.seed)
        parity_phase(d, args.seed)
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})


if __name__ == "__main__":
    main()
