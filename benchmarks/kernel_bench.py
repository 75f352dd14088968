"""Kernel micro-benchmarks: Pallas (interpret) vs jnp oracle us/call.

Wall-times on CPU are NOT the perf claim (interpret mode runs the kernel
body in Python); this benchmark validates the call path and records the
oracle cost — the TPU perf story lives in the roofline analysis.

``--emit BENCH_6.json`` writes the schema-versioned perf trajectory
(DESIGN.md §12): every row carries its us/call plus — for the PINNED
fused fast-path rows — the us/call of its unfused-oracle counterpart,
so ``tools/check_bench.py`` can gate on fused/oracle RATIOS (machine
speed cancels between the committed trajectory and a fresh CI run).

Forces an 8-device host platform (before jax initializes) so the sharded
cohort round (round_sharded vs round_vmapped rows) actually splits over
devices on CPU. ``benchmarks/run.sh`` is the tuned launcher.
"""
from __future__ import annotations

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import argparse
import fnmatch
import json
import time

import jax
import jax.numpy as jnp

from repro.core import aggregation, randk
from repro.kernels.clip_norm.ops import clip_flat
from repro.kernels.flash_attn.ops import attention
from repro.kernels.pfels_transmit.ops import fused_transmit
from repro.kernels.randk_gather.ops import gather_rows
from repro.kernels.ssd_scan.ops import ssd_scan

# bump when the emitted JSON layout changes; tools/check_bench.py refuses
# to compare trajectories across schema versions
SCHEMA_VERSION = 1

# untimed calls burned before the clock starts (the first triggers
# compilation; extras settle allocator/cache state) — ``--warmup`` flag
DEFAULT_WARMUP = 1


def _time(f, *args, reps=5, warmup=None):
    """us/call of ``f(*args)``: ``warmup`` untimed calls (floored at 1 so
    compilation never lands in the timed region), then ``reps`` timed
    calls on the monotonic high-resolution ``time.perf_counter`` clock
    (``time.time`` is wall-clock: coarse on some platforms and steppable
    by NTP mid-measurement)."""
    w = DEFAULT_WARMUP if warmup is None else warmup
    for _ in range(max(1, w)):
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def bench_pfels_transmit(key, rows, *, r=16, d=128 * 512):
    """Fused transmit pipeline (clip->rand_k->scale->AirComp) vs the
    unfused vmapped-ops path, whole (r, d) batch."""
    k = d // 4
    updates = jax.random.normal(key, (r, d))
    gains = jnp.full((r,), 0.05)
    idx = randk.sample_indices(key, d, k)
    kw = dict(d=d, sigma0=0.3, r=r)

    us = _time(jax.jit(lambda u: aggregation.aircomp_aggregate(
        u, idx, gains, 0.8, key, **kw)), updates)
    rows.append(("pfels_transmit_unfused", us, f"r={r},d={d},k={k}"))
    for use_kernel, tag in ((False, "fused_ref"), (True, "fused_pallas")):
        us = _time(jax.jit(lambda u: fused_transmit(
            u, idx, gains, 0.8, key, use_kernel=use_kernel, **kw)), updates)
        rows.append((f"pfels_transmit_{tag}", us, f"r={r},d={d},k={k}"))


def _fl_problem(cfg):
    """One shared FL benchmark problem (BENCH_MLP on synthetic federated
    data) so every round-driver row measures the same thing."""
    from jax.flatten_util import ravel_pytree

    from repro.configs.paper_models import BENCH_MLP
    from repro.data import make_federated_classification
    from repro.models import cnn

    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    d = ravel_pytree(params)[0].shape[0]
    x, y, _, _ = make_federated_classification(
        key, n_clients=30, per_client=30, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    return params, d, (x, y), loss_fn


def bench_round_drivers(rows, *, t_rounds=8):
    """T rounds, two drivers: a python loop over the jitted
    ``Trainer.step`` (one dispatch per round), fed the run's own round
    keys, and ``Trainer.run`` (one ``lax.scan`` program)."""
    from repro.configs import PFELSConfig
    from repro.fl import Trainer
    from repro.fl.api import replace

    cfg = PFELSConfig(num_clients=30, clients_per_round=8, local_steps=3,
                      rounds=t_rounds)
    params, d, (x, y), loss_fn = _fl_problem(cfg)
    trainer = Trainer(cfg, loss_fn, params)
    state = replace(trainer.init(jax.random.PRNGKey(1)),
                    key=jax.random.PRNGKey(2))
    keys = jax.random.split(state.key, t_rounds)

    def loop():
        s = state
        for k in keys:
            s, _ = trainer.step(replace(s, key=k), x, y)
        return s.prev_delta

    us = _time(loop, reps=3)
    rows.append(("rounds_python_loop", us, f"T={t_rounds},d={d}"))

    us = _time(lambda: trainer.run(state, x, y,
                                   rounds=t_rounds)[0].prev_delta, reps=3)
    rows.append(("rounds_trainer_run", us,
                 f"T={t_rounds},d={d},ledger=in-graph"))


def bench_bank_backends(rows, *, t_rounds=6):
    """ClientBank backends (DESIGN.md §10), same cfg/key/data: the
    resident scan (dense (n, d) bank in the carry) vs the streamed
    host-driven cohort loop (host bank + prefetched (r, ...) slices).
    The two are bit-identical; this row prices the host round-trips the
    streamed backend pays for device memory independent of n."""
    import dataclasses

    import numpy as np
    from jax.flatten_util import ravel_pytree

    from repro.configs import PFELSConfig
    from repro.configs.paper_models import BENCH_MLP
    from repro.data import make_federated_classification
    from repro.fl import Trainer
    from repro.fl.api import replace
    from repro.models import cnn

    cfg = PFELSConfig(num_clients=200, clients_per_round=8, local_steps=3,
                      error_feedback=True, rounds=t_rounds)
    params = cnn.init_cnn(jax.random.PRNGKey(0), BENCH_MLP)
    d = ravel_pytree(params)[0].shape[0]
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    x, y, _, _ = make_federated_classification(
        jax.random.PRNGKey(0), n_clients=cfg.num_clients, per_client=30,
        num_classes=10, image_shape=(1, 8, 8))

    for backend in ("resident", "streamed"):
        cfg_b = dataclasses.replace(cfg, bank_backend=backend)
        trainer = Trainer(cfg_b, loss_fn, params)
        state = replace(trainer.init(jax.random.PRNGKey(1)),
                        key=jax.random.PRNGKey(2))
        xs = np.asarray(x) if backend == "streamed" else x
        ys = np.asarray(y) if backend == "streamed" else y
        us = _time(lambda: jax.block_until_ready(
            trainer.run(state, xs, ys, rounds=t_rounds)[0].prev_delta),
            reps=3)
        rows.append((f"bank_{backend}", us,
                     f"T={t_rounds},n={cfg.num_clients},"
                     f"r={cfg.clients_per_round},d={d},ef=on"))


def bench_channel_models(rows, *, t_rounds=4):
    """Channel-registry scenarios (DESIGN.md §11), same cfg/key/data via
    Trainer.run: the seed block_fading MAC vs the 8-antenna MRC receiver
    (per-antenna draws + combining + the sqrt(M) noise plumbing) vs
    Gauss–Markov fading (an (n,) latent carried through the scan) — what
    opening the scenario axis costs on the round hot path."""
    import dataclasses

    from repro.configs import ChannelConfig, PFELSConfig
    from repro.fl import Trainer
    from repro.fl.api import replace

    cfg = PFELSConfig(num_clients=30, clients_per_round=8, local_steps=3,
                      rounds=t_rounds)
    params, d, (x, y), loss_fn = _fl_problem(cfg)

    for chan, tag in ((ChannelConfig(), "block_fading"),
                      (ChannelConfig(model="mimo_mrc", num_antennas=8),
                       "mimo_mrc[M=8]"),
                      (ChannelConfig(model="markov_fading",
                                     markov_rho=0.9), "markov[rho=.9]")):
        cfg_c = dataclasses.replace(cfg, channel=chan)
        trainer = Trainer(cfg_c, loss_fn, params)
        state = replace(trainer.init(jax.random.PRNGKey(1)),
                        key=jax.random.PRNGKey(2))
        us = _time(lambda: trainer.run(state, x, y,
                                       rounds=t_rounds)[0].prev_delta,
                   reps=3)
        rows.append((f"chan_{tag}", us,
                     f"T={t_rounds},r={cfg.clients_per_round},d={d}"))


def bench_sharded_round(rows):
    """Sharded cohort round (shard_map over ('pod','data'), DESIGN.md §7)
    vs the vmapped single-device round, same cfg and key, via
    Trainer.step."""
    import dataclasses

    from repro.configs import PFELSConfig
    from repro.fl import Trainer
    from repro.fl.api import replace
    from repro.launch.mesh import make_cohort_mesh

    cfg = PFELSConfig(num_clients=30, clients_per_round=8, local_steps=3)
    params, d, (x, y), loss_fn = _fl_problem(cfg)
    mesh = make_cohort_mesh(cfg.clients_per_round)
    shards = mesh.shape["pod"] * mesh.shape["data"]

    def _bench(cfg_i, mesh_i):
        trainer = Trainer(cfg_i, loss_fn, params, mesh=mesh_i)
        state = replace(trainer.init(jax.random.PRNGKey(1)),
                        key=jax.random.PRNGKey(2))
        return _time(lambda: trainer.step(state, x, y)[0].prev_delta,
                     reps=3)

    us = _bench(cfg, None)
    rows.append(("round_vmapped", us, f"r={cfg.clients_per_round},d={d}"))

    cfg_s = dataclasses.replace(cfg, client_sharding="cohort")
    us = _bench(cfg_s, mesh)
    rows.append(("round_sharded", us,
                 f"r={cfg.clients_per_round},d={d},shards={shards}"))


# the PR-6 fast-path matrix: every registered channel scenario ×
# execution path gets a fused row and its unfused-oracle twin
_SCENARIOS = (
    ("block_fading", {}),
    ("markov", dict(model="markov_fading", markov_rho=0.9)),
    ("mimo_mrc", dict(model="mimo_mrc", num_antennas=4)),
    ("dropout", dict(model="dropout", dropout_prob=0.4)),
)

# pinned fast-path row -> its unfused-oracle row. Pinned rows are the
# regression surface of the committed trajectory: tools/check_bench.py
# fails if a fresh run's (pinned us)/(oracle us) ratio regresses beyond
# tolerance vs the committed one, or if a pinned row disappears.
PINNED = {
    "pfels_transmit_fused_pallas": "pfels_transmit_unfused",
    **{f"scenario_{tag}_{path}_fused": f"scenario_{tag}_{path}_unfused"
       for tag, _ in _SCENARIOS for path in ("vmapped", "sharded")},
    # ISSUE 7: the compressor hooks (Support.active column, per-client
    # encode, EF residual) must not erode the fused fast path — the
    # carry-compressor row and the encode-hook row each gate their
    # fused/oracle ratio
    "compressor_top_k_ef_fused": "compressor_top_k_ef_unfused",
    "compressor_stoch_quant_fused": "compressor_stoch_quant_unfused",
}

# per-row gate tolerance stamped into the emitted trajectory (overrides
# check_bench's global --tolerance): whole-round Trainer.step timings on a
# shared CI runner jitter far more than isolated kernels, and the
# interpret-mode Pallas row runs its tile loop in Python — both want a
# looser leash. A genuine 2x slowdown (ratio +100%) still fails every row.
ROW_TOLERANCE = {
    "scenario_*": 0.75,
    "compressor_*": 0.75,
    "pfels_transmit_fused_pallas": 0.5,
}


def bench_scenarios(rows):
    """One Trainer.step round per channel model × execution path
    (vmapped / sharded-psum) × {fused default, unfused oracle} — the
    fast-path matrix ISSUE 6 makes the default. The fused rows are the
    pinned perf surface of BENCH_6.json."""
    import dataclasses

    from repro.configs import ChannelConfig, PFELSConfig
    from repro.fl import Trainer
    from repro.fl.api import replace
    from repro.launch.mesh import make_cohort_mesh

    cfg0 = PFELSConfig(num_clients=30, clients_per_round=8, local_steps=2)
    params, d, (x, y), loss_fn = _fl_problem(cfg0)
    mesh = make_cohort_mesh(cfg0.clients_per_round)

    for tag, chan_kw in _SCENARIOS:
        chan = ChannelConfig(**chan_kw)
        for path in ("vmapped", "sharded"):
            for fused in (True, False):
                cfg = dataclasses.replace(
                    cfg0, channel=chan, use_fused_kernel=fused,
                    client_sharding="cohort" if path == "sharded"
                    else "none")
                trainer = Trainer(cfg, loss_fn, params,
                                  mesh=mesh if path == "sharded" else None)
                state = replace(trainer.init(jax.random.PRNGKey(1)),
                                key=jax.random.PRNGKey(2))
                us = _time(lambda: trainer.step(state, x, y)[0].prev_delta,
                           reps=2)
                mode = "fused" if fused else "unfused"
                rows.append((f"scenario_{tag}_{path}_{mode}", us,
                             f"r={cfg0.clients_per_round},d={d},"
                             f"chan={chan.model}"))


def bench_compressors(rows):
    """One Trainer.step round per compressor-registry entry (DESIGN.md
    §13) × {fused default, unfused oracle} on the shared FL problem — what
    the Support.active column (threshold), the per-client encode hook
    (stoch_quant), and the carry/EF residual path (top_k_ef) cost on the
    round hot path relative to the seed rand_k round. The top_k_ef and
    stoch_quant fused rows are pinned in the committed trajectory."""
    import dataclasses

    from repro.configs import CompressionSchedule, PFELSConfig
    from repro.fl import Trainer
    from repro.fl.api import replace

    cfg0 = PFELSConfig(num_clients=30, clients_per_round=8, local_steps=2)
    params, d, (x, y), loss_fn = _fl_problem(cfg0)

    variants = (
        ("rand_k", dict(compressor="rand_k")),
        ("top_k_ef", dict(compressor="top_k_ef", transmit_clip=0.5)),
        ("threshold", dict(compressor="threshold", threshold_frac=0.3)),
        ("stoch_quant", dict(compressor="stoch_quant", quant_bits=6,
                             transmit_clip=0.5)),
        ("sched_linear", dict(schedule=CompressionSchedule(
            mode="linear", k_end_ratio=0.5))),
    )
    for tag, kw in variants:
        for fused in (True, False):
            cfg = dataclasses.replace(cfg0, use_fused_kernel=fused, **kw)
            trainer = Trainer(cfg, loss_fn, params)
            state = replace(trainer.init(jax.random.PRNGKey(1)),
                            key=jax.random.PRNGKey(2))
            us = _time(lambda: trainer.step(state, x, y)[0].prev_delta,
                       reps=2)
            mode = "fused" if fused else "unfused"
            rows.append((f"compressor_{tag}_{mode}", us,
                         f"r={cfg0.clients_per_round},d={d}"))


def bench_micro(key, rows):
    """Single-op Pallas-vs-ref rows (gather, clip, scan, attention)."""
    d = 128 * 2048
    delta = jax.random.normal(key, (d,))
    idx = jax.random.permutation(key, d // 128)[: d // 128 // 4]
    for use_kernel, tag in ((False, "ref"), (True, "pallas_interp")):
        us = _time(lambda: gather_rows(delta, idx, 1.5,
                                       use_kernel=use_kernel))
        rows.append((f"randk_gather_{tag}", us, f"d={d}"))

    x = 3 * jax.random.normal(key, (d,))
    for use_kernel, tag in ((False, "ref"), (True, "pallas_interp")):
        us = _time(lambda: clip_flat(x, 1.0, use_kernel=use_kernel))
        rows.append((f"clip_norm_{tag}", us, f"d={d}"))

    b, s, h, p, n = 2, 512, 4, 64, 64
    ks = jax.random.split(key, 5)
    xs = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (b, s, n)) / 8
    cm = jax.random.normal(ks[4], (b, s, n)) / 8
    for use_kernel, tag in ((False, "ref"), (True, "pallas_interp")):
        us = _time(lambda: ssd_scan(xs, dt, a, bm, cm, chunk=128,
                                    use_kernel=use_kernel), reps=2)
        rows.append((f"ssd_scan_{tag}", us, f"b{b}s{s}h{h}p{p}n{n}"))

    qf = jax.random.normal(key, (1, 512, 8, 64))
    kf = jax.random.normal(key, (1, 512, 2, 64))
    for use_kernel, tag in ((False, "ref"), (True, "pallas_interp")):
        us = _time(lambda: attention(qf, kf, kf, use_kernel=use_kernel),
                   reps=2)
        rows.append((f"flash_attn_{tag}", us, "b1s512h8kv2d64"))


def emit(rows, path):
    """Write the schema-versioned trajectory JSON. Every pinned row must
    have its oracle row in the same run (the gate compares ratios) —
    emitting a partial ``--only`` run that splits a pinned/oracle pair is
    an error, not a silently-gapped trajectory."""
    by_name = {name: us for name, us, _ in rows}
    out = []
    for name, us, cfgstr in rows:
        oracle = PINNED.get(name)
        if oracle is not None and oracle not in by_name:
            raise ValueError(
                f"pinned row {name!r} emitted without its oracle row "
                f"{oracle!r}; widen --only or drop --emit")
        row = {"op": name, "config": cfgstr,
               "us_per_call": round(us, 2),
               "oracle_us_per_call": (round(by_name[oracle], 2)
                                      if oracle else None),
               "pinned": name in PINNED}
        if name in PINNED:
            for pat, tol in ROW_TOLERANCE.items():
                if fnmatch.fnmatch(name, pat):
                    row["tolerance"] = tol
                    break
        out.append(row)
    doc = {"schema_version": SCHEMA_VERSION,
           "meta": {"jax": jax.__version__,
                    "device_count": len(jax.devices()),
                    "platform": jax.devices()[0].platform},
           "rows": out}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(out)} rows -> {path}", flush=True)


def run(only=None):
    key = jax.random.PRNGKey(0)
    rows = []
    groups = (
        ("micro", lambda: bench_micro(key, rows)),
        ("pfels_transmit", lambda: bench_pfels_transmit(key, rows)),
        ("rounds", lambda: bench_round_drivers(rows)),
        ("bank", lambda: bench_bank_backends(rows)),
        ("channels", lambda: bench_channel_models(rows)),
        ("sharded", lambda: bench_sharded_round(rows)),
        ("scenarios", lambda: bench_scenarios(rows)),
        ("compressors", lambda: bench_compressors(rows)),
    )
    for name, fn in groups:
        if only and not any(fnmatch.fnmatch(name, p) for p in only):
            continue
        fn()

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    return rows


def main(argv=None):
    global DEFAULT_WARMUP
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="also write the schema-versioned trajectory JSON "
                         "(e.g. benchmarks/BENCH_6.json)")
    ap.add_argument("--warmup", type=int, default=None,
                    help=f"untimed warmup calls per row (default "
                         f"{DEFAULT_WARMUP}; floored at 1 so compile "
                         f"never pollutes the timed region)")
    ap.add_argument("--only", default=None,
                    help="comma-separated fnmatch pattern(s) of bench "
                         "groups to run (micro, pfels_transmit, rounds, "
                         "bank, channels, sharded, scenarios, "
                         "compressors)")
    args = ap.parse_args(argv)
    if args.warmup is not None:
        DEFAULT_WARMUP = args.warmup
    rows = run(only=args.only.split(",") if args.only else None)
    if args.emit:
        emit(rows, args.emit)
    return 0


if __name__ == "__main__":
    main()
