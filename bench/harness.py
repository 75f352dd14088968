"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is data found by name from the cell's entry in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json`` and ``metrics/<metric>.py``. This module holds
only what every cell shares.

The program under test is ``repro.fl.Trainer``; the entry the window
drives is ``Trainer.run(state, x, y, rounds=rounds_per_call)``. Set-up
builds one trainer and its state from the seed and drives it through its
first call, whose rounds the reference follows; the window then continues
from that same state.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
from jax.flatten_util import ravel_pytree

import compare
import data
import reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the compile cache sits at one fixed path inside the checkout: the path is
# part of the cache key, so a directory that moves would never hit
CACHE_DIR = ROOT / ".bench_cache" / "jax"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# the program's local_train draws batches of this size; PFELSConfig does
# not reach it, so a traffic mix that asks for another is refused
PROGRAM_BATCH = 50


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------- spec

def load_spec(workload):
    """The cell's entry and everything it names, from files only."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads(
        (BENCH / "limits" / f"{workload}.json").read_text())
    if traffic["batch_size"] != PROGRAM_BATCH:
        raise SystemExit(f"traffic {cell['traffic']!r} asks for client "
                         f"batch {traffic['batch_size']}; the program's "
                         f"local_train only runs {PROGRAM_BATCH}")
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "limits": limits,
        "end_to_end": bench["end_to_end"], "per_layer": bench["per_layer"],
    }


def fl_params(traffic):
    """The round's parameters as the reference reads them."""
    ch = traffic["channel"]
    return {
        "num_clients": traffic["num_clients"],
        "clients_per_round": traffic["clients_per_round"],
        "local_steps": traffic["local_steps"],
        "batch_size": traffic["batch_size"],
        "local_lr": traffic["local_lr"], "momentum": traffic["momentum"],
        "clip": traffic["clip"],
        "compression_ratio": traffic["compression_ratio"],
        "epsilon": traffic["epsilon"],
        "gain_mean": ch["gain_mean"], "gain_clip": tuple(ch["gain_clip"]),
        "noise_std": ch["noise_std"],
        "snr_db_range": tuple(ch["snr_db_range"]),
    }


def seed_keys(seed):
    """Weights, data and trainer-init keys from a seed of up to 64 bits."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
    kw, kd, ki = jax.random.split(base, 3)
    return {"weights": kw, "data": kd, "init": ki}


# ------------------------------------------------------------------ device

def devices_or_exit(chips):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (devices[0] is {devs[0].platform!r});"
                     f" the benchmark does not fall back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_cache():
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Counts backend compiles and persistent-cache hits through JAX's
    monitoring listeners (a compile inside the window is a fault)."""

    def __init__(self):
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1


def peak_bytes(devs):
    """Peak device memory of the fullest chip: the allocator's peak of live
    arrays plus the peak it reserved for compiled programs' scratch, which
    ``peak_bytes_in_use`` alone leaves out."""
    def one(d):
        s = d.memory_stats() or {}
        return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
    return max(one(d) for d in devs)


# -------------------------------------------------------------------- cell

@functools.cache
def load_module(path):
    """A module of the benchmark's own, by file (metric readers, models)."""
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def net_of(config):
    """The configuration's plain reference model, ``models/<name>.py``."""
    return load_module(BENCH / "models" / f"{config['reference']}.py")


def program_attr(ref):
    """``module:attribute`` of the program, as a configuration names it."""
    module, attr = ref.split(":")
    return getattr(importlib.import_module(module), attr)


def build(spec, seed):
    """The program's trainer and the cell's inputs for ``seed``."""
    from repro.configs.base import ChannelConfig, PFELSConfig
    from repro.fl import Trainer

    config, traffic = spec["config"], spec["traffic"]
    prog = config["program"]
    mcfg = program_attr(prog["config"])
    init_fn, loss_fn = program_attr(prog["init"]), program_attr(prog["loss"])
    ch = traffic["channel"]
    cfg = PFELSConfig(
        num_clients=traffic["num_clients"],
        clients_per_round=traffic["clients_per_round"],
        local_steps=traffic["local_steps"], local_lr=traffic["local_lr"],
        clip=traffic["clip"], momentum=traffic["momentum"],
        compression_ratio=traffic["compression_ratio"],
        epsilon=traffic["epsilon"], rounds=traffic["rounds_per_call"],
        algorithm="pfels", compressor=traffic["compressor"],
        bank_backend=traffic["bank_backend"],
        error_feedback=traffic["error_feedback"],
        use_fused_kernel=traffic["use_fused_kernel"],
        channel=ChannelConfig(
            model=ch["model"], gain_mean=ch["gain_mean"],
            gain_clip=tuple(ch["gain_clip"]), noise_std=ch["noise_std"],
            snr_db_range=tuple(ch["snr_db_range"])))
    init = jax.jit(functools.partial(init_fn, cfg=mcfg))
    cell = {"spec": spec, "init": init,
            "rounds_per_call": traffic["rounds_per_call"]}
    reseed(cell, seed, lambda params: Trainer(
        cfg, lambda p, b: loss_fn(p, mcfg, b), params))
    return cell


def reseed(cell, seed, make_trainer=None):
    """Weights, data and the initial state for ``seed``, made on the
    device, each in one jitted call. The trainer (and so its compiled
    programs) is kept unless ``make_trainer`` builds it."""
    from repro.data.loader import ClientFnSource

    config, traffic = cell["spec"]["config"], cell["spec"]["traffic"]
    keys = seed_keys(seed)
    params = cell["init"](keys["weights"])
    d = sum(int(a.size) for a in jax.tree.leaves(params))
    if d != config["d"]:
        raise SystemExit(f"{config['program']['config']} builds d={d}, "
                         f"the configuration states d={config['d']}")
    per_client = traffic["samples_per_client"]
    kwargs = dict(num_classes=config["model"]["num_classes"],
                  image_shape=input_shape(config["model"]))
    n = traffic["num_clients"]
    if traffic["data"] == "resident":
        x, y = data.federated(keys["data"], n_clients=n,
                              per_client=per_client, **kwargs)
        cell["cohort"], cell["ref_data"] = _resident_cohort, (x, y)
    else:
        source = data.population(keys["data"], **kwargs)
        gen = functools.partial(data.cohort, per_client=per_client)
        x, y = ClientFnSource(functools.partial(gen, source), n), None
        cell["cohort"], cell["ref_data"] = gen, source
    if make_trainer is not None:
        cell["trainer"] = make_trainer(params)
    cell.update(x=x, y=y, d=d, keys=keys,
                state=cell["trainer"].init(keys["init"], params=params))


def _resident_cohort(arrays, sel):
    x, y = arrays
    return x[sel], y[sel]


def dispatch(cell, state):
    """One call of the window: ``rounds_per_call`` rounds of
    ``Trainer.run``, returned as soon as the program has them queued."""
    with jax.profiler.TraceAnnotation("bench.run_call"):
        return cell["trainer"].run(state, cell["x"], cell["y"],
                                   rounds=cell["rounds_per_call"])


def wait(out):
    """Block until a call's results exist; returns how many of its rounds
    gave a non-finite training loss."""
    with jax.profiler.TraceAnnotation("bench.block"):
        jax.block_until_ready(out)
    return int(np.sum(~np.isfinite(np.asarray(out[1]["train_loss"]))))


def run_call(cell, state):
    """One call, waited for."""
    out = dispatch(cell, state)
    wait(out)
    return out


def snapshot(state0, state, metrics):
    """Host copies of what the check compares, taken before the window."""
    bank = state.bank
    return {
        "flat0": np.asarray(ravel_pytree(state0.params)[0], np.float32),
        "flat": np.asarray(ravel_pytree(state.params)[0], np.float32),
        "delta_hat": np.asarray(state.prev_delta, np.float32),
        "metrics": {k: np.asarray(v, np.float64) for k, v in metrics.items()},
        "eps_sum": float(state.ledger.eps_sum),
        "spends": int(state.ledger.spends),
        "lanes": np.asarray(bank.lanes), "counts": np.asarray(bank.counts),
    }


def round_program_holds_kernel(ir_dir):
    """Whether the largest program lowered so far (the round program the
    first call compiled or loaded) holds the compiled Pallas kernel, so an
    interpret-mode fallback cannot pass."""
    mods = sorted(Path(ir_dir).glob("*.mlir"), key=lambda p: p.stat().st_size)
    return bool(mods) and "tpu_custom_call" in mods[-1].read_text()


def main_run(args, t_process):
    """One run from the command line: the cell named by ``--workload`` on
    this machine's chips."""
    spec = load_spec(args.workload)
    devs = devices_or_exit(spec["cell"]["chips"])
    enable_cache()
    return measure(spec, args.seed, args.seconds, args.trace, devs,
                   t_process)


def measure(spec, seed, seconds, trace, devs, t_process,
            require_kernel=True):
    """Set-up, the window, the check; returns the result dict."""
    t_start = time.perf_counter()
    log = CompileLog()
    ir_dir = ROOT / ".bench_cache" / "ir"
    shutil.rmtree(ir_dir, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", str(ir_dir))
    cell = build(spec, seed)
    t_built = time.perf_counter()
    state0 = cell["state"]
    state, m = run_call(cell, state0)
    t_called = time.perf_counter()
    jax.config.update("jax_dump_ir_to", "")
    first = snapshot(state0, state, m)
    kernel = round_program_holds_kernel(ir_dir)
    compiles_setup = log.compiles
    peak_setup = peak_bytes(devs)

    rpc = cell["rounds_per_call"]
    tracer = None
    if trace:
        import tracing
        tracer = tracing.Tracer(ROOT / ".bench_cache" / "trace")
        tracer.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    # closed loop, as a simulation drives it: the next call is queued
    # before the last one is waited for, so the chip is never left waiting
    # on the host between calls; the window ends when the last call is done
    rounds = calls = failed = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        out = dispatch(cell, state)
        while True:
            calls += 1
            rounds += rpc
            if time.perf_counter() - t0 >= seconds:
                break
            nxt = dispatch(cell, out[0])
            failed += wait(out)
            out = nxt
        failed += wait(out)
    window_s = time.perf_counter() - t0
    if tracer is not None:
        tracer.stop()
    if log.compiles != compiles_setup:
        raise RuntimeError(f"{log.compiles - compiles_setup} compile(s) "
                           f"inside the measured window")
    peak = peak_bytes(devs)
    del state, m, out
    cell["state"] = state0 = None

    config = spec["config"]
    ref = reference.run(cell["keys"], net_of(config), config["model"],
                        fl_params(spec["traffic"]), cell["cohort"],
                        cell["ref_data"], rpc)
    checks = compare.check(first, ref, leaf_sizes(config),
                           spec["limits"], rpc)
    correct = ((kernel or not require_kernel)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": rounds,
              "failed": failed}
    if trace:
        metrics, extra, breakdown = tracer.per_layer(spec, rounds, devs)
        device.update(extra)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = {"rounds_per_s": rounds / window_s,
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]}, device=device)
    result["info"] = {"calls": calls, "window_s": window_s,
                      "setup_split_s": {
                          "process_to_build": t_start - t_process,
                          "weights_data_trainer": t_built - t_start,
                          "first_call": t_called - t_built,
                          "snapshot_and_kernel_check": t0 - t_called},
                      "peak_setup_bytes": int(peak_setup),
                      "compiles_setup": compiles_setup,
                      "compile_s": log.seconds, "cache_hits": log.cache_hits,
                      "kernel_in_round_program": kernel,
                      "memory_stats": devs[0].memory_stats()}
    result["checks"] = checks
    return result


def input_shape(model):
    return (model["in_channels"], model["image_size"], model["image_size"])


def leaf_sizes(config):
    """Sizes of the parameter leaves in flattening order."""
    shapes = jax.eval_shape(functools.partial(
        net_of(config).init_params, model=config["model"]),
        jax.random.PRNGKey(0))
    return [int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)]
