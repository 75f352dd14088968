import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, record roofline terms.

Loop-corrected costs: XLA's cost_analysis counts each while-loop body ONCE,
so a scanned 32-layer stack or a 64-block flash-attention loop is
undercounted. We therefore derive FLOPs / bytes / collective-bytes from our
own HLO cost model (repro.launch.hlo_cost) which walks the compiled module's
call graph and multiplies each computation by its enclosing while-loop trip
counts (validated against analytic counts in tests/test_hlo_cost.py). The
raw cost_analysis() numbers are recorded alongside for reference.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import PFELSConfig
from repro.launch import inputs as I
from repro.launch import steps as S
from repro.launch.hlo_analysis import (collective_bytes, model_flops,
                                       roofline_terms)
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.sharding.rules import tree_shardings


def _param_shardings(cfg, mesh):
    with jax.set_mesh(mesh):
        shapes = T.init_shapes(cfg)
        logical = T.logical_axes(cfg)
    return shapes, tree_shardings(mesh, logical, shapes)


def _with_sharding(shapes, shardings):
    return jax.tree.map(
        lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        shapes, shardings)


def _reduce_rep(cfg, rep: int):
    kw = dict(n_repeat=rep, n_layers=rep * len(cfg.block_pattern))
    if cfg.is_encoder_decoder:
        kw["n_encoder_layers"] = rep
    return dataclasses.replace(cfg, **kw)


def lower_and_compile(cfg, shape, mesh, pfels, *, donate=True):
    """Build + lower + compile the step for (cfg, shape) on mesh.
    Returns (compiled, tokens_processed)."""
    param_shapes, param_sh = _param_shardings(cfg, mesh)
    params_in = _with_sharding(param_shapes, param_sh)
    n_params = sum(x.size for x in jax.tree.leaves(param_shapes))

    n_pods = mesh.shape.get("pod", 1)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            batch = I.train_batch_specs(cfg, shape, mesh)
            step = S.make_pfels_train_step(cfg, pfels, n_params, mesh)
            key = jax.ShapeDtypeStruct((2,), jnp.uint32)
            if n_pods > 1:
                # explicit client dim of model replicas, sharded over 'pod'
                c_shapes = S.clientize_shapes(param_shapes, n_pods)
                c_logical = S.clientize_logical(T.logical_axes(cfg), n_pods)
                params_in = _with_sharding(
                    c_shapes, tree_shardings(mesh, c_logical, c_shapes))
            jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(params_in, batch, key)
            tokens = shape.global_batch * shape.seq_len
        elif shape.kind == "prefill":
            batch = I.prefill_batch_specs(cfg, shape, mesh)
            step = S.make_prefill_step(cfg)
            lowered = jax.jit(step).lower(params_in, batch)
            tokens = shape.global_batch * shape.seq_len
        else:  # decode
            window = I.long_context_window(cfg, shape)
            spec = I.decode_specs(cfg, shape, mesh, window=window)
            step = S.make_serve_step(cfg, window=window)
            kwargs = {}
            if cfg.is_encoder_decoder:
                kwargs["enc_out"] = spec["enc_out"]
            jitted = jax.jit(step, donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(params_in, spec["token"], spec["caches"],
                                   **kwargs)
            tokens = shape.global_batch
        compiled = lowered.compile()
    return compiled, tokens, n_params




# §Perf-validated optimized variants (EXPERIMENTS.md §Perf): applied by
# `--perf`. Baseline tables always use the plain configs.
PERF_VARIANTS = {
    # dense-family train shapes: activation collectives >> weight
    # collectives at <= ~4B params -> pure FSDP + larger flash block
    ("phi3-mini-3.8b", "train_4k"): dict(parallelism="fsdp",
                                         attn_block_kv=1024),
    ("mamba2-130m", "train_4k"): dict(parallelism="fsdp"),
    # memory-bound 32k prefill: quarter the flash accumulator round-trips
    ("qwen2.5-14b", "prefill_32k"): dict(attn_block_kv=2048),
}


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               pfels: PFELSConfig = None, verbose: bool = True,
               analyze_loops: bool = True, perf: bool = False):
    cfg = get_config(arch)
    if perf and (arch, shape_name) in PERF_VARIANTS:
        cfg = dataclasses.replace(cfg, **PERF_VARIANTS[(arch, shape_name)])
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    # fleet of 1000 edge sites (paper §8.1); the pods of this mesh are the
    # sites participating this round. grad_accum bounds activation memory
    # for the widest models (see EXPERIMENTS.md §Perf).
    accum = 4 if cfg.d_model >= 8192 else (
        2 if (cfg.d_model >= 5120 or cfg.moe is not None) else 1)
    if cfg.family == "hybrid":
        accum = max(accum, 2)   # SSD chunk intermediates (80 heads)
    if multi_pod:
        if cfg.moe is not None:
            # per-pod MoE dispatch buffers under the client vmap
            accum = 8 if cfg.moe.num_experts >= 64 else 4
        elif cfg.d_model >= 8192:
            accum = 8
    # local_steps=1 for the baseline tables (tau > 1 is supported — see
    # tests/test_system.py and the tau datapoint in EXPERIMENTS.md §Perf)
    pfels = pfels or PFELSConfig(compression_ratio=0.3, epsilon=1.5,
                                 num_clients=1000, local_steps=1,
                                 clients_per_round=mesh.shape.get("pod", 1),
                                 grad_accum=accum)

    import contextlib
    from repro.sharding.rules import PURE_FSDP, logical_overrides
    par_ctx = (logical_overrides(PURE_FSDP) if cfg.parallelism == "fsdp"
               else contextlib.nullcontext())

    t0 = time.time()
    with par_ctx:
        compiled, tokens, n_params = lower_and_compile(cfg, shape, mesh,
                                                       pfels)
    t1 = time.time()
    mem = compiled.memory_analysis()
    raw_cost = compiled.cost_analysis()
    raw_coll = collective_bytes(compiled.as_text())

    if analyze_loops:
        corrected = analyze_hlo(compiled.as_text())
        corrected.setdefault("flops", 0.0)
        corrected.setdefault("bytes", 0.0)
        corrected.setdefault("coll", 0.0)
    else:
        corrected = {"flops": float(raw_cost.get("flops", 0.0)),
                     "bytes": float(raw_cost.get("bytes accessed", 0.0)),
                     "coll": float(raw_coll["total"])}
    t2 = time.time()

    terms = roofline_terms({"flops": corrected["flops"],
                            "bytes accessed": corrected["bytes"]},
                           {"total": corrected["coll"]}, n_chips)

    n_active = cfg.active_param_count_estimate()
    mf = model_flops(n_active, tokens,
                     "train" if shape.kind == "train" else "serve")
    mf_per_device = mf / n_chips
    useful = (mf_per_device / terms["hlo_flops_per_device"]
              if terms["hlo_flops_per_device"] else 0.0)

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "n_chips": n_chips,
        "n_params": int(n_params),
        "step_kind": shape.kind,
        "compile_s": round(t1 - t0, 2),
        "analysis_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
        },
        "raw_cost": {"flops": float(raw_cost.get("flops", 0.0)),
                     "bytes": float(raw_cost.get("bytes accessed", 0.0)),
                     "coll": raw_coll["total"],
                     "collective_counts": raw_coll["counts"]},
        "corrected_cost": {k: float(v) for k, v in corrected.items()},
        "roofline": terms,
        "model_flops_per_device": mf_per_device,
        "useful_flops_ratio": useful,
    }
    if verbose:
        gb = 1 << 30
        print(f"[{arch} x {shape_name} x {'2x16x16' if multi_pod else '16x16'}]"
              f" compile={record['compile_s']}s"
              f" mem/dev={record['memory']['peak_bytes_per_device']/gb:.2f}GiB"
              f" t_comp={terms['t_compute_s']*1e3:.2f}ms"
              f" t_mem={terms['t_memory_s']*1e3:.2f}ms"
              f" t_coll={terms['t_collective_s']*1e3:.2f}ms"
              f" dom={terms['dominant']}"
              f" useful={useful:.2f}", flush=True)
    return record


def dryrun_cohort(*, clients_per_round: int = 32, verbose: bool = True):
    """Lower + compile the sharded FL round (client_sharding="cohort",
    DESIGN.md §7) through the Trainer API on a cohort mesh carved from the
    forced host devices: sanity-checks that the shard_map round lowers at
    pod scale and records its compile/memory numbers like the model
    dry-runs."""
    from repro.configs.paper_models import BENCH_MLP
    from repro.data import make_federated_classification
    from repro.fl import Trainer
    from repro.launch.mesh import make_cohort_mesh
    from repro.models import cnn

    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_MLP)
    x, y, _, _ = make_federated_classification(
        key, n_clients=1000, per_client=30, num_classes=10,
        image_shape=(1, 8, 8))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b)
    cfg = PFELSConfig(num_clients=1000, clients_per_round=clients_per_round,
                      local_steps=1, client_sharding="cohort")
    mesh = make_cohort_mesh(cfg.clients_per_round)
    shards = mesh.shape["pod"] * mesh.shape["data"]
    trainer = Trainer(cfg, loss_fn, params, mesh=mesh)
    d = trainer.d
    state = trainer.init(jax.random.PRNGKey(1))

    t0 = time.time()
    lowered = trainer.step.lower(state, x, y)
    compiled = lowered.compile()
    t1 = time.time()
    mem = compiled.memory_analysis()
    record = {
        "kind": "cohort_round", "d": int(d),
        "clients_per_round": cfg.clients_per_round,
        "mesh": dict(mesh.shape), "shards": shards,
        "compile_s": round(t1 - t0, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
        },
    }
    if verbose:
        gb = 1 << 30
        print(f"[cohort round r={cfg.clients_per_round} x "
              f"{dict(mesh.shape)}] compile={record['compile_s']}s"
              f" mem/dev="
              f"{record['memory']['peak_bytes_per_device']/gb:.3f}GiB"
              f" shards={shards}", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cohort", action="store_true",
                    help="dry-run the sharded FL round (client_sharding="
                         "'cohort') instead of a model x shape combination")
    ap.add_argument("--cohort-r", type=int, default=32,
                    help="clients per round for --cohort")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-loop-analysis", action="store_true")
    ap.add_argument("--perf", action="store_true",
                    help="apply EXPERIMENTS.md §Perf optimized variants")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    if args.cohort:
        rec = dryrun_cohort(clients_per_round=args.cohort_r)
        path = os.path.join(args.out, f"cohort_round__r{args.cohort_r}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print("cohort dry-run OK")
        return
    jobs = []
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                jobs.append((a, s))
    else:
        jobs.append((args.arch, args.shape))

    failures = []
    for arch, shape in jobs:
        try:
            rec = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                             analyze_loops=not args.no_loop_analysis,
                             perf=args.perf)
            tag = "multipod" if args.multi_pod else "pod"
            if args.perf:
                tag += "_perf"
            path = os.path.join(args.out, f"{arch}__{shape}__{tag}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=2)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print(f"dry-run OK: {len(jobs)} combination(s)")


if __name__ == "__main__":
    main()
