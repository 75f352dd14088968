"""Readings the check's limits are set from, for one cell, in one process.

    python3 bench/calibrate.py --workload vgg11-cifar10.paper \
        --seeds 11,12,13 --control-seeds 11,12,13

For every seed in ``--seeds``: the program's first call from the seed
(the same trainer, compiled once, fed each seed's weights, data and
state) against the reference: the sound runs' readings, the lower end of
each limit. For every seed in ``--control-seeds``: the control, the
reference in bfloat16 put in the program's place, and the fault with half
of the cohort left out of the aggregate (the mean taken over the rest),
both against the float32 reference: the upper end. A state returned
unchanged needs no run: it reads 1 on ``change_gap``.

One JSON line per reading on standard output; the benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()

    import jax.numpy as jnp

    import compare
    import harness
    import reference

    spec = harness.load_spec(args.workload)
    harness.devices_or_exit(spec["cell"]["chips"])
    harness.enable_cache()
    config = spec["config"]
    fl = harness.fl_params(spec["traffic"])
    sizes = harness.leaf_sizes(config)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    cell = None
    order = seeds + [s for s in controls if s not in seeds]
    for seed in order:
        t0 = time.perf_counter()
        if cell is None:
            cell = harness.build(spec, seed)
        else:
            harness.reseed(cell, seed)
        rpc = cell["rounds_per_call"]
        args_ref = (cell["keys"], harness.net_of(config), config["model"], fl,
                    cell["cohort"], cell["ref_data"], rpc)
        readings = {}
        if seed in seeds:
            state0 = cell["state"]
            state, m = harness.run_call(cell, state0)
            prog = harness.snapshot(state0, state, m)
            del state, m, state0
            cell["state"] = None
        ref = reference.run(*args_ref)
        if seed in seeds:
            readings["program"] = compare.numbers(prog, ref, sizes, rpc)
        if seed in controls:
            readings["control_bf16"] = compare.numbers(
                reference.run(*args_ref, dtype=jnp.bfloat16), ref, sizes,
                rpc)
            readings["fault_half_cohort"] = compare.numbers(
                reference.run(*args_ref,
                              keep=fl["clients_per_round"] // 2),
                ref, sizes, rpc)
        for kind, nums in readings.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, "numbers": nums,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)


if __name__ == "__main__":
    main()
