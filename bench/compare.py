"""The comparison that decides ``correct``.

The program's first call (``rounds_per_call`` rounds from the seed) against
the plain reference's same rounds. Each number has a limit of its own in
``limits/<workload>.json``; how each limit was set is in ``PERF.md``.

- ``loss_gap``, ``update_norm_gap``, ``beta_gap``, ``energy_gap``: the
  largest relative gap over the call's rounds of the per-round training
  loss, mean local-update norm, Theorem-5 beta and transmit energy; the
  same names ending in ``_first``: the gap in the first round alone,
  before any difference in the parameters carries over.
- ``ledger_gap``: relative gap of the ledger's summed epsilon.
- ``delta_gap``: the last round's reconstructed update Delta_hat (the
  update the server applies, read from ``TrainState.prev_delta``), and
  ``change_gap``: the parameters' change over the call. Both by the worst
  leaf: the gap between the program's and the reference's norm of a
  leaf, over the larger of the reference's norm of that leaf and of the
  median leaf. Leaves whose reference change is under a thousandth of the
  median leaf's are left out of both.
- ``support_mismatch``: coordinates where one side's Delta_hat is zero and
  the other's is not (the rand_k support; exact).
- ``bank_mismatch``: clients whose bank lane key or participation count
  differs, plus rounds the ledger did not charge (exact).
"""
from __future__ import annotations

import numpy as np

PER_ROUND = {"loss_gap": "train_loss", "update_norm_gap": "update_norm",
             "beta_gap": "beta", "energy_gap": "energy"}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), np.finfo(np.float32).tiny)


def leaf_norms(flat, sizes):
    bounds = np.cumsum([0] + list(sizes))
    return np.array([np.linalg.norm(flat[a:b].astype(np.float64))
                     for a, b in zip(bounds[:-1], bounds[1:])])


def worst_leaf(prog, ref, sizes, keep):
    """max over kept leaves of |‖prog_l‖ - ‖ref_l‖| / max(‖ref_l‖, median)."""
    n_p, n_r = leaf_norms(prog, sizes)[keep], leaf_norms(ref, sizes)[keep]
    return float(np.max(np.abs(n_p - n_r)
                        / np.maximum(n_r, np.median(n_r))))


def numbers(prog, ref, sizes, rounds):
    """Every compared number, by name."""
    out = {}
    for name, k in PER_ROUND.items():
        gaps = _rel(prog["metrics"][k][:rounds], ref["metrics"][k][:rounds])
        out[name] = float(np.max(gaps))
        out[name + "_first"] = float(gaps[0])
    out["ledger_gap"] = float(_rel(prog["eps_sum"], ref["eps_sum"]))
    change_p = prog["flat"] - prog["flat0"]
    change_r = ref["flat"] - ref["flat0"]
    n_r = leaf_norms(change_r, sizes)
    keep = n_r >= 1e-3 * np.median(n_r)
    out["delta_gap"] = worst_leaf(prog["delta_hat"], ref["delta_hat"],
                                  sizes, keep)
    out["change_gap"] = worst_leaf(change_p, change_r, sizes, keep)
    out["support_mismatch"] = float(np.count_nonzero(
        (prog["delta_hat"] != 0) != (ref["delta_hat"] != 0)))
    lanes_p = np.asarray(prog["lanes"]).reshape(len(prog["counts"]), -1)
    lanes_r = np.asarray(ref["lanes"]).reshape(len(ref["counts"]), -1)
    out["bank_mismatch"] = float(
        np.count_nonzero(np.any(lanes_p != lanes_r, axis=1)
                         | (np.asarray(prog["counts"]) != ref["counts"]))
        + abs(prog.get("spends", rounds) - rounds))
    return out


def check(prog, ref, sizes, limits, rounds):
    """``{name: {"value", "limit"}}`` for every number the cell's limits
    name; a number without a limit in the cell is not compared."""
    got = numbers(prog, ref, sizes, rounds)
    return {k: {"value": got[k], "limit": float(v)}
            for k, v in limits.items()}
