"""Roofline-term extraction from the compiled dry-run artifact.

compute  = HLO_FLOPs(per device) / peak_FLOP/s
memory   = HLO_bytes(per device) / HBM_bw
collective = per-device collective bytes (ring model per op kind) / link_bw

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (system prompt).
"""
from __future__ import annotations

import re
from typing import Dict

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes moved over ICI, ring-model per collective kind."""
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        res_bytes = _shape_bytes(m.group(1))
        kind = m.group(2)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                g = int(gi.group(2))
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-reduce":
            moved = 2.0 * frac * res_bytes
        elif kind == "all-gather":
            moved = frac * res_bytes
        elif kind == "reduce-scatter":
            moved = frac * res_bytes * g      # input = result * g
        elif kind == "all-to-all":
            moved = frac * res_bytes
        else:  # collective-permute
            moved = float(res_bytes)
        out[kind] += moved
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


def roofline_terms(cost: Dict, coll: Dict, n_chips: int) -> Dict:
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_coll = coll["total"] / LINK_BW
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    return {
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll["total"],
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "n_chips": n_chips,
    }


def model_flops(n_active_params: int, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D for a train step (fwd+bwd), 2*N*D for inference."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active_params * tokens
