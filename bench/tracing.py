"""The traced run: record a profiler trace of the window, reduce it to
device-op intervals, and hand them to the per-layer metric readers.

The reduction keeps, for each chip used, the operations on the device
plane's "XLA Ops" line that fall inside the benchmark's own
``bench.window`` host span, and the host spans the benchmark writes
around each ``Trainer.run`` call (``bench.run_call``) and its wait
(``bench.block``). Busy time is the union of the device intervals;
idle gaps are labelled by the host span they fall in.
"""
from __future__ import annotations

import functools
import json
import re
import shutil
from pathlib import Path

import jax
import numpy as np

import flops
import harness

BENCH = Path(__file__).resolve().parent
HOST_SPANS = ("bench.window", "bench.run_call", "bench.block")


# ---------------------------------------------------------------- reduce

_SHAPE = re.compile(r"\b(pred|[sfu]\d+|bf16)\[([\d,]*)\]")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}


def _close(text, i):
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += text[j] in "([{"
        depth -= text[j] in ")]}"
        if depth == 0:
            return j + 1
    return len(text)


def parse_op(text):
    """(name, opcode, result shapes, operand shapes) of an HLO instruction
    as the TPU trace names its events: ``%name = shape opcode(operands),
    attributes``. Shapes are (dtype, dims) pairs."""
    m = re.match(r"%?(\S+) = ", text)
    if not m:
        return text, "", [], []
    i = m.end()
    j = _close(text, i) if text[i] == "(" else text.index(" ", i)
    k = text.index("(", j)
    opcode = text[j:k].strip()
    end = _close(text, k)

    def shapes(s):
        return [(dt, [int(x) for x in dims.split(",") if x])
                for dt, dims in _SHAPE.findall(s)]
    return m.group(1), opcode, shapes(text[i:j]), shapes(text[k:end])


def nbytes(shapes):
    return sum(DTYPE_BYTES[dt] * int(np.prod(dims)) for dt, dims in shapes)


def op_kind(text, opcode):
    """'sort', 'kernel' (a compiled Pallas kernel) or 'other'."""
    if opcode == "custom-call" and '"tpu_custom_call"' in text:
        return "kernel"
    return "sort" if opcode == "sort" else "other"


def _leaves(events):
    """Drop events that enclose a later event of the same line (a loop
    whose body ops are listed too), so no time counts twice."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[1] >= e[2]]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(profile, n_devices):
    """Window, busy time, per-op totals and idle gaps from a
    ``ProfileData``. Busy time is the union of the leaf operations'
    intervals (a loop op that encloses its body is not itself work)."""
    spans = {k: [] for k in HOST_SPANS}
    device_ops = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < n_devices:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops.append([(ev.name, ev.start_ns, ev.end_ns)
                                       for ev in line.events])
    if not spans["bench.window"]:
        raise RuntimeError("the trace holds no bench.window span")
    if len(device_ops) != n_devices:
        raise RuntimeError(f"the trace holds 'XLA Ops' of {len(device_ops)}"
                           f" chips, the run used {n_devices}")
    w0, w1 = spans["bench.window"][0]
    busy, totals, info, leaves0 = [], {}, {}, None
    for evs in device_ops:
        evs = _leaves([(n, max(a, w0), min(b, w1)) for n, a, b in evs
                       if b > w0 and a < w1])
        leaves0 = evs if leaves0 is None else leaves0
        busy.append(sum(b - a for a, b in _union([(a, b)
                                                   for _, a, b in evs])))
        for text, a, b in evs:
            if text not in info:
                name, opcode, res, ops = parse_op(text)
                info[text] = {"name": name, "kind": op_kind(text, opcode),
                              "bytes": nbytes(res) + nbytes(ops),
                              "elements": max([int(np.prod(d))
                                               for _, d in ops] or [0])}
            t = totals.setdefault(text, [0, 0])
            t[0] += b - a
            t[1] += 1
    nd = len(device_ops)
    ops = [dict(info[text], seconds=t * 1e-9 / nd, count=c / nd)
           for text, (t, c) in totals.items()]
    ops.sort(key=lambda o: -o["seconds"])
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": float(np.mean(busy)) * 1e-9, "ops": ops,
            "gaps": _idle_gaps(leaves0, w0, w1, spans)}


def _idle_gaps(evs, w0, w1, spans):
    """Idle stretches of one device, each labelled by the innermost host
    span of the benchmark that covers its middle."""
    merged = _union([(a, b) for _, a, b in evs])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    labels = (("bench.block", "host waits for the call to finish"),
              ("bench.run_call", "host inside Trainer.run"),
              ("bench.window", "host between calls"))
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        label = next((text for span, text in labels
                      if any(s <= mid <= e for s, e in spans[span])),
                     "outside the benchmark's spans")
        gaps.append((label, (b - a) * 1e-9))
    return gaps


def breakdown(red):
    """The ``breakdown`` of the result line: the ten device ops that took
    most time and the ten longest idle gaps, in seconds."""
    return {"device_ops": [[o["name"], o["seconds"]]
                           for o in red["ops"][:10]],
            "idle_gaps": [list(g) for g in sorted(
                red["gaps"], key=lambda g: -g[1])[:10]]}


# ---------------------------------------------------------------- tracer

def load_reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read


class Tracer:
    def __init__(self, out_dir):
        self.dir = Path(out_dir)

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self):
        jax.profiler.stop_trace()

    def per_layer(self, spec, rounds, devs):
        """(per-layer metrics, device busy/window, breakdown)."""
        from jax.profiler import ProfileData
        pb = max(self.dir.glob("**/*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
        red = reduce(ProfileData.from_file(str(pb)), len(devs))
        ctx = context(spec, red, rounds, devs)
        metrics = {}
        for m in spec["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        return (metrics, {"busy_s": red["busy_s"],
                          "window_s": red["window_s"]}, breakdown(red))


def context(spec, red, rounds, devs):
    """What the metric readers read."""
    config, traffic = spec["config"], spec["traffic"]
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peaks for device {kind!r} in peaks.json")
    model, net = config["model"], harness.net_of(config)
    params = jax.eval_shape(functools.partial(net.init_params, model=model),
                            jax.random.PRNGKey(0))
    fwd = functools.partial(net.forward, model=model)
    return {
        "rounds": rounds, "window_s": red["window_s"],
        "busy_s": red["busy_s"], "ops": red["ops"], "chips": len(devs),
        "peaks": peaks[kind],
        "train_flops_per_sample": flops.train_flops_per_sample(
            lambda p, x: fwd(p, x=x), params, harness.input_shape(model)),
        "samples_per_round": (traffic["clients_per_round"]
                              * traffic["local_steps"]
                              * traffic["batch_size"]),
    }
