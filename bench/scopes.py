"""Where the round's device time and idle gaps belong, by layer.

The program runs each layer of its round under one flat
``jax.named_scope`` (``SCOPES``); the compiled ops carry it in their
``op_name`` metadata, and the profiler writes that into the trace as the
``tf_op`` stat of each "XLA Ops" event. ``ProfileData`` does not expose
event-metadata stats, so this module decodes the traced run's
``.xplane.pb`` itself, with the few fields of the ``XSpace`` schema that
it needs. It gives:

- ``ms_per_round(ctx, scopes)``: the per-layer readers' number, device
  milliseconds per round of the ops under the named scopes;
- ``labelled_gaps(space, n)``: each idle stretch of the window with the
  benchmark's own label, then where the device stood (between programs,
  or inside one between two scopes) and the innermost host span of the
  program (``streamed.*``, ``loader.*``) over its middle;
- ``python3 bench/scopes.py [trace_dir] --rounds N``: both, and the host
  spans' durations, as one JSON object for the trace a ``--trace 1`` run
  leaves in ``.bench_cache/trace``.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import json
import re
import statistics
from pathlib import Path

BENCH = Path(__file__).resolve().parent
# where harness.measure's tracer writes the traced run's profile
TRACE_DIR = BENCH.parent / ".bench_cache" / "trace"
SCOPES = ("pfels.cohort", "pfels.channel", "pfels.support", "pfels.beta",
          "pfels.local_train", "pfels.aircomp", "pfels.ledger",
          "pfels.bank", "pfels.server")
_SCOPE = re.compile(r"\bpfels\.[a-z_]+")
HOST_PREFIXES = ("streamed.", "loader.")


def scope_of(op_name):
    """The ``pfels.*`` scope in an op_name, or None. A path component
    may sit inside transforms, as ``vmap(transpose(jvp(pfels.x)))``."""
    found = set(_SCOPE.findall(op_name or ""))
    if len(found) > 1:
        raise ValueError(f"op_name {op_name!r} carries scopes "
                         f"{sorted(found)}; the program's are flat")
    return found.pop() if found else None


# ------------------------------------------------------------ decoding

@functools.cache
def _xspace():
    """The message class of the ``XSpace`` schema subset read here: the
    field numbers of tsl/profiler/protobuf/xplane.proto. Maps are read as
    repeated key/value entries, which is their wire form."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")
    types = {"i": F.TYPE_INT64, "u": F.TYPE_UINT64, "d": F.TYPE_DOUBLE,
             "s": F.TYPE_STRING}

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            repeated = kind.startswith("*")
            kind = kind.lstrip("*")
            f = m.field.add(name=fname, number=number, label=(
                F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL))
            if kind in types:
                f.type = types[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xspace.{kind}"

    message("XStat", ("metadata_id", 1, "i"), ("double_value", 2, "d"),
            ("uint64_value", 3, "u"), ("int64_value", 4, "i"),
            ("str_value", 5, "s"), ("ref_value", 7, "u"))
    # the value fields form one oneof, so HasField tells which is set
    stat = fd.message_type[-1]
    stat.oneof_decl.add(name="value")
    for f in stat.field[1:]:
        f.oneof_index = 0
    message("XEvent", ("metadata_id", 1, "i"), ("offset_ps", 2, "i"),
            ("duration_ps", 3, "i"), ("stats", 4, "*XStat"))
    message("XLine", ("name", 2, "s"), ("timestamp_ns", 3, "i"),
            ("events", 4, "*XEvent"))
    message("XEventMetadata", ("id", 1, "i"), ("name", 2, "s"),
            ("stats", 5, "*XStat"))
    message("XStatMetadata", ("id", 1, "i"), ("name", 2, "s"))
    message("EventMetadataEntry", ("key", 1, "i"),
            ("value", 2, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, "i"),
            ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, "s"), ("lines", 3, "*XLine"),
            ("event_metadata", 4, "*EventMetadataEntry"),
            ("stat_metadata", 5, "*StatMetadataEntry"))
    message("XSpace", ("planes", 1, "*XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def load(path):
    return _xspace().FromString(Path(path).read_bytes())


def newest_trace(trace_dir=None):
    """The profile file a traced run wrote last, as ``Tracer`` finds it."""
    trace_dir = TRACE_DIR if trace_dir is None else trace_dir
    files = list(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(files, key=lambda p: p.stat().st_mtime)


def _stat_value(stat, names):
    if stat.HasField("str_value"):
        return stat.str_value
    if stat.HasField("ref_value"):
        return names.get(stat.ref_value)
    for field in ("int64_value", "uint64_value", "double_value"):
        if stat.HasField(field):
            return getattr(stat, field)
    return None


def _events(plane, line, op_names=False, wanted=None):
    """(metadata name, start_ns, end_ns, stats) of a line's events whose
    name ``wanted(name)`` accepts (all without it), on the clock
    ``ProfileData`` gives them; with ``op_names`` the op_name of each
    event's metadata (its ``tf_op`` stat) in place of the stats."""
    names = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {e.key: e.value for e in plane.event_metadata}
    ops = {}
    for ev in line.events:
        md = meta[ev.metadata_id]
        if wanted is not None and not wanted(md.name):
            continue
        a = line.timestamp_ns + ev.offset_ps // 1000
        if op_names:
            if ev.metadata_id not in ops:
                ops[ev.metadata_id] = next(
                    (str(_stat_value(st, names) or "") for st in md.stats
                     if names.get(st.metadata_id) == "tf_op"), "")
            extra = ops[ev.metadata_id]
        else:
            extra = {names.get(st.metadata_id): _stat_value(st, names)
                     for st in ev.stats}
        yield md.name, a, a + ev.duration_ps // 1000, extra


def host_events(space, wanted):
    """(name, start_ns, end_ns, stats) of the host events whose name
    ``wanted(name)`` accepts, from every ``/host:`` plane."""
    out = []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += _events(plane, line, wanted=wanted)
    return out


def window(space):
    spans = host_events(space, lambda n: n == "bench.window")
    if not spans:
        raise RuntimeError("the trace holds no bench.window span")
    return spans[0][1], spans[0][2]


def device_lines(space, n_devices, line_name):
    """{chip: [(event name, start_ns, end_ns, op_name)]} of one line of
    each device plane."""
    out = {}
    for plane in space.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < n_devices:
            out[int(m.group(1))] = [
                e for line in plane.lines if line.name == line_name
                for e in _events(plane, line, op_names=True)]
    return out


# -------------------------------------------------------- attribution

def device_leaves(space, n_devices):
    """{chip: [(scope, start_ns, end_ns, event text)]}: each chip's "XLA
    Ops" inside the window, clipped to it, without the events that enclose
    others (``tracing``'s rule, so the durations sum as its ``ops`` do).
    Each event carries its own op_name, so instruction names that two
    programs of the window share need no matching."""
    import tracing

    w0, w1 = window(space)
    return {chip: tracing._leaves([
        (scope_of(op), max(a, w0), min(b, w1), text)
        for text, a, b, op in evs if b > w0 and a < w1])
        for chip, evs in device_lines(space, n_devices, "XLA Ops").items()}


def seconds_by_scope(space, n_devices):
    """{scope or None: device seconds in the window, averaged over the
    chips}."""
    leaves = device_leaves(space, n_devices)
    out = {}
    for evs in leaves.values():
        for scope, a, b, _ in evs:
            out[scope] = out.get(scope, 0.0) + (b - a) * 1e-9 / len(leaves)
    return out


def ms_per_round(ctx, scopes):
    """Device ms per round of the ops under ``scopes``, or None where
    none ran. The attribution is made once per traced run, from the
    newest trace, and kept in the readers' context as ``scoped``: empty
    where no op carries a scope (a program without them). Raises where
    the attributed time and the reduction's ``ops`` disagree by over 1%,
    so the readers never read a silent zero."""
    if "scoped" not in ctx:
        by = seconds_by_scope(load(newest_trace()), ctx["chips"])
        total = sum(op["seconds"] for op in ctx["ops"])
        if abs(sum(by.values()) - total) > 0.01 * total:
            raise RuntimeError(
                f"the trace's scopes hold {sum(by.values()):.6g} s of "
                f"device ops, the reduction {total:.6g} s")
        ctx["scoped"] = by if any(by) else {}
    t = sum(ctx["scoped"].get(s, 0.0) for s in scopes)
    return 1e3 * t / ctx["rounds"] if t > 0 else None


# --------------------------------------------------------------- gaps

def _module(name):
    """``jit_trainer_run(1234)`` -> ``trainer_run``."""
    return re.sub(r"^jit_|\(\d+\)$", "", name)


def labelled_gaps(space, n_devices, top=None):
    """[(label, seconds)] of chip 0's ``top`` longest idle stretches in
    the window (all, in time order, without ``top``): the benchmark's own
    label (``tracing``), then ``; device between programs`` or ``; device
    in <program> after <scope> before <scope>``, then ``; async <op>
    <scope>`` for the longest asynchronous op (a copy or transfer the
    program waits on, which is not busy time) over the gap's middle, then
    ``; host in <span> round <i>`` for the innermost ``streamed.*`` or
    ``loader.*`` span over it."""
    import tracing

    w0, w1 = window(space)
    leaves = device_leaves(space, n_devices)[0]
    spans = {k: [(a, b) for _, a, b, _ in host_events(
        space, lambda n, k=k: n == k)] for k in tracing.HOST_SPANS}
    base = tracing._idle_gaps([(None, a, b) for _, a, b, _ in leaves],
                              w0, w1, spans)
    edges = [w0] + [x for iv in tracing._union(
        [(a, b) for _, a, b, _ in leaves]) for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    order = sorted(range(len(gaps)), key=lambda i: -base[i][1])[:top] \
        if top else range(len(gaps))
    modules = [(_module(name), a, b) for name, a, b, _ in device_lines(
        space, n_devices, "XLA Modules")[0]]
    asyncs = device_lines(space, n_devices, "Async XLA Ops")[0]
    host = host_events(space, lambda n: n.startswith(HOST_PREFIXES))
    starts = [a for _, a, _, _ in leaves]
    out = []
    for i in order:
        (label, seconds), (a, b) = base[i], gaps[i]
        inside = [m for m, s, e in modules if s <= a and b <= e]
        if inside:
            # the union's gap ends where a leaf starts; every leaf before
            # it ends at or before the gap's start
            j = bisect.bisect_left(starts, b)
            label += (f"; device in {inside[0]} after "
                      f"{leaves[j - 1][0] if j else None} before "
                      f"{leaves[j][0] if j < len(leaves) else None}")
        else:
            label += "; device between programs"
        mid = (a + b) / 2
        over = [(e - s, text, op) for text, s, e, op in asyncs
                if s <= mid <= e]
        if over:
            _, text, op = max(over, key=lambda o: o[0])
            label += f"; async {tracing.parse_op(text)[0]} {scope_of(op)}"
        over = [(e - s, n, st) for n, s, e, st in host if s <= mid <= e]
        if over:
            _, name, st = min(over, key=lambda o: o[0])
            label += f"; host in {name} round {st.get('round')}"
        out.append((label, seconds))
    return out


def host_span_stats(space):
    """{span name: count, total/median/max ms, and the round of the
    longest} over the program's host spans in the window."""
    w0, w1 = window(space)
    by = {}
    for name, a, b, st in host_events(
            space, lambda n: n.startswith(HOST_PREFIXES)):
        if w0 <= a and b <= w1:
            by.setdefault(name, []).append(((b - a) * 1e-6, st))
    return {name: {"count": len(v),
                   "total_ms": sum(d for d, _ in v),
                   "median_ms": statistics.median(d for d, _ in v),
                   "max_ms": max(d for d, _ in v),
                   "max_round": max(v, key=lambda x: x[0])[1].get("round"),
                   "bytes": sum(int(st.get("bytes") or 0) for _, st in v)}
            for name, v in sorted(by.items())}


def summary(path, n_devices=1, rounds=None, top=10):
    """What ``main`` prints: device time by scope (ms per round, or
    seconds without ``rounds``) with each scope's longest ops, the
    longest labelled gaps, and the host spans."""
    from jax.profiler import ProfileData

    import tracing

    red = tracing.reduce(ProfileData.from_file(str(path)), n_devices)
    space = load(path)
    per = 1e3 / rounds if rounds else 1.0
    by_op = {}
    for evs in device_leaves(space, n_devices).values():
        for scope, a, b, text in evs:
            key = (scope, tracing.parse_op(text)[0])
            by_op[key] = by_op.get(key, 0.0) + (b - a) * 1e-9 / n_devices
    scoped = {}
    for (scope, _), t in by_op.items():
        scoped[scope] = scoped.get(scope, 0.0) + t
    return {
        "unit": "ms per round" if rounds else "s",
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "busy": red["busy_s"] * per,
        "ops": sum(o["seconds"] for o in red["ops"]) * per,
        "scoped": {str(k): v * per for k, v in sorted(
            scoped.items(), key=lambda kv: -kv[1])},
        "top_ops": {str(k): [[n, t * per] for (s, n), t in sorted(
            by_op.items(), key=lambda kv: -kv[1]) if s == k][:top]
            for k in scoped},
        "gaps": [list(g) for g in labelled_gaps(space, n_devices, top)],
        "host_spans": host_span_stats(space),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir", nargs="?", default=str(TRACE_DIR))
    ap.add_argument("--rounds", type=int, default=None,
                    help="the traced run's rounds (its 'attempted')")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    print(json.dumps(summary(newest_trace(args.trace_dir), args.chips,
                             args.rounds)))


if __name__ == "__main__":
    main()
