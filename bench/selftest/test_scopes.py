"""The layer attribution of ``scopes.py`` and the four readers built on it.

On the recorded v5e trace (a program without layer names) the reduction
the existing metrics read is pinned, number for number, and the new
readers read nothing. On a synthetic trace, written in the profiler's
own file format, each op finds its layer and each idle gap where the
device and the host stood. At VGG width 1/8 the compiled round program
carries every scope the readers read.
"""
import re
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

import harness
import scopes
import tracing

FIXTURE = Path(__file__).parent / "fixtures" / "tiny.xplane.pb"
READERS = ("local_train_ms", "support_ms", "aircomp_ms", "bookkeeping_ms")
PATH = "jit(trainer_run)/while/body/closed_call/"


def test_fixture_reduction_is_unchanged():
    """What mfu, idle_share, support_sort_ms and transmit_roofline read."""
    red = tracing.reduce(ProfileData.from_file(str(FIXTURE)), 1)
    assert red["window_s"] == 0.012860639
    assert red["busy_s"] == 0.000287766
    assert len(red["ops"]) == 45
    assert sum(o["count"] for o in red["ops"]) == 135
    assert [(o["name"], round(o["seconds"] * 1e9)) for o in red["ops"][:3]
            ] == [("sort.12", 76070), ("sort", 59382), ("fusion", 57855)]
    gaps = sorted(round(g[1] * 1e9) for g in red["gaps"])
    assert (len(gaps), sum(gaps)) == (119, 12572873)
    assert gaps[-5:] == [775158, 926682, 3339676, 3447533, 3854091]


def test_fixture_gaps_keep_the_benchmark_words():
    space = scopes.load(FIXTURE)
    red = tracing.reduce(ProfileData.from_file(str(FIXTURE)), 1)
    ours = scopes.labelled_gaps(space, 1)
    assert [g[1] for g in ours] == [g[1] for g in red["gaps"]]
    assert [g[0].split("; ")[0] for g in ours] == [g[0] for g in red["gaps"]]
    assert all("; device " in g[0] for g in ours)
    # the program has no scopes: all its time is unscoped
    assert scopes.seconds_by_scope(space, 1) == {
        None: pytest.approx(sum(o["seconds"] for o in red["ops"]))}


# ---------------------------------------------------------- synthetic

STATS = {1: "round", 2: "bytes", 3: "tf_op"}
# (instruction text, op_name or None, start ns, end ns); the second
# program reuses an instruction name of the first, under another scope
OPS = [("%a.1 = f32[8]{0} gather(f32[8]{0} %x)", PATH + "pfels.cohort/gather",
        10, 20),
       ("%b.1 = f32[8]{0} multiply(f32[8]{0} %x)",
        PATH + "vmap(transpose(jvp(pfels.local_train)))/mul", 25, 50),
       ("%c.1 = u32[8]{0} sort(u32[8]{0} %x)",
        PATH + "pfels.support/jit(_shuffle)/sort", 55, 70),
       ("%a.1 = f32[8]{0} copy(f32[8]{0} %x)", None, 100, 110),
       ("%d.1 = f32[8]{0} add(f32[8]{0} %x)", PATH + "pfels.aircomp/add",
        115, 160)]
MODULES = [("jit_trainer_run(42)", 10, 70), ("jit_trainer_run(42)", 100, 160)]
ASYNC = [("%copy-start.2 = f32[8]{0} copy-start(f32[8]{0} %x)",
          PATH + "pfels.aircomp/copy", 108, 116)]
HOST = [("bench.window", 0, 200, {}), ("bench.run_call", 0, 5, {}),
        ("bench.block", 5, 90, {}), ("bench.run_call", 95, 100, {}),
        ("bench.block", 100, 200, {}),
        ("streamed.scatter", 60, 120, {"round": 3, "bytes": 64}),
        ("loader.cohort", 80, 90, {"round": 4})]


def _synthetic():
    """An XSpace as the profiler writes it: one host plane, one chip."""
    space = scopes._xspace()()

    def plane(name, lines):
        p = space.planes.add(name=name)
        for k, v in STATS.items():
            e = p.stat_metadata.add(key=k)
            e.value.id, e.value.name = k, v
        for line_name, events in lines:
            line = p.lines.add(name=line_name, timestamp_ns=0)
            for name, a, b, stats, meta_stats in events:
                mid = len(p.event_metadata) + 1
                e = p.event_metadata.add(key=mid)
                e.value.id, e.value.name = mid, name
                for k, v in meta_stats.items():
                    e.value.stats.add(metadata_id=k, str_value=v)
                ev = line.events.add(metadata_id=mid, offset_ps=a * 1000,
                                     duration_ps=(b - a) * 1000)
                for k, v in stats.items():
                    ev.stats.add(metadata_id=k, int64_value=v)

    plane("/host:CPU", [("python", [
        (n, a, b, {1 if k == "round" else 2: v for k, v in st.items()}, {})
        for n, a, b, st in HOST])])
    plane("/device:TPU:0", [
        ("XLA Modules", [(n, a, b, {}, {}) for n, a, b in MODULES]),
        ("XLA Ops", [(t, a, b, {}, {3: op + ":"} if op else {})
                     for t, op, a, b in OPS]),
        ("Async XLA Ops", [(t, a, b, {}, {3: op + ":"})
                           for t, op, a, b in ASYNC])])
    return space


def test_ops_find_their_layer():
    space = _synthetic()
    red = tracing.reduce(ProfileData.from_serialized_xspace(
        space.SerializeToString()), 1)
    by = scopes.seconds_by_scope(space, 1)
    assert by == pytest.approx({"pfels.cohort": 10e-9,
                                "pfels.local_train": 25e-9,
                                "pfels.support": 15e-9, None: 10e-9,
                                "pfels.aircomp": 45e-9})
    assert sum(by.values()) == pytest.approx(red["busy_s"])


def test_a_trace_that_disagrees_with_the_reduction_fails_loudly(
        tmp_path, monkeypatch):
    (tmp_path / "t.xplane.pb").write_bytes(_synthetic().SerializeToString())
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    ops = [{"name": "a.1", "seconds": 10e-9}, {"name": "b.1",
                                                "seconds": 200e-9}]
    with pytest.raises(RuntimeError, match="the reduction"):
        scopes.ms_per_round({"ops": ops, "chips": 1, "rounds": 1},
                            ("pfels.cohort",))
    with pytest.raises(ValueError, match="flat"):
        scopes.scope_of(PATH + "pfels.support/pfels.beta/mul")


def test_gaps_say_where_device_and_host_stood():
    gaps = scopes.labelled_gaps(_synthetic(), 1)
    assert [round(s * 1e9) for _, s in gaps] == [10, 5, 5, 30, 5, 40]
    labels = [g[0] for g in gaps]
    assert labels[0] == ("host waits for the call to finish; "
                         "device between programs")
    assert labels[1].endswith("; device in trainer_run after pfels.cohort "
                              "before pfels.local_train")
    assert labels[2].endswith("after pfels.local_train before "
                              "pfels.support")
    # the innermost of the program's host spans over the gap's middle
    assert labels[3] == ("host waits for the call to finish; device "
                         "between programs; host in loader.cohort round 4")
    assert labels[4].endswith("after None before pfels.aircomp; async "
                              "copy-start.2 pfels.aircomp; host in "
                              "streamed.scatter round 3")
    assert [g[1] for g in scopes.labelled_gaps(_synthetic(), 1, top=2)
            ] == pytest.approx([40e-9, 30e-9])


def test_host_span_stats():
    stats = scopes.host_span_stats(_synthetic())
    assert stats["streamed.scatter"] == {
        "count": 1, "total_ms": pytest.approx(60e-6),
        "median_ms": pytest.approx(60e-6), "max_ms": pytest.approx(60e-6),
        "max_round": 3, "bytes": 64}


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_where_no_scope_ran(name, tmp_path,
                                                 monkeypatch):
    read = tracing.load_reader(name)
    assert read({"scoped": {}, "rounds": 3}) is None
    assert read({"scoped": {None: 1.0}, "rounds": 3}) is None
    # from the trace file, as a traced run reads it: the fixture's program
    # has no scopes
    (tmp_path / "p").mkdir()
    (tmp_path / "p" / "t.xplane.pb").write_bytes(FIXTURE.read_bytes())
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    red = tracing.reduce(ProfileData.from_file(str(FIXTURE)), 1)
    assert read({"ops": red["ops"], "chips": 1, "rounds": 3}) is None


def test_readers_read_their_scopes(tmp_path, monkeypatch):
    space = _synthetic()
    (tmp_path / "t.xplane.pb").write_bytes(space.SerializeToString())
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    red = tracing.reduce(ProfileData.from_serialized_xspace(
        space.SerializeToString()), 1)
    ctx = {"ops": red["ops"], "chips": 1, "rounds": 2}
    got = {n: tracing.load_reader(n)(ctx) for n in READERS}
    assert got == pytest.approx({"local_train_ms": 12.5e-6,
                                 "support_ms": 7.5e-6,
                                 "aircomp_ms": 22.5e-6,
                                 "bookkeeping_ms": 5e-6})
    # with the unscoped time, the four sum to busy time per round
    assert sum(got.values()) + 1e3 * ctx["scoped"][None] / 2 == \
        pytest.approx(1e3 * red["busy_s"] / 2)


def test_round_program_carries_every_scope_the_readers_read():
    """At VGG width 1/8: a scope renamed in the program fails here rather
    than reading nothing on the chip."""
    spec = harness.load_spec("vgg11-cifar10.paper")
    program = dict(spec["config"]["program"],
                   config="repro.configs.paper_models:BENCH_CNN_CIFAR")
    spec["config"] = dict(spec["config"], program=program, model={
        "arch": "vgg", "in_channels": 3, "image_size": 16,
        "num_classes": 10, "width_mult": 0.125})
    spec["config"]["d"] = sum(harness.leaf_sizes(spec["config"]))
    spec["traffic"] = dict(spec["traffic"], num_clients=200,
                           samples_per_client=20)
    cell = harness.build(spec, 7)
    text = cell["trainer"].run_program(cell["rounds_per_call"]).lower(
        cell["state"], cell["x"], cell["y"]).compile().as_text()
    named = {scopes.scope_of(o)
             for o in re.findall(r'op_name="([^"]*)"', text)}
    wanted = {s for n in READERS
              for s in harness.load_module(
                  harness.BENCH / "metrics" / f"{n}.py").SCOPES}
    assert wanted == set(scopes.SCOPES) <= named
    jax.clear_caches()


def test_summary_per_round(tmp_path):
    """What ``python3 bench/scopes.py --rounds N`` prints."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_synthetic().SerializeToString())
    s = scopes.summary(path, 1, rounds=2)
    assert s["busy"] == pytest.approx(1e3 * 105e-9 / 2)
    assert sum(s["scoped"].values()) == pytest.approx(s["busy"])
    assert s["top_ops"]["None"] == [["a.1", pytest.approx(1e3 * 10e-9 / 2)]]
    assert s["top_ops"]["pfels.cohort"][0][0] == "a.1"
    assert len(s["gaps"]) == 6 and s["gaps"][0][1] == pytest.approx(40e-9)
    assert set(s["host_spans"]) == {"streamed.scatter", "loader.cohort"}
