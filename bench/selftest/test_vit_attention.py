"""``vit_attention_ms`` on a synthetic trace written in the profiler's own
file format: it counts the leaf ops whose op_name carries ``vit.attention``
(forward and backward, inside ``pfels.local_train``), clipped to the
window, and reads nothing where no op carries it."""
import pytest

import scopes
import tracing

LT = "jit(trainer_run)/while/body/pfels.local_train/"
# (instruction text, op_name, start ns, end ns)
OPS = [
    ("%q.1 = f32[8]{0} dot(f32[8]{0} %x)",
     LT + "vmap(jvp(vit.attention))/dot_general", 10, 30),
    ("%s.1 = f32[8]{0} exp(f32[8]{0} %x)",
     LT + "vmap(transpose(jvp(vit.attention)))/exp", 40, 45),
    ("%m.1 = f32[8]{0} dot(f32[8]{0} %x)",
     LT + "vmap(jvp(vit.mlp))/dot_general", 50, 80),
    ("%a.1 = f32[8]{0} add(f32[8]{0} %x)", "jit(trainer_run)/pfels.aircomp/add",
     85, 95),
    # half outside the window: only the inside half counts
    ("%q.2 = f32[8]{0} dot(f32[8]{0} %x)",
     LT + "vmap(jvp(vit.attention))/dot_general", 190, 210),
]


def _trace(ops):
    space = scopes._xspace()()
    host = space.planes.add(name="/host:CPU")
    e = host.event_metadata.add(key=1)
    e.value.id, e.value.name = 1, "bench.window"
    host.lines.add(name="python", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=0, duration_ps=200_000)
    dev = space.planes.add(name="/device:TPU:0")
    st = dev.stat_metadata.add(key=3)
    st.value.id, st.value.name = 3, "tf_op"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (text, op, a, b) in enumerate(ops, start=1):
        md = dev.event_metadata.add(key=i)
        md.value.id, md.value.name = i, text
        md.value.stats.add(metadata_id=3, str_value=op + ":")
        line.events.add(metadata_id=i, offset_ps=a * 1000,
                        duration_ps=(b - a) * 1000)
    return space


def _read(tmp_path, monkeypatch, ops, rounds=2):
    (tmp_path / "t.xplane.pb").write_bytes(_trace(ops).SerializeToString())
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    return tracing.load_reader("vit_attention_ms")({"chips": 1,
                                                   "rounds": rounds})


def test_reads_the_attention_ops_in_the_window(tmp_path, monkeypatch):
    # 20 + 5 + 10 ns over 2 rounds
    assert _read(tmp_path, monkeypatch, OPS) == pytest.approx(35e-6 / 2)


def test_reads_nothing_without_attention(tmp_path, monkeypatch):
    ops = [o for o in OPS if "vit.attention" not in o[1]]
    assert _read(tmp_path, monkeypatch, ops) is None


def test_attention_ops_keep_their_round_layer():
    """The ops it reads still count to ``pfels.local_train``."""
    assert {scopes.scope_of(op) for _, op, _, _ in OPS
            if "vit.attention" in op} == {"pfels.local_train"}
