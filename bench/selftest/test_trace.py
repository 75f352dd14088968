"""The trace reduction, on a small trace recorded on a TPU v5e.

``fixtures/tiny.xplane.pb`` holds three calls of one jitted step: a
permutation of 32,768 keys (JAX sorts random bits for it) and the fused
transmit kernel over a (8, 32768) f32 batch, each call inside the
benchmark's own ``bench.run_call`` and ``bench.block`` host spans, all in
one ``bench.window``, with 2 ms of host sleep between calls.
"""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import tracing

FIXTURE = Path(__file__).parent / "fixtures" / "tiny.xplane.pb"
R, D = 8, 8 * 4096


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return tracing.reduce(ProfileData.from_file(str(FIXTURE)), 1)


def test_window_and_busy(red):
    assert 0 < red["busy_s"] < red["window_s"]
    # three 2 ms host sleeps at least lie between the device's work
    assert red["window_s"] - red["busy_s"] >= 0.006


def test_kernel_and_sorts_found(red):
    kernel = [o for o in red["ops"] if o["kind"] == "kernel"]
    assert [o["count"] for o in kernel] == [3.0]
    # the (r, d) updates, the dense mask and noise columns, (r, 1) and
    # (r, M) coefficient columns in; y (1, d) and the energy (1, 1) out
    assert kernel[0]["elements"] == R * D
    assert kernel[0]["bytes"] == 4 * (R * D + 2 * D + 3 * R + D + 1)
    sorts = [o for o in red["ops"] if o["kind"] == "sort"]
    assert sorts and all(o["count"] == 3.0 for o in sorts)


def test_gaps_are_labelled(red):
    labels = {g[0] for g in red["gaps"]}
    assert "host between calls" in labels
    bd = tracing.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_parse_op():
    text = ('%fused_combine.10 = (f32[1,9224192]{1,0:T(1,128)}, '
            'f32[1,1]{1,0:T(1,128)}) custom-call(f32[32,1]{1,0:T(8,128)} '
            '%a, bf16[32,9224192]{1,0} %b), custom_call_target='
            '"tpu_custom_call"')
    name, opcode, res, ops = tracing.parse_op(text)
    assert (name, opcode) == ("fused_combine.10", "custom-call")
    assert res == [("f32", [1, 9224192]), ("f32", [1, 1])]
    assert ops == [("f32", [32, 1]), ("bf16", [32, 9224192])]
    assert tracing.op_kind(text, opcode) == "kernel"
    assert tracing.nbytes(ops) == 4 * 32 + 2 * 32 * 9224192
    sort = ("%sort.4 = (u32[8]{0}, s32[8]{0}) sort(u32[8]{0} %x, s32[8]{0}"
            " %y), dimensions={0}")
    assert tracing.parse_op(sort)[1] == "sort"


def test_enclosing_events_are_not_counted_twice():
    """A loop event that encloses its body's ops is dropped; busy time is
    the union of what is left."""
    evs = [("%while.1 = s32[] while(s32[] %a)", 0, 100),
           ("%a.1 = f32[] add(f32[] %x)", 10, 30),
           ("%b.1 = f32[] add(f32[] %x)", 50, 90)]
    plane = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        NS(name=n, start_ns=a, end_ns=b) for n, a, b in evs])])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        NS(name="bench.window", start_ns=0, end_ns=100)])])
    red = tracing.reduce(NS(planes=[plane, host]), 1)
    assert {o["name"] for o in red["ops"]} == {"a.1", "b.1"}
    assert red["busy_s"] == pytest.approx(60e-9)
    assert sorted(g[1] for g in red["gaps"]) == pytest.approx(
        [10e-9, 10e-9, 20e-9])
