"""The trace reduction, on a small trace recorded on a TPU v5e (one
single-hop and one two-hop request at n = 16, 200 slots) and on
hand-made intervals."""
from pathlib import Path

import pytest

from fabric_bench import trace

pytest.importorskip("jax")

RECORDED = Path(__file__).parent / "data" / "trace_small.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(trace.read(str(RECORDED)))


def test_recorded_trace_has_device_and_spans(recorded):
    assert len(recorded.requests) == 2 and len(recorded.engine) == 2
    assert recorded.window == (recorded.requests[0][1],
                               recorded.requests[-1][2])
    assert 0.0 < recorded.busy_s < recorded.window_s


def test_recorded_modules_by_jitted_name(recorded):
    assert set(recorded.module_s) == {"singlehop", "twohop_fct"}
    # the two modules are all the device ran: their time is the busy time,
    # to the gaps between the operations inside a module
    assert sum(recorded.module_s.values()) == pytest.approx(
        recorded.busy_s, rel=0.01)


def test_recorded_idle_adds_up(recorded):
    idle = sum(v for _, v in recorded.idle_gaps)
    assert idle + recorded.busy_s == pytest.approx(recorded.window_s)
    kinds = {k for k, _ in recorded.idle_gaps}
    assert {"construct", "engine:before_device",
            "engine:after_device"} <= kinds


def test_recorded_op_names_are_short(recorded):
    assert recorded.device_ops
    for name, seconds in recorded.device_ops:
        assert name.startswith("%") and len(name) < 80 and seconds > 0


def test_union_covered_gaps():
    m = trace.union([("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0)])
    assert m == [[0.0, 2.0], [3.0, 4.0]]
    assert trace.covered(m, 1.0, 3.5) == pytest.approx(1.5)
    assert trace.gaps(m, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_idle_split_by_host_activity():
    tr = trace.Trace(
        ops=[[("%f = f32[] fusion()", 4.0, 5.0)]],
        modules=[[("jit_singlehop(1)", 4.0, 5.0)]],
        spans=[("fb.request", 0.0, 10.0), ("fb.construct", 0.0, 2.0),
               ("fb.engine", 2.0, 10.0)])
    r = trace.reduce(tr)
    assert dict(r.idle_gaps) == pytest.approx(
        {"construct": 2.0, "engine:before_device": 2.0,
         "engine:after_device": 5.0})
    assert r.device_ops == [["%f fusion", 1.0]]
    assert r.busy_s == 1.0 and r.window_s == 10.0


def test_no_request_no_reduction():
    assert trace.reduce(trace.Trace([[]], [[]], [])) is None
