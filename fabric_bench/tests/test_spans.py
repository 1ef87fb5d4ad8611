"""The span-based per-layer metrics: their arithmetic on hand-made program
records and a hand-made device trace whose clock is offset from the
host's, and on the tiny cells served on the CPU."""
import sys

import pytest

from fabric_bench import harness, runner, spans, trace
from fabric_bench.tests import tiny

pytest.importorskip("jax")

from repro.core import tracing  # noqa: E402

HOST = ["stage_ms", "replay_ms", "replay_arrive_ms", "h2d_mb",
        "normalize_ms", "round_ms", "decompose_ms"]
DEVICE = ["fetch_idle_ms", "idle_unspanned_pct"]

# one request's spans, as (id, parent, name, start ms, end ms, attrs) from
# the request's start on the host clock
REQUEST = [
    (8, None, "fabric.construct", 10, 190, {}),
    (9, 8, "fabric.construct.normalize", 20, 80, {}),
    (10, 8, "fabric.construct.round", 80, 90, {}),
    (11, 8, "fabric.construct.decompose", 90, 170, {}),
    (1, None, "fabric.sweep", 200, 950, {"cases": 4}),
    (2, 1, "fabric.batch", 250, 900, {}),
    (3, 2, "fabric.stage", 250, 300, {"h2d_bytes": 36_000_000}),
    (4, 2, "fabric.dispatch", 300, 320, {}),
    (5, 2, "fabric.fetch", 320, 500, {"d2h_bytes": 1}),
    (6, 2, "fabric.replay", 500, 800, {"arrive_ns": 100_000_000}),
    (7, 2, "fabric.results", 800, 850, {}),
]


def _records(host_t0_s: float, id_base: int) -> list:
    base = round(host_t0_s * 1e9)
    parent = {i: p for i, p, *_ in REQUEST}

    def root(i):
        while parent[i] is not None:
            i = parent[i]
        return i

    return [tracing.Record(
        i + id_base, None if p is None else p + id_base, root(i) + id_base,
        name, base + a * 1_000_000, base + b * 1_000_000, attrs)
        for i, p, name, a, b, attrs in REQUEST]


def _ctx(monkeypatch, trace_requests=2):
    """Two requests served at host seconds 100 and 200, traced on a device
    clock where they start at 5 and 7; the chip runs 310-450 ms into
    each."""
    cell = tiny.cell("ws256_singlehop")
    req = harness.make_request(cell, harness.make_pool(cell), 5, 0)
    served = [harness.Served(req, [], {}, t0, t0 + 1.0, 0.0)
              for t0 in (100.0, 200.0)]
    recs = _records(100.0, 0) + _records(200.0, 100)
    # a span outside both requests is not theirs
    recs.append(tracing.Record(999, None, 999, "fabric.stage",
                               round(150e9), round(150.5e9),
                               {"h2d_bytes": 5}))
    monkeypatch.setattr(tracing, "records", lambda: recs)
    starts = (5.0, 7.0)[:trace_requests]
    tr = trace.Trace(
        ops=[[("%f = f32[] fusion()", s + 0.31, s + 0.45) for s in starts]],
        modules=[[]],
        spans=[("fb.request", s, s + 1.0) for s in starts])
    return runner.Context(cell, "TPU v5 lite", 1.0, traced=served,
                          trace=trace.reduce(tr))


def _read(ctx, names):
    got = runner.read_metrics(ctx, [{"name": n, "unit": "x"}
                                    for n in names])
    return {k: v["value"] for k, v in got.items()}


def test_span_metrics_on_hand_made_records(monkeypatch):
    m = _read(_ctx(monkeypatch), HOST + DEVICE)
    assert m == pytest.approx({
        "stage_ms": 50.0, "replay_ms": 300.0, "replay_arrive_ms": 100.0,
        "h2d_mb": 36.0, "normalize_ms": 60.0, "round_ms": 10.0,
        "decompose_ms": 80.0,
        # fetch maps to 5.32-5.50 s; the chip is busy to 5.45
        "fetch_idle_ms": 50.0,
        # idle 0.86 s a request; no leaf span over 5.00-5.01, 5.19-5.25
        # (a gap and the sweep's self time), 5.85-6.00
        "idle_unspanned_pct": 100.0 * 0.22 / 0.86})


def test_self_time_subtracts_children(monkeypatch):
    _ctx(monkeypatch)
    recs = _records(100.0, 0)
    by = {r.name: r for r in recs}
    assert spans.self_ns(by["fabric.sweep"], recs) == pytest.approx(100e6)
    assert spans.self_ns(by["fabric.batch"], recs) == pytest.approx(50e6)
    assert spans.self_ns(by["fabric.construct"], recs) == pytest.approx(
        30e6)


def test_device_metrics_need_paired_requests(monkeypatch):
    m = _read(_ctx(monkeypatch, trace_requests=1), HOST + DEVICE)
    assert set(m) == set(HOST)


def test_no_recorder_no_metrics(monkeypatch):
    """A program without ``repro.core.tracing`` (an older checkout)."""
    import repro.core
    ctx = _ctx(monkeypatch)
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read(ctx, HOST + DEVICE) == {}


@pytest.mark.parametrize("name,host", [
    ("ws256_singlehop", HOST), ("ws256_twohop", ["stage_ms", "h2d_mb"])])
def test_tiny_cells_report_host_metrics(tmp_path, name, host):
    cell = tiny.cell(name)
    ctx, _ = runner.measure(cell, 2**33 + 3, 0.1, True, "cpu", t_start=0.0,
                            trace_dir=str(tmp_path / "trace"),
                            log=lambda *a: None)
    assert ctx.trace is None                   # no TPU plane on the CPU
    m = _read(ctx, HOST + DEVICE)
    assert set(m) == set(host)
    assert all(v > 0 for v in m.values()), m
