"""The ``ws512_twohop`` cell at a size the CPU tests can hold: served on the
``twohop_sparse`` kernel (the crossover lowered below the shrunk n), correct
against the plain reference, the bfloat16 control failing its limit, the
sparse kernel's least work equal to the dense one's, and the readers of the
staging counters and of the kernel's trace events."""
import pytest

from fabric_bench import control, harness, runner, trace
from fabric_bench.kernels import twohop_dense, twohop_sparse

pytest.importorskip("jax")

from repro.core import simulator as sim  # noqa: E402
from repro.core import tracing  # noqa: E402

ROOT = harness.ROOT.parent
NAME = "ws512_twohop"
# n stays above the program's 64-rack limit for per-flow two-hop FCTs, so
# the aggregate path serves, as it does at n = 512
N, HORIZON = 72, 128
SEED = 2**32 + 512


def _cell() -> harness.Cell:
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell, entry = harness.load_cell(bench, NAME, ROOT)
    assert entry["config"] == "websearch_n512" and entry["chips"] == 1
    assert cell.n == 512
    cell.config["n"] = N
    cell.traffic["workload"]["horizon"] = HORIZON
    cell.traffic["trace_requests"] = 1
    return cell


@pytest.fixture
def sparse(monkeypatch):
    """The crossover below the shrunk n, so ``twohop_sparse`` serves as at
    n = 512."""
    monkeypatch.setattr(sim, "_TWOHOP_DENSE_MAX_N", 64)


def _passes(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= v for k, v in limits.items())


def _batches(served: list) -> list:
    out = []
    for s in served:
        a, b = s.t0 * 1e9, s.t1 * 1e9
        out += [r for r in tracing.records() if r.name == "fabric.batch"
                and r.t0_ns >= a and r.t1_ns <= b]
    return out


def test_limits_have_no_fct_comparison():
    assert set(_cell().limits) == {"agg_rel", "exact"}
    assert _cell().limits["exact"] == 0


def test_sparse_kernel_serves_the_cell_correctly(sparse):
    cell = _cell()
    ctx, facts = runner.measure(cell, SEED, 0.1, False, "cpu", t_start=0.0,
                                log=lambda *a: None)
    assert facts["compiles_in_window"] == 0
    batches = _batches(ctx.window)
    assert len(batches) == len(ctx.window) >= 1
    assert {r.attrs["kernel"] for r in batches} == {"twohop_sparse"}
    assert {r.attrs["n"] for r in batches} == {N}
    checked = runner.check(cell, ctx.window, SEED)
    assert set(checked) == {"agg_rel", "exact"}
    assert checked["exact"]["value"] == 0
    assert runner.is_correct(checked), checked


def test_control_fails_where_the_sparse_kernel_passes(sparse):
    cell = _cell()
    r = control.readings(cell, SEED + 1)
    assert _passes(r["program"], cell.limits), r
    assert not _passes(r["control"], cell.limits), r
    assert r["control"]["agg_rel"] > cell.limits["agg_rel"], r


def test_sparse_count_is_the_dense_count():
    cell = _cell()
    req = harness.make_request(cell, harness.make_pool(cell), SEED, 0)
    cases = harness.kernel_batches(cell, req)[twohop_sparse.BATCH]
    assert twohop_sparse.BATCH == twohop_dense.BATCH == "twohop"
    assert len(cases) == 4
    assert twohop_sparse.count(cases) == twohop_dense.count(cases)
    flops, nbytes = twohop_sparse.count(cases)
    assert flops > 0 and nbytes > 0


def _served(cell):
    req = harness.make_request(cell, harness.make_pool(cell), SEED, 0)
    return [harness.Served(req, [], {}, t0, t0 + 1.0, 0.0)
            for t0 in (100.0, 200.0)]


def _stage(rid, t0_s, attrs):
    base = round(t0_s * 1e9)
    return tracing.Record(rid, None, rid, "fabric.stage", base + 10_000_000,
                          base + 500_000_000, attrs)


def _read(ctx, names):
    got = runner.read_metrics(ctx, [{"name": n, "unit": "x"} for n in names])
    return {k: v["value"] for k, v in got.items()}


def test_stage_readers_on_hand_made_records(monkeypatch):
    cell = _cell()
    served = _served(cell)
    recs = [
        # a sparse request: capacity table and lookup table
        _stage(1, 100.0, {"h2d_bytes": 10, "caps_ns": 300_000_000,
                          "lut_ns": 90_000_000}),
        # a dense request counts the capacity table only
        _stage(2, 200.0, {"h2d_bytes": 10, "caps_ns": 100_000_000}),
        # a span outside both requests is not theirs
        _stage(3, 150.0, {"caps_ns": 7_000_000_000, "lut_ns": 1}),
    ]
    monkeypatch.setattr(tracing, "records", lambda: recs)
    ctx = runner.Context(cell, "TPU v5 lite", 1.0, traced=served)
    m = _read(ctx, ["stage_caps_ms", "stage_lut_ms"])
    # means per traced request: (300 + 100) / 2 and (90 + 0) / 2
    assert m == pytest.approx({"stage_caps_ms": 200.0, "stage_lut_ms": 45.0})


def test_stage_lut_reads_nothing_without_the_counter(monkeypatch):
    cell = _cell()
    served = _served(cell)
    recs = [_stage(1, 100.0, {"caps_ns": 2_000_000}),
            _stage(2, 200.0, {"caps_ns": 4_000_000})]
    monkeypatch.setattr(tracing, "records", lambda: recs)
    ctx = runner.Context(cell, "TPU v5 lite", 1.0, traced=served)
    assert _read(ctx, ["stage_caps_ms", "stage_lut_ms"]) == pytest.approx(
        {"stage_caps_ms": 3.0})
    # a program without the counters (the parent of this cell) reads none
    monkeypatch.setattr(tracing, "records",
                        lambda: [_stage(1, 100.0, {"h2d_bytes": 1})])
    assert _read(ctx, ["stage_caps_ms", "stage_lut_ms"]) == {}


def test_kernel_readers_on_a_hand_made_trace():
    """Two requests whose ``jit_twohop_sparse`` modules run 40 ms and
    60 ms of device time; the roofline is the two-hop problem's count at
    the chip's peaks over that time."""
    from fabric_bench import peaks
    cell = _cell()
    served = _served(cell)
    tr = trace.Trace(
        ops=[[("%f = f32[] fusion()", 5.1, 5.14),
              ("%f = f32[] fusion()", 7.1, 7.16)]],
        modules=[[("jit_twohop_sparse(3)", 5.1, 5.14),
                  ("jit_twohop_sparse(3)", 7.1, 7.16)]],
        spans=[("fb.request", 5.0, 6.0), ("fb.request", 7.0, 8.0)])
    ctx = runner.Context(cell, "TPU v5 lite", 1.0, traced=served,
                         trace=trace.reduce(tr))
    m = _read(ctx, ["twohop_sparse_ms", "twohop_sparse_roofline",
                    "twohop_dense_ms", "twohop_dense_roofline"])
    assert set(m) == {"twohop_sparse_ms", "twohop_sparse_roofline"}
    assert m["twohop_sparse_ms"] == pytest.approx(50.0)
    flops = nbytes = 0.0
    for s in served:
        f, b = twohop_dense.count(harness.kernel_batches(cell, s.request)
                                  ["twohop"])
        flops, nbytes = flops + f, nbytes + b
    least, _ = peaks.least_seconds(flops, nbytes, "TPU v5 lite")
    assert m["twohop_sparse_roofline"] == pytest.approx(100 * least / 0.1)
    assert 0 < m["twohop_sparse_roofline"] < 100


def test_traced_tiny_cell_reports_the_staging_counters(sparse, tmp_path):
    cell = _cell()
    ctx, _ = runner.measure(cell, SEED + 2, 0.1, True, "cpu", t_start=0.0,
                            trace_dir=str(tmp_path / "trace"),
                            log=lambda *a: None)
    assert ctx.trace is None                   # no TPU plane on the CPU
    m = _read(ctx, ["stage_ms", "stage_caps_ms", "stage_lut_ms"])
    assert set(m) == {"stage_ms", "stage_caps_ms", "stage_lut_ms"}
    assert all(v > 0 for v in m.values()), m
    assert m["stage_caps_ms"] + m["stage_lut_ms"] < m["stage_ms"]
