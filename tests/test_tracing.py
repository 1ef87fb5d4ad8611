"""The span recorder (``repro.core.tracing``) and the spans the served
path records: nesting and request identity, the ring's bound, the
counters against the quantities they count, and results unchanged by the
fetch moved ahead of the replay."""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import simulator as sim
from repro.core import tracing
from repro.core.schedule import oblivious_schedule, vermilion_schedule
from repro.core.simulator import SweepCase, run_sweep, websearch_workload

BPS = 100e9 * 4.5e-6
RECFG = 1 / 9
ENGINE = ["fabric.stage", "fabric.dispatch", "fabric.fetch",
          "fabric.replay", "fabric.results"]


def _tree(root_name: str):
    """The newest ``root_name`` record and every record under it."""
    recs = tracing.records()
    root = next(r for r in reversed(recs) if r.name == root_name)
    return root, [r for r in recs if r.root_id == root.id]


def _children(rec, recs):
    return sorted((r for r in recs if r.parent_id == rec.id),
                  key=lambda r: r.t0_ns)


def _cases(mode: str, n: int = 8, seed: int = 5):
    wl = websearch_workload(n, 0.4, 300, BPS, d_hat=2, seed=seed)
    if mode == "single_hop":
        s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                               recfg_frac=RECFG)
    else:
        s = oblivious_schedule(n, d_hat=2, recfg_frac=RECFG)
    return [SweepCase(s, wl, mode, mode)], wl


@pytest.mark.parametrize("mode,kernel", [("single_hop", "singlehop"),
                                         ("rotorlb", "twohop_fct")])
def test_sweep_spans_nest_under_one_root(mode, kernel):
    cases, _ = _cases(mode)
    run_sweep(cases, BPS, backend="jax")
    root, recs = _tree("fabric.sweep")
    assert root.parent_id is None and root.attrs == {"cases": 1}
    assert all(r.root_id == root.id for r in recs)
    (batch,) = _children(root, recs)
    assert batch.name == "fabric.batch"
    assert batch.attrs == {"kernel": kernel, "B": 1, "n": 8,
                           "H_pad": sim._pad_to(300, sim._PAD_H)}
    assert [r.name for r in _children(batch, recs)] == ENGINE
    for r in recs:
        assert root.t0_ns <= r.t0_ns <= r.t1_ns <= root.t1_ns


@pytest.mark.parametrize("kernel", ["dense", "sparse"])
def test_aggregate_two_hop_spans(kernel):
    cases, wl = _cases("rotorlb")
    with tracing.span("fabric.sweep"):
        sim._twohop_batch_jax([(c.sched, c.wl) for c in cases], BPS,
                              ["rotorlb"], kernel=kernel)
    root, recs = _tree("fabric.sweep")
    (batch,) = _children(root, recs)
    assert batch.attrs["kernel"] == f"twohop_{kernel}"
    assert [r.name for r in _children(batch, recs)] == [
        "fabric.stage", "fabric.dispatch", "fabric.fetch", "fabric.results"]


def test_sanitizer_fetches_the_final_carry_inside_results():
    cases, _ = _cases("single_hop")
    run_sweep(cases, BPS, backend="jax", sanitize=True)
    root, recs = _tree("fabric.sweep")
    (batch,) = _children(root, recs)
    results = _children(batch, recs)[-1]
    assert results.name == "fabric.results"
    (fetch,) = _children(results, recs)
    assert fetch.name == "fabric.fetch"
    assert fetch.attrs["d2h_bytes"] == 8 * 8 * 4       # voq_f, f32 (B n n)


def test_adaptive_batch_records_the_engine_spans():
    wl = websearch_workload(8, 0.4, 300, BPS, d_hat=2, seed=5)
    sim.run_adaptive([sim.AdaptiveCase(wl, 100, k=3, d_hat=2,
                                       recfg_frac=RECFG, policy="oracle")],
                     BPS, backend="jax")
    batch, recs = _tree("fabric.batch")
    assert batch.parent_id is None and batch.attrs["kernel"] == "singlehop"
    names = [r.name for r in _children(batch, recs)]
    # the control loop's constructions come first, inside the batch
    built = names.count("fabric.construct")
    assert built >= 1 and names == ["fabric.construct"] * built + ENGINE


def test_ring_stays_bounded():
    for i in range(tracing.MAXLEN + 10):
        with tracing.span("fabric.test", i=i):
            pass
    recs = tracing.records()
    assert len(recs) == tracing.MAXLEN
    assert recs[-1].name == "fabric.test"
    assert recs[-1].attrs == {"i": tracing.MAXLEN + 9}
    assert recs[0].attrs == {"i": 10}


def test_span_counters_and_nesting():
    with tracing.span("fabric.outer", k="x") as outer:
        outer.add("n", 2)
        with tracing.span("fabric.inner") as inner:
            inner.add("n", 1)
            inner.add("n", 4)
        outer.add("n", 3)
    *_, rin, rout = tracing.records()
    assert (rin.name, rout.name) == ("fabric.inner", "fabric.outer")
    assert rin.parent_id == rout.id and rin.root_id == rout.id
    assert rout.parent_id is None and rout.root_id == rout.id
    assert rin.attrs == {"n": 5} and rout.attrs == {"k": "x", "n": 5}
    assert rout.t0_ns <= rin.t0_ns <= rin.t1_ns <= rout.t1_ns


def test_span_records_on_error():
    with pytest.raises(KeyError):
        with tracing.span("fabric.fails"):
            raise KeyError("x")
    assert tracing.records()[-1].name == "fabric.fails"
    with tracing.span("fabric.after") as s:
        pass
    assert s.parent_id is None


@pytest.mark.parametrize("mode", ["single_hop", "rotorlb"])
def test_flows_arrived_counts_flows_inside_the_horizon(mode):
    cases, wl = _cases(mode)
    # a second case with a shorter horizon drops its late arrivals
    short = sim.Workload(src=wl.src, dst=wl.dst, size=wl.size,
                         arrival=wl.arrival, n=wl.n, horizon=200)
    cases.append(SweepCase(cases[0].sched, short, mode, "short"))
    run_sweep(cases, BPS, backend="jax")
    _, recs = _tree("fabric.sweep")
    (replay,) = [r for r in recs if r.name == "fabric.replay"]
    want = int((wl.arrival < wl.horizon).sum()
               + (short.arrival < short.horizon).sum())
    assert want < 2 * wl.num_flows
    assert replay.attrs["flows_arrived"] == want


def test_pairs_credited_counts_the_live_mask(monkeypatch):
    seen = {}
    real = sim._replay_credit

    def spy(credit, order, bucket, p_pid, tx64, dr, H):
        seen["live"] = int(((tx64[:H] > 1e-9) | dr[:H]).sum())
        return real(credit, order, bucket, p_pid, tx64, dr, H)

    monkeypatch.setattr(sim, "_replay_credit", spy)
    cases, _ = _cases("single_hop")
    run_sweep(cases, BPS, backend="jax")
    _, recs = _tree("fabric.sweep")
    (replay,) = [r for r in recs if r.name == "fabric.replay"]
    assert seen["live"] > 0
    assert replay.attrs["pairs_credited"] == seen["live"]
    assert replay.attrs["arrive_ns"] > 0


@pytest.mark.parametrize("mode,kernel,fetched", [
    ("single_hop", "singlehop", 1),       # voq_f, (tx, drained)
    ("rotorlb", "twohop_fct", 0)])        # (dp, second), final carry
def test_h2d_bytes_sum_the_kernel_inputs(monkeypatch, mode, kernel, fetched):
    fns = sim._jax_fns()
    seen = {}
    real = fns[kernel]

    def spy(*args):
        seen["bytes"] = sum(a.nbytes for a in args)
        out = real(*args)
        seen["d2h"] = sum(a.nbytes for a in out[fetched])
        return out

    monkeypatch.setitem(fns, kernel, spy)
    cases, _ = _cases(mode)
    run_sweep(cases, BPS, backend="jax")
    _, recs = _tree("fabric.sweep")
    (stage,) = [r for r in recs if r.name == "fabric.stage"]
    (fetch,) = [r for r in recs if r.name == "fabric.fetch"]
    assert stage.attrs["h2d_bytes"] == seen["bytes"] > 0
    assert fetch.attrs["d2h_bytes"] == seen["d2h"] > 0


def test_vermilion_construction_stages():
    wl = websearch_workload(8, 0.4, 300, BPS, d_hat=2, seed=5)
    vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2, seed=1,
                       normalize="saturate")
    root, recs = _tree("fabric.construct")
    assert root.parent_id is None
    assert root.attrs == {"kind": "vermilion", "count": 1, "n": 8}
    assert [r.name for r in _children(root, recs)] == [
        "fabric.construct.normalize", "fabric.construct.round",
        "fabric.construct.decompose"]
    oblivious_schedule(8, d_hat=2)
    assert tracing.records()[-1].name == "fabric.construct"
    assert tracing.records()[-1].attrs == {"kind": "oblivious", "count": 1,
                                           "n": 8}


@pytest.mark.parametrize("mode", ["single_hop", "rotorlb", "vlb"])
def test_traced_sweep_matches_numpy_exactly(mode):
    """The host copy of the kernel outputs now happens in ``fabric.fetch``
    ahead of the replay: FCTs still equal the numpy engine's bit for bit,
    with the sanitizer on and off alike."""
    cases, _ = _cases(mode, seed=11)
    ref = run_sweep(cases, BPS)[0].result
    for sanitize in (False, True):
        got = run_sweep(cases, BPS, backend="jax", sanitize=sanitize)
        r = got[0].result
        assert np.array_equal(ref.fct_slots, r.fct_slots, equal_nan=True)
        assert np.isclose(ref.delivered_bits, r.delivered_bits, rtol=1e-5)
        assert np.isclose(ref.avg_hops, r.avg_hops, rtol=1e-5)
