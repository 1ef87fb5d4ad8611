"""Flow-level simulator: conservation, FCT sanity, mode ordering, JAX parity,
golden traces of the vectorized engine against the reference engine."""
import numpy as np
import pytest

from repro.core.schedule import oblivious_schedule, vermilion_schedule
from repro.core.simulator import (
    SweepCase,
    Workload,
    run_sweep,
    simulate,
    simulate_reference,
    websearch_workload,
)

BPS = 25e9 * 4.5e-6  # bits per slot at 25G / 4.5us
RECFG = 1 / 9


def tiny_workload(n=4, horizon=50):
    # one flow per node to its +1 neighbor, one slot-size each
    src = np.arange(n)
    dst = (src + 1) % n
    return Workload(
        src=src, dst=dst,
        size=np.full(n, BPS * 0.5),
        arrival=np.zeros(n, dtype=np.int64),
        n=n, horizon=horizon,
    )


def test_conservation_single_hop():
    wl = websearch_workload(8, 0.2, 400, BPS, d_hat=2, seed=0)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2, recfg_frac=RECFG)
    r = simulate(s, wl, BPS)
    assert r.delivered_bits <= r.offered_bits + 1e-6
    assert 0 <= r.utilization <= 1


def test_conservation_two_hop():
    wl = websearch_workload(8, 0.2, 400, BPS, d_hat=2, seed=0)
    s = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    for mode in ("rotorlb", "vlb"):
        r = simulate(s, wl, BPS, mode=mode)
        assert r.delivered_bits <= r.offered_bits + 1e-6
        assert r.avg_hops >= 1.0


def test_ring_demand_completes_fast():
    n = 4
    wl = tiny_workload(n)
    m = wl.demand_matrix()
    s = vermilion_schedule(m, k=3, d_hat=1, seed=0)
    r = simulate(s, wl, BPS)
    assert np.isfinite(r.fct_slots).all()
    assert r.fct_slots.max() <= 10  # direct circuits nearly every slot


def test_fct_only_counts_after_arrival():
    wl = Workload(
        src=np.array([0]), dst=np.array([1]),
        size=np.array([BPS * 0.1]), arrival=np.array([20]),
        n=4, horizon=60,
    )
    s = oblivious_schedule(4, d_hat=1)
    r = simulate(s, wl, BPS)
    assert np.isfinite(r.fct_slots[0])
    assert r.fct_slots[0] >= 1


def test_processor_sharing_short_beats_elephant():
    """A short flow sharing a pair with an elephant must finish far sooner."""
    wl = Workload(
        src=np.array([0, 0]), dst=np.array([1, 1]),
        size=np.array([BPS * 100, BPS * 0.2]),
        arrival=np.array([0, 5], dtype=np.int64),
        n=4, horizon=500,
    )
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=1)
    r = simulate(s, wl, BPS)
    assert r.fct_slots[1] < r.fct_slots[0] / 5


def test_vermilion_beats_oblivious_singlehop_util():
    wl = websearch_workload(8, 0.5, 600, BPS, d_hat=2, seed=3)
    m = wl.demand_matrix()
    sv = vermilion_schedule(m, k=3, d_hat=2, recfg_frac=RECFG)
    so = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    rv = simulate(sv, wl, BPS)
    ro = simulate(so, wl, BPS)  # oblivious restricted to single hop
    assert rv.utilization > ro.utilization


def test_jax_parity():
    pytest.importorskip("jax")
    wl = websearch_workload(6, 0.3, 300, BPS, d_hat=2, seed=2)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2, recfg_frac=RECFG)
    cases = [SweepCase(s, wl, "single_hop", "v")]
    r_np = run_sweep(cases, BPS)[0].result
    r_jax = run_sweep(cases, BPS, backend="jax")[0].result
    assert np.isclose(r_np.delivered_bits, r_jax.delivered_bits, rtol=1e-5)


def test_percentiles_api():
    wl = websearch_workload(6, 0.2, 300, BPS, d_hat=2, seed=4)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2)
    r = simulate(s, wl, BPS)
    p_all = r.fct_percentile(99)
    p_short = r.fct_percentile(99, short_cutoff=8e5)
    assert np.isfinite(p_all) and np.isfinite(p_short)


# ---------------------------------------------------------------------------
# Golden traces: vectorized engine vs the pre-vectorization reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["single_hop", "rotorlb", "vlb"])
@pytest.mark.parametrize("seed", [0, 3, 7])
def test_golden_trace_vs_reference(mode, seed):
    wl = websearch_workload(10, 0.45, 400, BPS, d_hat=2, seed=seed)
    if mode == "single_hop":
        s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                               recfg_frac=RECFG, seed=seed)
    else:
        s = oblivious_schedule(10, d_hat=2, recfg_frac=RECFG)
    a = simulate_reference(s, wl, BPS, mode=mode)
    b = simulate(s, wl, BPS, mode=mode)
    assert np.array_equal(a.fct_slots, b.fct_slots)
    assert np.isclose(a.delivered_bits, b.delivered_bits, rtol=1e-6)
    assert np.isclose(a.avg_hops, b.avg_hops, rtol=1e-6)


@pytest.mark.parametrize("mode", ["single_hop", "rotorlb"])
def test_golden_trace_overloaded(mode):
    """Deep queues exercise the offset bookkeeping and pad fallback."""
    wl = websearch_workload(6, 2.5, 500, BPS, d_hat=1, seed=0)
    s = oblivious_schedule(6, d_hat=1, recfg_frac=RECFG)
    a = simulate_reference(s, wl, BPS, mode=mode)
    b = simulate(s, wl, BPS, mode=mode)
    assert np.array_equal(a.fct_slots, b.fct_slots)
    assert np.isclose(a.delivered_bits, b.delivered_bits, rtol=1e-6)


# ---------------------------------------------------------------------------
# Flow-credit ledger against the plain processor-sharing tracker
# ---------------------------------------------------------------------------

def _hot_pair(rng):
    """240 flows on pair (0, 1), served below their offered rate for a
    while so the pair's run grows past 200 live flows, beside light
    traffic on two other pairs."""
    H = 200
    src = np.r_[np.zeros(240, int), rng.integers(1, 3, 60)]
    dst = np.r_[np.ones(240, int), np.zeros(60, int)]
    size = rng.lognormal(np.log(1e5), 1.5, 300)
    arrival = np.r_[np.sort(rng.integers(0, 80, 240)),
                    np.sort(rng.integers(0, 150, 60))]
    d = np.zeros((H, 3, 3))
    d[:, 0, 1] = rng.uniform(0.5, 1.0, H) * size[:240].sum() / 80
    d[:80, 0, 1] *= 0.05
    d[:, 1, 0] = d[:, 2, 0] = rng.uniform(1e4, 3e5, H)
    return 3, src, dst, size, arrival, d


def _bursts(rng):
    """Three bursts of 60 same-slot arrivals into pair (1, 2)."""
    H = 120
    arrival = np.repeat([0, 10, 20], 60)
    src, dst = np.ones(180, int), np.full(180, 2)
    size = rng.lognormal(np.log(5e4), 1.0, 180)
    d = np.zeros((H, 3, 3))
    d[:, 1, 2] = rng.uniform(0.0, 2.0, H) * size.sum() / 60
    return 3, src, dst, size, arrival, d


def _ties(rng):
    """Equal sizes: 8 flows a slot of three sizes on two pairs, so runs
    hold equal stored sizes from one slot and from several."""
    H = 90
    arrival = np.repeat(np.arange(30), 8)
    src = np.tile([0, 0, 0, 0, 2, 2, 2, 2], 30)
    dst = np.tile([2, 2, 2, 2, 1, 1, 1, 1], 30)
    size = rng.choice([2e4, 5e4, 1e5], len(src))
    d = np.zeros((H, 3, 3))
    d[:, 0, 2], d[:, 2, 1] = rng.choice([0.0, 5e4, 1.5e5, 4e5], (2, H))
    return 3, src, dst, size, arrival, d


def _long_drain(rng):
    """Runs of 30 flows on three pairs: one slot sinks the 20 small ones
    (more completions than the water-level pad holds), the next drains
    the 10 large ones in full."""
    H = 20
    src, dst, size, arrival = [], [], [], []
    d = np.zeros((H, 3, 3))
    for t, (u, v) in zip((0, 4, 8), ((1, 0), (2, 0), (0, 1))):
        small = rng.uniform(1e3, 2e3, 20)
        large = rng.uniform(1e6, 2e6, 10)
        size += [*small, *large]
        src += [u] * 30
        dst += [v] * 30
        arrival += [t] * 30
        d[t + 1, u, v] = 20 * 2e3 + 10 * 3e3
        d[t + 2, u, v] = 2.5e7
    order = np.argsort(arrival, kind="stable")
    return (3, np.array(src)[order], np.array(dst)[order],
            np.array(size)[order], np.array(arrival)[order], d)


def _rebase(rng):
    """An elephant on pair (0, 1) lifts its offset past 1e9 while 40
    small flows a slot complete on two other pairs, so the ledger
    rebases its offsets; medium flows keep completing on the pair."""
    H = 80
    src, dst, size, arrival = [0], [1], [1e13], [0]
    for t in range(H):
        k = 40 + (t % 5 == 0)
        src += [1] * 20 + [2] * 20 + [0] * (k - 40)
        dst += [2] * 20 + [0] * 20 + [1] * (k - 40)
        size += [*rng.uniform(1e3, 1e4, 40), *rng.uniform(1e8, 1e9, k - 40)]
        arrival += [t] * k
    d = np.zeros((H, 3, 3))
    d[:, 0, 1] = rng.uniform(5e8, 7e8, H)
    d[:, 1, 2] = d[:, 2, 0] = 3e5
    return 3, *map(np.array, (src, dst, size, arrival)), d


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("build", [_hot_pair, _bursts, _ties, _long_drain,
                                   _rebase])
def test_credit_ledger_matches_flow_tracker(build, seed):
    """The flow-credit ledger gives the plain processor-sharing tracker's
    FCTs, one case fed exact (n, n) delivered matrices each slot."""
    from repro.core.simulator import _CreditState, _FlowTracker
    n, src, dst, size, arrival, deliver = build(np.random.default_rng(seed))
    wl = Workload(src=src, dst=dst, size=size, arrival=arrival, n=n,
                  horizon=len(deliver))
    tracker = _FlowTracker(wl)
    fct = np.full(wl.num_flows, np.inf)
    ledger = _CreditState(n * n, src * n + dst, size, arrival, fct)
    rebased = False
    for slot, d in enumerate(deliver):
        before = ledger.off.max()
        f = np.flatnonzero(arrival == slot)
        if f.size:
            tracker.arrive(f)
            ledger.arrive(f)
        tracker.credit(d, slot)
        ledger.credit(d.reshape(-1), slot)
        rebased |= ledger.off.max() < before
    assert np.isfinite(fct).sum() > wl.num_flows // 2
    assert np.array_equal(tracker.fct, fct)
    assert rebased == (build is _rebase)


def test_run_sweep_matches_per_case_simulate():
    """One batched sweep across modes reproduces per-case results."""
    wl = websearch_workload(8, 0.4, 300, BPS, d_hat=2, seed=5)
    sv = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                            recfg_frac=RECFG)
    so = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    cases = [SweepCase(sv, wl, "single_hop", "v"),
             SweepCase(so, wl, "rotorlb", "r"),
             SweepCase(so, wl, "vlb", "l"),
             SweepCase(so, wl, "single_hop", "o")]
    rows = run_sweep(cases, BPS)
    assert [r.label for r in rows] == ["v", "r", "l", "o"]
    for c, r in zip(cases, rows):
        ref = simulate_reference(c.sched, c.wl, BPS, mode=c.mode)
        assert np.array_equal(ref.fct_slots, r.result.fct_slots), c.label
        assert np.isclose(ref.delivered_bits, r.result.delivered_bits,
                          rtol=1e-6)


def test_run_sweep_jax_backend_aggregates():
    """backend='jax' reproduces the numpy aggregate AND the exact per-flow
    FCT multiset (the f64 credit replay over the f32 device trace)."""
    pytest.importorskip("jax")
    wl = websearch_workload(6, 0.3, 200, BPS, d_hat=2, seed=2)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                           recfg_frac=RECFG)
    cases = [SweepCase(s, wl, "single_hop", "v")]
    r_np = run_sweep(cases, BPS)[0].result
    r_jx = run_sweep(cases, BPS, backend="jax")[0].result
    assert np.isclose(r_np.delivered_bits, r_jx.delivered_bits, rtol=1e-5)
    assert np.array_equal(r_np.fct_slots, r_jx.fct_slots, equal_nan=True)


# ---------------------------------------------------------------------------
# Two-hop JAX backend: parity with the NumPy relay engine (which is itself
# golden-traced to simulate_reference, so these pins are transitive)
# ---------------------------------------------------------------------------

def _assert_jax_parity(r_np, r_jx, rtol=1e-3):
    assert np.isclose(r_np.utilization, r_jx.utilization, rtol=rtol)
    assert np.isclose(r_np.delivered_bits, r_jx.delivered_bits, rtol=rtol)
    assert np.isclose(r_np.avg_hops, r_jx.avg_hops, rtol=rtol)
    # small instances route through the per-flow twohop_fct kernel, whose
    # credit replay reproduces the numpy FCT multiset exactly; the
    # aggregate-only dense/sparse kernels leave fct_slots all-inf
    finite = np.isfinite(r_jx.fct_slots)
    if finite.any():
        assert np.array_equal(r_np.fct_slots, r_jx.fct_slots,
                              equal_nan=True)


@pytest.mark.parametrize("mode", ["rotorlb", "vlb"])
@pytest.mark.parametrize("kernel", ["dense", "sparse"])
def test_twohop_jax_parity(mode, kernel):
    """Both kernel formulations match the NumPy engine for both modes."""
    pytest.importorskip("jax")
    from repro.core.simulator import _twohop_batch_jax
    wl = websearch_workload(10, 0.45, 300, BPS, d_hat=2, seed=1)
    s = oblivious_schedule(10, d_hat=2, recfg_frac=RECFG)
    r_np = simulate(s, wl, BPS, mode=mode)
    r_jx = _twohop_batch_jax([(s, wl)], BPS, [mode], kernel=kernel)[0]
    _assert_jax_parity(r_np, r_jx)


def test_twohop_jax_mixed_mode_grid():
    """One jax sweep over rotorlb + vlb + single_hop matches numpy rows."""
    pytest.importorskip("jax")
    wl = websearch_workload(8, 0.4, 250, BPS, d_hat=2, seed=5)
    sv = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                            recfg_frac=RECFG)
    so = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    cases = [SweepCase(sv, wl, "single_hop", "v"),
             SweepCase(so, wl, "rotorlb", "r"),
             SweepCase(so, wl, "vlb", "l")]
    rows_np = run_sweep(cases, BPS)
    rows_jx = run_sweep(cases, BPS, backend="jax")
    assert [r.label for r in rows_jx] == ["v", "r", "l"]
    for a, b in zip(rows_np, rows_jx):
        _assert_jax_parity(a.result, b.result)
    assert rows_jx[2].result.avg_hops >= rows_jx[1].result.avg_hops >= 1.0


def test_twohop_jax_overloaded():
    """Deep queues: the offload/drain bookkeeping under sustained backlog."""
    pytest.importorskip("jax")
    wl = websearch_workload(6, 2.5, 400, BPS, d_hat=1, seed=0)
    s = oblivious_schedule(6, d_hat=1, recfg_frac=RECFG)
    for mode in ("rotorlb", "vlb"):
        r_np = simulate(s, wl, BPS, mode=mode)
        r_jx = run_sweep([SweepCase(s, wl, mode, mode)], BPS,
                         backend="jax")[0].result
        _assert_jax_parity(r_np, r_jx)


def test_twohop_jax_mixed_horizons():
    """Cases with different wl.horizon batch correctly (finished cases
    idle while the batch runs on)."""
    pytest.importorskip("jax")
    s = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    wl_a = websearch_workload(8, 0.5, 120, BPS, d_hat=2, seed=2)
    wl_b = websearch_workload(8, 0.5, 300, BPS, d_hat=2, seed=3)
    cases = [SweepCase(s, wl_a, "rotorlb", "short"),
             SweepCase(s, wl_b, "vlb", "long")]
    rows_np = run_sweep(cases, BPS)
    rows_jx = run_sweep(cases, BPS, backend="jax")
    for a, b in zip(rows_np, rows_jx):
        _assert_jax_parity(a.result, b.result)


def test_jax_backend_no_retrace(assert_no_retrace):
    """Repeated same-shape sweeps reuse the compiled kernels: the scan
    bodies must not re-trace (the PR 3 aggregate engine re-traced every
    call)."""
    pytest.importorskip("jax")
    wl = websearch_workload(7, 0.4, 150, BPS, d_hat=2, seed=4)
    sv = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                            recfg_frac=RECFG)
    so = oblivious_schedule(7, d_hat=2, recfg_frac=RECFG)
    cases = [SweepCase(sv, wl, "single_hop", "v"),
             SweepCase(so, wl, "rotorlb", "r"),
             SweepCase(so, wl, "vlb", "l")]
    run_sweep(cases, BPS, backend="jax")          # compile (or cache hit)
    with assert_no_retrace():
        for _ in range(3):
            run_sweep(cases, BPS, backend="jax")


def test_jax_twohop_kernels_no_retrace(assert_no_retrace):
    """Dense and sparse two-hop relay kernels are pinned separately."""
    pytest.importorskip("jax")
    from repro.core.simulator import _twohop_batch_jax
    wl = websearch_workload(7, 0.4, 150, BPS, d_hat=2, seed=4)
    so = oblivious_schedule(7, d_hat=2, recfg_frac=RECFG)
    batch = [(so, wl)]
    for kernel in ("dense", "sparse"):
        _twohop_batch_jax(batch, BPS, ["rotorlb"], kernel=kernel)
        with assert_no_retrace(kernels=(f"twohop_{kernel}",)):
            for _ in range(3):
                _twohop_batch_jax(batch, BPS, ["rotorlb"], kernel=kernel)


def _mixed_relay_batch():
    """rotorlb and vlb cases at n = 13: oblivious d_hat = 3 and d_hat = 4
    (periods 4 and 3), a Vermilion k = 3 schedule whose merged parallel
    circuits leave rows below its degree, and horizons of 60 and 45."""
    n = 13
    wl = websearch_workload(n, 0.5, 60, BPS, d_hat=2, seed=5)
    wl2 = websearch_workload(n, 0.7, 45, BPS, d_hat=2, seed=6)
    sv = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=3,
                            recfg_frac=RECFG)
    cases = [(oblivious_schedule(n, d_hat=3, recfg_frac=RECFG), wl),
             (oblivious_schedule(n, d_hat=4, recfg_frac=RECFG), wl2),
             (sv, wl), (sv, wl2)]
    return cases, ["rotorlb", "vlb", "rotorlb", "vlb"]


def _kernel_io(cases, modes, kernel):
    """The arrays handed to ``twohop_<kernel>`` and what it returned."""
    from repro.core import simulator as sim
    fns = sim._jax_fns()
    name = f"twohop_{kernel}"
    real, seen = fns[name], {}

    def spy(*args):
        seen["io"] = (args, real(*args))
        return seen["io"][1]

    fns[name] = spy
    try:
        sim._twohop_batch_jax(cases, BPS, modes, kernel=kernel)
    finally:
        fns[name] = real
    return seen["io"]


def test_sparse_kernel_matches_dense_slot_by_slot():
    """Per-slot delivered and second-hop bits and the final VOQ and relay
    carries of ``twohop_sparse`` match ``twohop_dense`` on one batch.  The
    two sum each slot's terms in another order (over a row's D slots, not
    its n columns), so entries agree to f32 rounding: rtol 1e-5, plus
    1e-6 of an array's largest entry for entries that are a difference of
    near-equal terms (a drained queue's remainder)."""
    pytest.importorskip("jax")
    cases, modes = _mixed_relay_batch()
    sv = cases[2][0]
    assert any(len(at) < 13 * sv.d_hat for at, _, _ in sv.slot_circuits())
    _, ((d_deliv, d_second), d_carry) = _kernel_io(cases, modes, "dense")
    inputs, ((s_deliv, s_second), s_carry) = _kernel_io(cases, modes,
                                                         "sparse")
    assert inputs[4].shape[1] == 4                      # D: the d_hat = 4 case
    pairs = [(d_deliv, s_deliv), (d_second, s_second),
             *zip(d_carry, s_carry)]
    for want, got in pairs:
        want = np.asarray(want)
        got = np.asarray(got).reshape(want.shape)
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-6 * np.abs(want).max())
    assert np.asarray(d_second)[45:, [1, 3]].sum() == 0     # past horizon


def test_dense_kernel_inputs_are_pinned():
    """The dense kernel's staged inputs for one mixed batch, byte for byte
    (dtype, shape and data of each array): the sparse kernel's table
    layout must leave the dense path's staging as it was."""
    pytest.importorskip("jax")
    import hashlib
    cases, modes = _mixed_relay_batch()
    inputs, _ = _kernel_io(cases, modes, "dense")
    h = hashlib.sha256()
    for a in inputs:
        a = np.asarray(a)
        h.update(a.dtype.str.encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == (
        "e8e608acd6e82dcf30e1082c8aa24ac26a123479d95d23785cc65a6a6b67d002")


def test_completed_frac_monotone_in_capacity():
    """More bits per slot never completes fewer flows."""
    wl = websearch_workload(8, 0.6, 400, BPS, d_hat=2, seed=2)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                          recfg_frac=RECFG)
    fracs = [simulate(s, wl, scale * BPS).completed_frac
             for scale in (0.25, 0.5, 1.0, 2.0, 4.0)]
    assert all(b >= a - 1e-12 for a, b in zip(fracs, fracs[1:])), fracs
