"""jax-vs-NumPy per-flow parity: exact FCT multisets across every backend
path (static sweep and adaptive), backend validation errors, compile-cache
introspection, retrace pins for the new kernels, the jittable estimation
ops, and the padded slot-circuit export."""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.estimation import (
    TrafficEstimator,
    dequantize,
    dequantize_jax,
    fleet_update_quantize_jax,
    quantize_row,
)
from repro.core.faults import FaultEvent, FaultSchedule
from repro.core.schedule import oblivious_schedule, vermilion_schedule
from repro.core.simulator import (
    AdaptiveCase,
    SweepCase,
    Workload,
    compile_cache_stats,
    phase_shifting_workload,
    run_adaptive,
    run_sweep,
    websearch_workload,
)

BPS = 100e9 * 4.5e-6
RECFG = 1 / 9


def _fct_multisets_equal(a, b):
    fa = np.sort(a[np.isfinite(a)])
    fb = np.sort(b[np.isfinite(b)])
    return fa.shape == fb.shape and np.array_equal(fa, fb)


# ---------------------------------------------------------------------------
# Static sweep: exact per-flow FCT parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["single_hop", "rotorlb", "vlb"])
def test_sweep_fct_multiset_parity(mode):
    """backend='jax' reproduces the numpy FCT multiset exactly (f64 credit
    replay over the f32 device trace, drain-reconciled)."""
    wl = websearch_workload(8, 0.4, 300, BPS, d_hat=2, seed=5)
    if mode == "single_hop":
        s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                               recfg_frac=RECFG)
    else:
        s = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    cases = [SweepCase(s, wl, mode, mode)]
    r_np = run_sweep(cases, BPS)[0].result
    r_jx = run_sweep(cases, BPS, backend="jax")[0].result
    assert np.array_equal(r_np.fct_slots, r_jx.fct_slots, equal_nan=True)
    assert np.isclose(r_np.delivered_bits, r_jx.delivered_bits, rtol=1e-5)


@pytest.mark.parametrize("mode", ["single_hop", "rotorlb"])
def test_sweep_fct_parity_overload(mode):
    """Sustained backlog: deep queues exercise drain reconciliation, where
    f32 serving would otherwise strand near-complete flows."""
    wl = websearch_workload(6, 2.5, 400, BPS, d_hat=1, seed=0)
    s = oblivious_schedule(6, d_hat=1, recfg_frac=RECFG)
    cases = [SweepCase(s, wl, mode, mode)]
    r_np = run_sweep(cases, BPS)[0].result
    r_jx = run_sweep(cases, BPS, backend="jax")[0].result
    assert np.array_equal(r_np.fct_slots, r_jx.fct_slots, equal_nan=True)


def test_sweep_fct_parity_mixed_horizons():
    """Different-horizon cases batch through one kernel without leaking
    service across the shorter case's end."""
    s = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    wl_a = websearch_workload(8, 0.5, 120, BPS, d_hat=2, seed=2)
    wl_b = websearch_workload(8, 0.5, 300, BPS, d_hat=2, seed=3)
    cases = [SweepCase(s, wl_a, "rotorlb", "short"),
             SweepCase(s, wl_b, "vlb", "long")]
    rows_np = run_sweep(cases, BPS)
    rows_jx = run_sweep(cases, BPS, backend="jax")
    for a, b in zip(rows_np, rows_jx):
        assert np.array_equal(a.result.fct_slots, b.result.fct_slots,
                              equal_nan=True), a.label


@pytest.mark.parametrize("mode", ["single_hop", "rotorlb"])
def test_sweep_fct_parity_hot_pair(mode):
    """A hot pair: 240 flows on (0, 1) within 60 slots, beside websearch
    traffic, so one pair's ledger run holds 100-230 live flows; the
    drain-reconciled (single-hop) and level-widened (two-hop) replays
    still give numpy's FCTs exactly."""
    bg = websearch_workload(8, 0.3, 300, BPS, d_hat=2, seed=11)
    rng = np.random.default_rng(11)
    src = np.r_[bg.src, np.zeros(240, int)]
    dst = np.r_[bg.dst, np.ones(240, int)]
    size = np.r_[bg.size, rng.uniform(2e4, 4e5, 240)]
    arrival = np.r_[bg.arrival, rng.integers(0, 60, 240)]
    o = np.argsort(arrival, kind="stable")
    wl = Workload(src=src[o], dst=dst[o], size=size[o], arrival=arrival[o],
                  n=8, horizon=300)
    s = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    cases = [SweepCase(s, wl, mode, mode)]
    r_np = run_sweep(cases, BPS)[0].result
    r_jx = run_sweep(cases, BPS, backend="jax")[0].result
    hot = (wl.src == 0) & (wl.dst == 1)
    assert np.isfinite(r_np.fct_slots[hot]).sum() > 60
    assert np.array_equal(r_np.fct_slots, r_jx.fct_slots, equal_nan=True)


def test_sweep_percentiles_available_on_jax():
    wl = websearch_workload(8, 0.4, 300, BPS, d_hat=2, seed=7)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                           recfg_frac=RECFG)
    r = run_sweep([SweepCase(s, wl, "single_hop", "v")], BPS,
                  backend="jax")[0].result
    assert np.isfinite(r.fct_percentile(50))
    assert np.isfinite(r.fct_percentile(99))


# ---------------------------------------------------------------------------
# Adaptive loop: the jax control-plane replay matches the numpy engine
# ---------------------------------------------------------------------------

def _wl(seed, n=12, horizon=900, load=0.7):
    return phase_shifting_workload(n, load, horizon, BPS, d_hat=3,
                                   seed=seed)


def _assert_adaptive_parity(a, b):
    assert _fct_multisets_equal(a.result.fct_slots, b.result.fct_slots), \
        a.label
    assert a.recomputes == b.recomputes
    assert a.stale_slots == b.stale_slots
    assert a.dark_slots == b.dark_slots
    assert a.schedule_groups_max == b.schedule_groups_max
    assert np.array_equal(np.asarray(a.epoch_estimate_tv),
                          np.asarray(b.epoch_estimate_tv), equal_nan=True)
    assert np.array_equal(np.asarray(a.epoch_disagreement),
                          np.asarray(b.epoch_disagreement), equal_nan=True)
    assert np.array_equal(np.asarray(a.epoch_collision_loss),
                          np.asarray(b.epoch_collision_loss),
                          equal_nan=True)
    assert np.isclose(a.result.utilization, b.result.utilization,
                      rtol=1e-6)


@pytest.mark.parametrize("gather_steps", [None, 6, 2])
@pytest.mark.parametrize("collision", ["drop", "lowest", "receiver"])
def test_adaptive_jax_matches_numpy(gather_steps, collision):
    """Golden disagreement grid: per-flow FCTs, control-plane counters, and
    epoch metrics all match the numpy loop bit-for-bit (FCTs/metrics) or to
    f32 tolerance (utilization)."""
    case = AdaptiveCase(wl=_wl(11), d_hat=3, epoch_slots=150,
                        gather_steps=gather_steps, collision=collision,
                        label=f"{gather_steps}-{collision}")
    a = run_adaptive([case], bits_per_slot=BPS, backend="numpy")[0]
    b = run_adaptive([case], bits_per_slot=BPS, backend="jax")[0]
    _assert_adaptive_parity(a, b)


@pytest.mark.parametrize("policy", ["oracle", "stale", "oblivious"])
def test_adaptive_jax_policies(policy):
    case = AdaptiveCase(wl=_wl(21), d_hat=3, epoch_slots=150,
                        policy=policy, label=policy)
    a = run_adaptive([case], bits_per_slot=BPS, backend="numpy")[0]
    b = run_adaptive([case], bits_per_slot=BPS, backend="jax")[0]
    _assert_adaptive_parity(a, b)


def test_adaptive_jax_charged_case():
    """Construction charging + activation penalty + hot-swap hysteresis:
    the darkened-slot bookkeeping must replay exactly."""
    case = AdaptiveCase(wl=_wl(31), d_hat=3, epoch_slots=150,
                        construction_slots=37,
                        reconfig_penalty_slots=20,
                        swap_tv_threshold=0.2, label="charged")
    a = run_adaptive([case], bits_per_slot=BPS, backend="numpy")[0]
    b = run_adaptive([case], bits_per_slot=BPS, backend="jax")[0]
    _assert_adaptive_parity(a, b)
    assert a.dark_slots > 0


def test_adaptive_jax_batched_grid_matches_per_case():
    """A mixed grid through one run_adaptive call matches case-by-case
    numpy rows (the batch groups by n and amortizes one device scan)."""
    cases = [
        AdaptiveCase(wl=_wl(41), d_hat=3, epoch_slots=150, label="a"),
        AdaptiveCase(wl=_wl(42), d_hat=3, epoch_slots=150, gather_steps=4,
                     collision="lowest", label="b"),
        AdaptiveCase(wl=_wl(43), d_hat=3, epoch_slots=150, policy="oracle",
                     label="c"),
    ]
    rows_np = run_adaptive(cases, bits_per_slot=BPS, backend="numpy")
    rows_jx = run_adaptive(cases, bits_per_slot=BPS, backend="jax")
    assert [r.label for r in rows_jx] == ["a", "b", "c"]
    for a, b in zip(rows_np, rows_jx):
        _assert_adaptive_parity(a, b)


@pytest.mark.parametrize("gather_steps", [None, 4])
def test_adaptive_jax_collision_grid_shares_control(gather_steps):
    """One workload object under three collision policies in one call:
    the batch shares construction and estimation across the cases and
    serves byte-identical plans once, and every row still matches its own
    numpy run.  The sanitizer is off, since it turns that sharing off."""
    wl = _wl(51)
    cases = [AdaptiveCase(wl=wl, d_hat=3, epoch_slots=150,
                          gather_steps=gather_steps, collision=col,
                          label=col)
             for col in ("drop", "lowest", "receiver")]
    rows_jx = run_adaptive(cases, bits_per_slot=BPS, backend="jax",
                           sanitize=False)
    for case, b in zip(cases, rows_jx):
        a = run_adaptive([case], bits_per_slot=BPS, backend="numpy",
                         sanitize=False)[0]
        _assert_adaptive_parity(a, b)


# ---------------------------------------------------------------------------
# Backend validation: clear errors at entry, not deep in dispatch
# ---------------------------------------------------------------------------

def test_sweep_jax_faults_rejected_at_entry():
    wl = websearch_workload(8, 0.4, 200, BPS, d_hat=2, seed=1)
    s = oblivious_schedule(8, d_hat=2, recfg_frac=RECFG)
    fs = FaultSchedule((FaultEvent(10, "plane_down", plane=0),))
    cases = [SweepCase(s, wl, "single_hop", "ok"),
             SweepCase(s, wl, "single_hop", "faulty", faults=fs)]
    with pytest.raises(NotImplementedError, match=r"faulty.*numpy"):
        run_sweep(cases, BPS, backend="jax")
    # the same grid runs fine on numpy
    assert len(run_sweep(cases, BPS, backend="numpy")) == 2


def test_sweep_unknown_backend():
    wl = websearch_workload(6, 0.3, 100, BPS, d_hat=1, seed=0)
    s = oblivious_schedule(6, d_hat=1)
    with pytest.raises(ValueError, match="backend"):
        run_sweep([SweepCase(s, wl, "single_hop", "x")], BPS,
                  backend="torch")


def test_adaptive_jax_rejects_unsupported_features():
    wl = _wl(51, horizon=300)
    fs = FaultSchedule((FaultEvent(10, "plane_down", plane=0),))
    # faults are a pinned NotImplementedError (ROADMAP follow-up — the jax
    # kernels carry no per-slot fault mask); the rest are plain ValueErrors
    unsupported = [
        (AdaptiveCase(wl=wl, d_hat=3, epoch_slots=150, faults=fs,
                      label="faults"), NotImplementedError),
        (AdaptiveCase(wl=wl, d_hat=3, epoch_slots=150, repair=True,
                      label="repair"), ValueError),
        (AdaptiveCase(wl=wl, d_hat=3, epoch_slots=150, collision="fullest",
                      label="fullest"), ValueError),
        (AdaptiveCase(wl=wl, d_hat=3, epoch_slots=150,
                      activation_jitter_slots=3, label="jitter"), ValueError),
    ]
    for case, exc in unsupported:
        with pytest.raises(exc, match=r"numpy"):
            run_adaptive([case], bits_per_slot=BPS, backend="jax")
        # every one of them still runs on the numpy backend
        run_adaptive([case], bits_per_slot=BPS, backend="numpy")


def test_adaptive_jax_faults_pinned_not_implemented():
    """The faults x jax gap is explicit: a FaultSchedule on the jax
    backend raises NotImplementedError naming the case and the remedy,
    and the identical case runs on numpy (the pinned support matrix)."""
    wl = _wl(52, horizon=300)
    fs = FaultSchedule((FaultEvent(20, "plane_down", plane=0),))
    case = AdaptiveCase(wl=wl, d_hat=3, epoch_slots=150, faults=fs,
                        label="faulted-grid")
    with pytest.raises(NotImplementedError,
                       match=r"faulted-grid.*fault injection.*numpy"):
        run_adaptive([case], bits_per_slot=BPS, backend="jax")
    rows = run_adaptive([case], bits_per_slot=BPS, backend="numpy")
    assert len(rows) == 1 and rows[0].label == "faulted-grid"


# ---------------------------------------------------------------------------
# Compile cache: introspection + retrace pins for the new kernels
# ---------------------------------------------------------------------------

def test_compile_cache_stats_structure():
    wl = websearch_workload(8, 0.4, 200, BPS, d_hat=2, seed=9)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                           recfg_frac=RECFG)
    run_sweep([SweepCase(s, wl, "single_hop", "v")], BPS, backend="jax")
    stats = compile_cache_stats()
    for kernel in ("twohop_dense", "twohop_sparse", "singlehop",
                   "twohop_fct"):
        assert kernel in stats
        entry = stats[kernel]
        assert set(entry) == {"traces", "calls", "hits", "shape_buckets"}
        assert entry["hits"] == max(entry["calls"] - entry["traces"], 0)
        assert len(entry["shape_buckets"]) <= max(entry["calls"], 1)
    assert stats["singlehop"]["calls"] >= 1


def test_singlehop_kernel_no_retrace(assert_no_retrace):
    wl = websearch_workload(8, 0.4, 200, BPS, d_hat=2, seed=9)
    s = vermilion_schedule(wl.demand_matrix(), k=3, d_hat=2,
                           recfg_frac=RECFG)
    cases = [SweepCase(s, wl, "single_hop", "v")]
    run_sweep(cases, BPS, backend="jax")          # compile (or cache hit)
    with assert_no_retrace(kernels=("singlehop",)):
        for _ in range(3):
            run_sweep(cases, BPS, backend="jax")


def test_adaptive_jax_no_retrace(assert_no_retrace):
    """The adaptive path serves through the shared singlehop kernel —
    repeated same-shape runs must reuse the compiled executable."""
    case = AdaptiveCase(wl=_wl(61, horizon=450), d_hat=3, epoch_slots=150,
                        label="pin")
    run_adaptive([case], bits_per_slot=BPS, backend="jax")
    with assert_no_retrace(kernels=("singlehop",)):
        for _ in range(2):
            run_adaptive([case], bits_per_slot=BPS, backend="jax")


def test_twohop_fct_kernel_no_retrace(assert_no_retrace):
    wl = websearch_workload(7, 0.4, 150, BPS, d_hat=2, seed=4)
    s = oblivious_schedule(7, d_hat=2, recfg_frac=RECFG)
    cases = [SweepCase(s, wl, "rotorlb", "r")]
    run_sweep(cases, BPS, backend="jax")
    with assert_no_retrace(kernels=("twohop_fct",)):
        for _ in range(3):
            run_sweep(cases, BPS, backend="jax")


# ---------------------------------------------------------------------------
# Jittable estimation ops
# ---------------------------------------------------------------------------

def test_fleet_update_quantize_jax_parity():
    """On integer-friendly grids the f32 device round matches the numpy
    fleet pipeline tick-for-tick."""
    n, k = 8, 3
    rng = np.random.default_rng(0)
    # demand in whole quantizer units so f32 normalization is exact
    unit = BPS * k / (k - 1)
    period = (rng.integers(0, 50, size=(n, n)) * unit).astype(np.float64)
    fleet = TrafficEstimator.fleet(n, alpha=0.5)
    ref_ewma = fleet.update(period)
    ref_q = quantize_row(ref_ewma, k, BPS)
    ewma_j, q_j = fleet_update_quantize_jax(
        np.zeros((n, n)), period, alpha=0.5, k=k, bits_per_slot=BPS)
    assert np.array_equal(np.asarray(q_j), ref_q)
    assert np.allclose(np.asarray(ewma_j), ref_ewma, rtol=1e-6)
    deq_np = dequantize(ref_q, k, BPS)
    deq_j = np.asarray(dequantize_jax(q_j, k, BPS))
    assert np.allclose(deq_j, deq_np, rtol=1e-6)


def test_fleet_update_quantize_jax_rejects_bad_k():
    with pytest.raises(ValueError):
        fleet_update_quantize_jax(np.zeros((4, 4)), np.zeros((4, 4)),
                                  alpha=0.3, k=1, bits_per_slot=BPS)


# ---------------------------------------------------------------------------
# Padded slot-circuit export
# ---------------------------------------------------------------------------

def test_slot_circuits_padded_matches_ragged():
    s = vermilion_schedule(
        websearch_workload(9, 0.5, 200, BPS, d_hat=2, seed=3)
        .demand_matrix(), k=3, d_hat=2, recfg_frac=RECFG)
    plans = s.slot_circuits(c=2.0)
    pid, cap = s.slot_circuits_padded(c=2.0, pair_base=81, j_pad=16)
    assert pid.shape == cap.shape and pid.shape[0] == s.n_slots
    assert pid.shape[1] % 16 == 0
    assert pid.dtype == np.int32 and cap.dtype == np.float32
    n = s.n
    for t, (src, dst, w) in enumerate(plans):
        j = len(src)
        assert np.array_equal(pid[t, :j], 81 + src * n + dst)
        assert np.allclose(cap[t, :j], w)
        # padding is an exact no-op: pair_base id, zero capacity
        assert (pid[t, j:] == 81).all()
        assert (cap[t, j:] == 0.0).all()
