"""The staging counters on the ``fabric.stage`` span of the two-hop jax
batches: the capacity table's time on dense and sparse batches, the support
lookup table's time on sparse batches only (with the lookup table's plans
and support they time), and kernel inputs and results unchanged by the
counting.  A batch stages each distinct schedule once (``caps_tables``):
its rows, lookup table and kernel outputs are those of one float64 table
per case."""
from contextlib import contextmanager

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import simulator as sim  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.core.schedule import oblivious_schedule  # noqa: E402
from repro.core.simulator import websearch_workload  # noqa: E402

BPS = 100e9 * 4.5e-6
RECFG = 1 / 9
CAPS = {"caps_ns"}
LUT = {"lut_ns"}


def _cases(n=13, d_hats=(4, 3), horizon=60, seed=5):
    """One oblivious case per plane count: at n = 13, d_hat 4 and 3 give
    periods of 3 and 4 slots."""
    wl = websearch_workload(n, 0.4, horizon, BPS, d_hat=2, seed=seed)
    return [(oblivious_schedule(n, d_hat=d, recfg_frac=RECFG), wl)
            for d in d_hats]


def _modes(cases):
    return [("rotorlb", "vlb")[b % 2] for b in range(len(cases))]


def _run(cases, kernel):
    """Serve ``cases`` on ``kernel``; returns the results, the stage
    span's record, the arrays handed to the kernel and the batch's staged
    capacity table and per-slot row index (``caps_flat``, ``cap_idx``),
    which the sparse kernel reads only through its plan tables."""
    fns = sim._jax_fns()
    name = f"twohop_{kernel}"
    real, seen = fns[name], {}
    real_inputs = sim._jax_batch_inputs

    def spy(*args):
        seen["inputs"] = [np.asarray(a) for a in args]
        return real(*args)

    def staged(*args):
        out = real_inputs(*args)
        seen["tables"] = (out[2], out[3])
        return out

    fns[name] = spy
    sim._jax_batch_inputs = staged
    try:
        res = sim._twohop_batch_jax(cases, BPS, _modes(cases),
                                    kernel=kernel)
    finally:
        fns[name] = real
        sim._jax_batch_inputs = real_inputs
    stage = next(r for r in reversed(tracing.records())
                 if r.name == "fabric.stage")
    return res, stage, seen["inputs"], seen["tables"]


def test_periods_multiply_into_plans():
    cases = _cases()
    assert [s.n_slots for s, _ in cases] == [3, 4]
    _, stage, inputs, _ = _run(cases, "sparse")
    H = max(wl.horizon for _, wl in cases)
    plan_idx, p_v, p_cap = inputs[3], inputs[4], inputs[5]
    assert len(np.unique(plan_idx[:H])) == 12             # lcm(3, 4)
    assert p_v.shape[0] == 16                             # power-of-two pad
    assert int(plan_idx.max()) == 11
    # every merged plan: 4 and 3 shifts of 13 circuits, in rows of
    # D = 4 slots (the d_hat = 4 case's degree)
    assert p_v.shape[1:] == (4, 2 * 13)
    assert (p_cap[:12] > 0).sum(axis=(1, 2)).tolist() == [(4 + 3) * 13] * 12
    assert not p_cap[12:].any()
    assert stage.attrs["lut_ns"] > 0
    assert stage.attrs["plan_d"] == 4
    assert stage.attrs["plan_fill"] == pytest.approx((4 + 3) / (2 * 4))


@pytest.mark.parametrize("kernel", ["dense", "sparse"])
def test_caps_counters_on_every_two_hop_batch(kernel):
    cases = _cases()
    _, stage, inputs, (caps_flat, _) = _run(cases, kernel)
    assert caps_flat.dtype == np.float32
    assert caps_flat.shape == (3 + 4, 13, 13)
    assert stage.attrs["caps_ns"] > 0
    assert CAPS <= set(stage.attrs)
    if kernel == "sparse":
        assert LUT <= set(stage.attrs)
        # the sparse kernel takes its capacities from the plan tables
        assert not any(a.shape == caps_flat.shape for a in inputs)
    else:
        assert not LUT & set(stage.attrs)
        assert inputs[0].tobytes() == caps_flat.tobytes()


def test_counted_time_is_inside_the_stage_span():
    _, stage, _, _ = _run(_cases(), "sparse")
    assert (stage.attrs["caps_ns"] + stage.attrs["lut_ns"]
            < stage.t1_ns - stage.t0_ns)


class _Null:
    def add(self, key, n):
        pass


@contextmanager
def _null_span(name, **attrs):
    yield _Null()


@pytest.mark.parametrize("kernel", ["dense", "sparse"])
def test_counting_leaves_inputs_and_results_bit_identical(kernel,
                                                          monkeypatch):
    cases = _cases(horizon=90, seed=7)
    res, _, inputs, _ = _run(cases, kernel)
    monkeypatch.setattr(sim, "span", _null_span)
    bare, _, bare_inputs, _ = _run(cases, kernel)
    assert len(inputs) == len(bare_inputs)
    for a, b in zip(inputs, bare_inputs):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for r, b in zip(res, bare):
        assert r.delivered_bits == b.delivered_bits
        assert r.avg_hops == b.avg_hops
        assert r.utilization == b.utilization


N4 = 13


def _four(shared: bool):
    """Two loads x rotorlb / vlb on d_hat = 4 oblivious schedules (as a
    two-hop cell's request): one schedule object for all four cases, or
    one built per case (equal content, distinct objects)."""
    wls = [websearch_workload(N4, load, 60, BPS, d_hat=2, seed=5)
           for load in (0.3, 0.6)]
    one = oblivious_schedule(N4, d_hat=4, recfg_frac=RECFG)
    return [(one if shared else
             oblivious_schedule(N4, d_hat=4, recfg_frac=RECFG), wl)
            for wl in wls for _ in range(2)]


# batch, expected capacity-table rows (a d_hat = 4 period is 3 slots at
# n = 13), expected distinct tables
BATCHES = {"shared": (lambda: _four(True), 3, 1),
           "equal": (lambda: _four(False), 3, 1),
           "mixed": (_cases, 3 + 4, 2)}


def _per_case_caps(cases):
    """The capacity table as one float64 table per case: the rows
    concatenated and cast to float32, each case's row offsets, and each
    case's float64 table."""
    caps64 = [s.capacity_per_slot(BPS) for s, _ in cases]
    ns = np.array([c.shape[0] for c in caps64])
    offs = np.concatenate([[0], np.cumsum(ns[:-1])])
    return (np.concatenate(caps64).astype(np.float32), offs, ns, caps64)


def _per_case_idx(offs, ns, H, H_pad):
    cap_idx = np.zeros((H_pad, len(ns)), dtype=np.int32)
    cap_idx[:H] = offs[None, :] + (np.arange(H)[:, None] % ns[None, :])
    return cap_idx


def _per_case_lut(cases, H, H_pad):
    """The sparse lookup table built from one float64 table per case."""
    caps_flat, offs, ns, caps64 = _per_case_caps(cases)
    return sim._sparse_plan_lut(
        [[np.nonzero(c) for c in caps] for caps in caps64], caps_flat,
        _per_case_idx(offs, ns, H, H_pad), H, _Null())


@pytest.mark.parametrize("batch", sorted(BATCHES))
@pytest.mark.parametrize("kernel", ["dense", "sparse"])
def test_each_distinct_schedule_is_staged_once(kernel, batch):
    make, rows, tables = BATCHES[batch]
    cases = make()
    _, stage, _, (caps_flat, cap_idx) = _run(cases, kernel)
    assert caps_flat.dtype == np.float32
    assert caps_flat.shape == (rows, N4, N4)
    assert stage.attrs["caps_tables"] == tables
    H = max(wl.horizon for _, wl in cases)
    for b, (s, _) in enumerate(cases):
        want = s.capacity_per_slot(BPS).astype(np.float32)
        for slot in range(H):
            got = caps_flat[cap_idx[slot, b]]
            assert (got.tobytes()
                    == want[slot % s.n_slots].tobytes()), (b, slot)


@pytest.mark.parametrize("batch", sorted(BATCHES))
@pytest.mark.parametrize("kernel", ["dense", "sparse"])
def test_kernel_outputs_match_per_case_tables(kernel, batch):
    cases = BATCHES[batch][0]()
    _, _, inputs, (_, staged_idx) = _run(cases, kernel)
    caps_flat, offs, ns, _ = _per_case_caps(cases)
    H, H_pad = max(wl.horizon for _, wl in cases), len(staged_idx)
    if kernel == "sparse":
        per_case = [*inputs[:3], *_per_case_lut(cases, H, H_pad), inputs[-1]]
    else:
        per_case = [caps_flat, _per_case_idx(offs, ns, H, H_pad), *inputs[2:]]
    fn = sim._jax_fns()[f"twohop_{kernel}"]
    (delivered, second), carry = fn(*inputs)
    (delivered0, second0), carry0 = fn(*per_case)
    for a, b in [(delivered, delivered0), (second, second0),
                 *zip(carry, carry0)]:
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_lookup_table_matches_per_case_tables(batch):
    cases = BATCHES[batch][0]()
    _, _, inputs, _ = _run(cases, "sparse")
    H = max(wl.horizon for _, wl in cases)
    want = _per_case_lut(cases, H, len(inputs[3]))
    assert len(want) == 4
    for got, ref in zip(inputs[3:7], want):
        assert got.dtype == ref.dtype and np.array_equal(got, ref)


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_in_table_names_each_filled_out_slot_once(batch):
    cases = BATCHES[batch][0]()
    _, stage, inputs, _ = _run(cases, "sparse")
    plan_idx, p_v, p_cap, p_in = inputs[3:7]
    P, D, BN = p_v.shape
    n = BN // len(cases)
    H = max(wl.horizon for _, wl in cases)
    used = len(np.unique(plan_idx[:H]))
    filled = p_cap > 0
    assert stage.attrs["plan_d"] == D
    assert stage.attrs["plan_fill"] == pytest.approx(
        filled[:used].sum() / (used * BN * D))
    for p in range(P):
        named = p_in[p] < D * BN
        ids = p_in[p][named]                  # flat out-slots k * BN + row
        # each filled out-slot once, and no unused one
        assert np.array_equal(np.sort(ids), np.flatnonzero(filled[p]))
        # an in-slot of row (b, v) names a circuit of case b into v
        dest = np.nonzero(named)[1]
        assert np.array_equal(p_v[p].reshape(-1)[ids], dest % n)
        assert np.array_equal(ids % BN // n, dest // n)


@pytest.mark.parametrize("kernel", [None, "dense", "sparse"])
def test_sanitizer_checks_a_float64_table_per_case(kernel, monkeypatch):
    from repro.analysis.sanitize import Sanitizer
    cases = _four(True)
    seen = []
    real = Sanitizer.check_caps_dense

    def spy(self, caps, *args, **kw):
        seen.append((caps.dtype, caps.shape))
        return real(self, caps, *args, **kw)

    monkeypatch.setattr(Sanitizer, "check_caps_dense", spy)
    san = Sanitizer()
    res = sim._twohop_batch_jax(cases, BPS, _modes(cases), kernel=kernel,
                                san=san)
    assert len(res) == 4
    ns = cases[0][0].n_slots
    assert seen == [(np.float64, (ns, N4, N4))] * 4
    assert san.counts["caps_served"] == 4


def test_sanitizer_refuses_a_served_table_off_by_one_ulp():
    from repro.analysis.sanitize import SanitizeError, Sanitizer
    s = _four(True)[0][0]
    caps = s.capacity_per_slot(BPS)
    served = caps.astype(np.float32)
    Sanitizer().check_caps_served(served, caps)
    served[0][caps[0] > 0] = np.nextafter(served[0][caps[0] > 0], 0)
    with pytest.raises(SanitizeError):
        Sanitizer().check_caps_served(served, caps)


def test_numpy_batch_on_one_schedule_is_unchanged():
    shared, equal = _four(True), _four(False)
    modes = _modes(shared)
    got = sim._simulate_batch(shared, BPS, modes)
    same = sim._simulate_batch(equal, BPS, modes)
    for (s, wl), m, r, e in zip(shared, modes, got, same):
        assert np.array_equal(r.fct_slots, e.fct_slots)
        assert r.delivered_bits == e.delivered_bits
        assert r.avg_hops == e.avg_hops
        ref = sim.simulate_reference(s, wl, BPS, mode=m)
        assert np.array_equal(r.fct_slots, ref.fct_slots)
        assert np.isclose(r.delivered_bits, ref.delivered_bits, rtol=1e-6)
