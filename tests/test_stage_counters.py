"""The staging counters on the ``fabric.stage`` span of the two-hop jax
batches: the capacity table's time on dense and sparse batches, the support
lookup table's time on sparse batches only (with the lookup table's plans
and support they time), and kernel inputs and results unchanged by the
counting."""
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


def _run(cases, kernel):
    """Serve ``cases`` on ``kernel``; returns the results, the stage
    span's record and the arrays handed to the kernel."""
    fns = sim._jax_fns()
    name = f"twohop_{kernel}"
    real, seen = fns[name], {}

    def spy(*args):
        seen["inputs"] = [np.asarray(a) for a in args]
        return real(*args)

    fns[name] = spy
    try:
        res = sim._twohop_batch_jax(cases, BPS, ["rotorlb", "vlb"],
                                    kernel=kernel)
    finally:
        fns[name] = real
    stage = next(r for r in reversed(tracing.records())
                 if r.name == "fabric.stage")
    return res, stage, seen["inputs"]


def test_periods_multiply_into_plans():
    cases = _cases()
    assert [s.n_slots for s, _ in cases] == [3, 4]
    _, stage, inputs = _run(cases, "sparse")
    H = max(wl.horizon for _, wl in cases)
    plan_idx, p_row, p_valid = inputs[5], inputs[6], inputs[9]
    assert len(np.unique(plan_idx[:H])) == 12             # lcm(3, 4)
    assert p_row.shape[0] == 16                           # power-of-two pad
    assert int(plan_idx.max()) == 11
    # the largest merged plan: 4 and 3 shifts of 13 circuits, before the
    # _PAD_J pad
    assert int(p_valid.sum(axis=1).max()) == (4 + 3) * 13
    assert p_row.shape[1] == sim._pad_to((4 + 3) * 13, sim._PAD_J)
    assert stage.attrs["lut_ns"] > 0


@pytest.mark.parametrize("kernel", ["dense", "sparse"])
def test_caps_counters_on_every_two_hop_batch(kernel):
    cases = _cases()
    _, stage, inputs = _run(cases, kernel)
    caps_flat = inputs[0]
    assert caps_flat.dtype == np.float32
    assert caps_flat.shape == (3 + 4, 13, 13)
    assert stage.attrs["caps_ns"] > 0
    assert CAPS <= set(stage.attrs)
    if kernel == "sparse":
        assert LUT <= set(stage.attrs)
    else:
        assert not LUT & set(stage.attrs)


def test_counted_time_is_inside_the_stage_span():
    _, stage, _ = _run(_cases(), "sparse")
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
    res, _, inputs = _run(cases, kernel)
    monkeypatch.setattr(sim, "span", _null_span)
    bare, _, bare_inputs = _run(cases, kernel)
    assert len(inputs) == len(bare_inputs)
    for a, b in zip(inputs, bare_inputs):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for r, b in zip(res, bare):
        assert r.delivered_bits == b.delivered_bits
        assert r.avg_hops == b.avg_hops
        assert r.utilization == b.utilization
