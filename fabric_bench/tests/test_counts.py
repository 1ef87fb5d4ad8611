"""Roofline counts and the peaks table: hand-counted shapes, counts that do
not move when the program's formulation does, and no default device."""
import numpy as np
import pytest

from fabric_bench import harness, peaks
from fabric_bench.kernels import singlehop, twohop_dense

CASE = dict(n=4, d_hat=2, horizon=10, flows=7)


def test_singlehop_count_by_hand():
    # 4 * 2 circuits over 10 slots = 80 circuit-slots
    flops, nbytes = singlehop.count([CASE])
    assert flops == 2 * 80 + 7
    assert nbytes == 5 * 80 + 8 * 7


def test_twohop_dense_count_by_hand():
    # each of the 80 circuit-slots sprays over n = 4 destinations
    flops, nbytes = twohop_dense.count([CASE, CASE])
    assert flops == 2 * ((2 * 4 + 6) * 80 + 7)
    assert nbytes == 2 * (8 * 10 + 8 * 7)


def test_twohop_count_is_over_the_support_not_n_cubed():
    big = dict(n=256, d_hat=4, horizon=1000, flows=0)
    flops, _ = twohop_dense.count([big])
    assert flops < 2 * 256 ** 3 * 1000 / 16


def test_counts_do_not_follow_the_formulation(monkeypatch):
    """The counts read the problem's shapes: changing the dense/sparse
    crossover or the padding buckets leaves them as they were."""
    sim = pytest.importorskip("repro.core.simulator")
    bench = harness.load_json(harness.ROOT.parent / "BENCHMARK.json")
    cell, _ = harness.load_cell(bench, "ws256_twohop", harness.ROOT.parent)
    cell.config["n"] = 12
    cell.traffic["workload"]["horizon"] = 150
    req = harness.make_request(cell, harness.make_pool(cell), 11, 0)
    before = {k: m.count(harness.kernel_batches(cell, req)[m.BATCH])
              for k, m in (("s", singlehop), ("t", twohop_dense))}
    monkeypatch.setattr(sim, "_TWOHOP_DENSE_MAX_N", 4)
    monkeypatch.setattr(sim, "_PAD_H", 512)
    monkeypatch.setattr(sim, "_PAD_K", 128)
    monkeypatch.setattr(sim, "_PAD_J", 256)
    after = {k: m.count(harness.kernel_batches(cell, req)[m.BATCH])
             for k, m in (("s", singlehop), ("t", twohop_dense))}
    assert before == after
    t_flops, _ = after["t"]
    horizons = [f.horizon for f in req.flows]
    assert t_flops == sum((2 * 12 + 6) * 12 * 4 * h
                          + int((f.arrival < h).sum())
                          for f, h in zip(req.flows, horizons)) * 2


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "cpu")


def test_least_seconds_names_the_bound():
    t, bound = peaks.least_seconds(197e12, 1.0, "TPU v5 lite")
    assert bound == "flops" and np.isclose(t, 1.0)
    t, bound = peaks.least_seconds(1.0, 819e9, "TPU v5 lite")
    assert bound == "bytes" and np.isclose(t, 1.0)
