"""The benchmark's traffic generator is a copy of the program's: it
reproduces the program's generator bit for bit at a small size."""
import numpy as np
import pytest

from fabric_bench import gen

sim = pytest.importorskip("repro.core.simulator")

BITS = 100e9 * 4.5e-6


def _same(a, b) -> None:
    for k in ("src", "dst", "size", "arrival"):
        x, y = getattr(a, k), getattr(b, k)
        assert x.dtype == y.dtype and np.array_equal(x, y), k
    assert (a.n, a.horizon) == (b.n, b.horizon)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_websearch_workload_copy(seed):
    args = (12, 0.45, 300, BITS)
    _same(gen.websearch_workload(*args, d_hat=3, seed=seed),
          sim.websearch_workload(*args, d_hat=3, seed=seed,
                                 pattern="rack_permutation"))


def test_websearch_cdf_copy():
    assert np.array_equal(gen.WEBSEARCH_CDF, sim.WEBSEARCH_CDF)


def test_demand_matrix_copy():
    f = gen.websearch_workload(9, 0.3, 200, BITS, d_hat=2, seed=6)
    w = sim.websearch_workload(9, 0.3, 200, BITS, d_hat=2, seed=6)
    assert np.array_equal(f.demand_matrix(), w.demand_matrix())
