"""Traffic generator of the benchmark, copied from the program so that no
change to the program can change the traffic a cell sends.

``websearch_workload``: Poisson flow arrivals at a fraction of each rack's
egress capacity, DCTCP websearch flow sizes (Alizadeh et al., SIGCOMM
2010), every rack sending to one other by a rack permutation.

``fabric_bench/tests/test_gen.py`` holds the copy bit-equal to the
program's generator.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# DCTCP websearch flow-size CDF (bytes, cumulative probability)
WEBSEARCH_CDF = np.array([
    (6_000, 0.15), (13_000, 0.30), (19_000, 0.40), (33_000, 0.53),
    (53_000, 0.60), (133_000, 0.70), (667_000, 0.80), (1_467_000, 0.90),
    (2_107_000, 0.95), (6_667_000, 0.98), (20_000_000, 1.00),
])


@dataclass(frozen=True)
class Flows:
    """One workload: per-flow source, destination, size in bits and arrival
    slot, sorted by arrival."""
    src: np.ndarray
    dst: np.ndarray
    size: np.ndarray
    arrival: np.ndarray
    n: int
    horizon: int

    def demand_matrix(self) -> np.ndarray:
        """Average offered bits per slot for every pair."""
        m = np.zeros((self.n, self.n))
        np.add.at(m, (self.src, self.dst), self.size)
        return m / self.horizon


def _sample_websearch(rng: np.random.Generator, size: int) -> np.ndarray:
    """Flow sizes in bits, piecewise linear in the websearch CDF."""
    u = rng.random(size)
    sizes_b, probs = WEBSEARCH_CDF[:, 0], WEBSEARCH_CDF[:, 1]
    lo_p = np.concatenate([[0.0], probs[:-1]])
    lo_s = np.concatenate([[100.0], sizes_b[:-1]])
    idx = np.searchsorted(probs, u, side="left")
    frac = (u - lo_p[idx]) / (probs[idx] - lo_p[idx])
    return (lo_s[idx] + frac * (sizes_b[idx] - lo_s[idx])) * 8.0  # bits


def websearch_workload(n: int, load: float, horizon: int,
                       bits_per_slot: float, d_hat: int = 1,
                       seed: int = 0) -> Flows:
    rng = np.random.default_rng(seed)
    mean_size = float(np.mean(_sample_websearch(rng, 20000)))
    lam = load * d_hat * bits_per_slot / mean_size  # flows/slot/node
    srcs, dsts, sizes, arrs = [], [], [], []
    shift = 1 + int(rng.integers(0, n - 1))
    perm = (np.arange(n) + shift) % n
    for s in range(n):
        k = rng.poisson(lam * horizon)
        arrs.append(rng.integers(0, horizon, size=k))
        srcs.append(np.full(k, s))
        sizes.append(_sample_websearch(rng, k))
        dsts.append(np.full(k, perm[s]))
    order = np.argsort(np.concatenate(arrs), kind="stable")
    return Flows(
        src=np.concatenate(srcs)[order].astype(np.int64),
        dst=np.concatenate(dsts)[order].astype(np.int64),
        size=np.concatenate(sizes)[order],
        arrival=np.concatenate(arrs)[order].astype(np.int64),
        n=n, horizon=horizon)
