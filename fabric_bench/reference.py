"""Plain reference of the fabric simulator's semantics, in float64.

It imports nothing of the program and is written for plainness, one dense
(n, n) slot at a time:

* Circuits.  A schedule is a (T, n) array of matchings, ``perms[t, u] = v``
  a circuit u -> v; ``d_hat`` matchings run together (one per port plane),
  each circuit carrying ``w = bits_per_slot * (1 - recfg_frac)`` bits a
  slot, self-loops dropped, parallel circuits adding up; slot ``t`` runs
  period slot ``t % n_slots``.
* Single hop.  Per (src, dst) queue: arrivals join, then
  ``tx = min(queue, capacity)`` leaves.  Each pair's bits are shared by
  its active flows by processor sharing (water filling); a flow completes
  when at most 1e-6 of its bits remain, at ``slot + 1 - arrival``.
* Two hop (rotorlb, vlb).  Relay buckets (at, dst) drain first over the
  circuits; then the direct hop (rotorlb only); then the leftover capacity
  of each source sprays its queue over its circuits in proportion to link
  share and queue share, bits whose relay is their destination landing at
  once.

The reference builds the oblivious round robin itself.  Vermilion
schedules are the program's, built by its public constructor: they are
held to Algorithm 1's guarantee (:func:`perm_violations`,
:func:`vermilion_violations`) before the reference serves them, so
construction is checked by that property, not against a second schedule.

``rnd`` rounds state after every update: ``None`` keeps float64, and
:func:`round_bf16` is the lower-precision control of ``control.py``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .gen import Flows

FCT_DONE = 1e-6          # bits left at which a flow counts as complete
CREDIT_MIN = 1e-9        # smallest per-slot delivery the flows are credited
_EPS = 1e-12


def round_bf16(x):
    """Round to bfloat16 and back: the control's precision."""
    import ml_dtypes
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def _keep(x):
    return x


def saturate(m: np.ndarray, iters: int = 200) -> np.ndarray:
    """Sinkhorn projection toward a doubly stochastic matrix."""
    m = np.asarray(m, dtype=np.float64).copy()
    if (m <= 0).all():
        return m
    m = np.where(m <= 0, 1e-12, m)
    for _ in range(iters):
        m /= m.sum(axis=1, keepdims=True)
        m /= m.sum(axis=0, keepdims=True)
    return m


def oblivious_perms(n: int) -> np.ndarray:
    """RotorNet's round robin: matching t connects u -> (u + t + 1) mod n."""
    return (np.arange(n)[None, :] + np.arange(1, n)[:, None]) % n


def perm_violations(perms: np.ndarray) -> int:
    """Matchings of a schedule that are not permutations."""
    perms = np.asarray(perms)
    n = perms.shape[1]
    return int((np.sort(perms, axis=1) != np.arange(n)[None, :]).any(axis=1)
               .sum())


def vermilion_violations(perms: np.ndarray, m: np.ndarray, k: int) -> int:
    """Count the ways a Vermilion schedule built from demand ``m``
    (normalized by :func:`saturate`) breaks Algorithm 1 besides its
    matchings (:func:`perm_violations`): k * n matchings, and for every
    pair u != v more circuits per period than its scaled demand
    ``(k - 1) n m_uv`` (the rounding keeps within 1 of it, and the
    oblivious residual adds one circuit per pair)."""
    perms = np.asarray(perms)
    T, n = perms.shape
    bad = int(T != k * n)
    counts = np.zeros((n, n), dtype=np.int64)
    np.add.at(counts, (np.tile(np.arange(n), T), perms.reshape(-1)), 1)
    norm = saturate(m)
    np.fill_diagonal(norm, 0.0)
    need = np.floor((k - 1) * n * norm - 1e-6) + 1
    off = ~np.eye(n, dtype=bool)
    return bad + int((counts < need)[off].sum())


@dataclass
class Plan:
    """One periodic schedule: ``perms`` (T, n), ``d_hat`` matchings a slot,
    ``w`` bits per circuit and slot."""
    perms: np.ndarray
    d_hat: int
    w: float
    n: int

    def caps(self, slot: int) -> np.ndarray:
        n_slots = -(-self.perms.shape[0] // self.d_hat)
        p = slot % n_slots
        blk = self.perms[p * self.d_hat:(p + 1) * self.d_hat]
        cap = np.zeros((self.n, self.n))
        np.add.at(cap, (np.tile(np.arange(self.n), len(blk)), blk.reshape(-1)),
                  self.w)
        np.fill_diagonal(cap, 0.0)
        return cap


def _arrival_bounds(flows: Flows) -> np.ndarray:
    return np.searchsorted(flows.arrival, np.arange(flows.horizon + 1))


def serve_singlehop(flows: Flows, plan: Plan, pairs: np.ndarray, rnd=None):
    """Single-hop serving.  Returns the bits delivered in each slot and,
    for each of ``pairs`` ((P, 2) src, dst), the bits it got each slot."""
    rnd = rnd or _keep
    n, H = flows.n, flows.horizon
    bnd = _arrival_bounds(flows)
    size = rnd(flows.size)
    voq = np.zeros((n, n))
    delivered = np.zeros(H)
    tracked = np.zeros((H, len(pairs)))
    pu, pv = pairs[:, 0], pairs[:, 1]
    for slot in range(H):
        a, b = bnd[slot], bnd[slot + 1]
        if b > a:
            np.add.at(voq, (flows.src[a:b], flows.dst[a:b]), size[a:b])
            voq = rnd(voq)
        tx = np.minimum(voq, rnd(plan.caps(slot)))
        voq = rnd(voq - tx)
        delivered[slot] = tx.sum()
        tracked[slot] = tx[pu, pv]
    return delivered, tracked


def serve_twohop(flows: Flows, plan: Plan, direct: bool, rnd=None):
    """Two-hop serving (rotorlb with ``direct``, vlb without).  Returns the
    bits delivered and the bits that took a second hop, each slot."""
    rnd = rnd or _keep
    n, H = flows.n, flows.horizon
    bnd = _arrival_bounds(flows)
    size = rnd(flows.size)
    voq = np.zeros((n, n))
    relay = np.zeros((n, n))                     # bucket totals [at, dst]
    delivered, second = np.zeros(H), np.zeros(H)
    off = 1.0 - np.eye(n)
    for slot in range(H):
        a, b = bnd[slot], bnd[slot + 1]
        if b > a:
            np.add.at(voq, (flows.src[a:b], flows.dst[a:b]), size[a:b])
            voq = rnd(voq)
        cap = rnd(plan.caps(slot))
        send1 = np.minimum(relay, cap)           # relayed bits go first
        relay = rnd(relay - send1)
        cap = rnd(cap - send1)
        got = send1.sum()
        second[slot] = got
        if direct:
            tx = np.minimum(voq, cap)
            voq = rnd(voq - tx)
            cap = rnd(cap - tx)
            got += tx.sum()
        leftover = cap.sum(axis=1)
        queue = voq.sum(axis=1)
        send = np.minimum(leftover, queue)
        link = np.where(leftover[:, None] > _EPS,
                        cap / np.maximum(leftover, _EPS)[:, None], 0.0)
        share = np.where(queue[:, None] > _EPS,
                         voq / np.maximum(queue, _EPS)[:, None], 0.0)
        moved = rnd((send[:, None] * link).T @ share)   # [relay v, dst d]
        voq = rnd(np.maximum(voq - send[:, None] * share, 0.0))
        got += np.trace(moved)
        relay = rnd(relay + moved * off)
        delivered[slot] = got
    return delivered, second


def pair_fcts(size: np.ndarray, arrival: np.ndarray,
              tx: np.ndarray) -> np.ndarray:
    """FCTs of one pair's flows (in arrival order) under processor
    sharing of the bits ``tx`` the pair got each slot."""
    F = len(size)
    fct = np.full(F, np.inf)
    rem = size.astype(np.float64).copy()
    active: list[int] = []
    nxt = 0
    for slot in np.nonzero(tx > CREDIT_MIN)[0]:
        while nxt < F and arrival[nxt] <= slot:
            active.append(nxt)
            nxt += 1
        if not active:
            continue
        rems = rem[active]
        s = min(float(tx[slot]), float(rems.sum()))
        order = np.argsort(rems)
        sorted_r = rems[order]
        csum = np.cumsum(sorted_r)
        m = len(active)
        fill = csum + sorted_r * np.arange(m - 1, -1, -1)
        j = int(np.searchsorted(fill, s, side="left"))
        level = (sorted_r[-1] if j >= m
                 else (s - (csum[j - 1] if j else 0.0)) / (m - j))
        left = rems - np.minimum(rems, level)
        rem[active] = left
        still = []
        for f, r in zip(active, left):
            if r <= FCT_DONE:
                fct[f] = slot + 1 - arrival[f]
            else:
                still.append(f)
        active = still
    return fct
