"""Flow-level timeslot simulator for periodic circuit-switched networks.

Replaces the paper's htsim packet-level simulation with an exact
fixed-duration-timeslot abstraction at flow granularity (DESIGN.md §9):
per (src, dst) virtual output queues, FIFO within a queue, transmissions
paused during reconfiguration (the (1 - recfg_frac) capacity factor).

Routing modes:
* ``single_hop``   — Vermilion / greedy / any traffic-aware schedule.
* ``rotorlb``      — RotorNet's two-hop load balancing: direct first,
                     leftover capacity offloads to relays; relayed traffic
                     has priority at the second hop.
* ``vlb``          — Sirius-style Valiant: all traffic takes two hops via
                     the currently-connected intermediates.

Simulator architecture
======================
The engine is array-programmed end to end; the only Python-level loop is
over timeslots, and a whole (schedule, workload, mode) sweep grid advances
through one slot loop with a leading batch axis:

1. **Precomputed arrival buckets.**  Flows (from every workload in the
   batch) are concatenated and sorted by arrival slot once; each slot's
   arrivals are a contiguous index range injected into the VOQ state with
   one ``np.add.at``.

2. **Sparse single-hop dynamics.**  A slot can only move bits over its
   <= n * d_hat circuits, so the single-hop engine touches nothing else:
   the periodic circuit support (pair ids + capacities, memoized per
   period-slot residue) drives O(B n d_hat) scalar gather/min/scatter ops
   per slot — no dense (B, n, n) work at all, and element-for-element
   identical VOQ dynamics to the reference engine.

3. **Circuit-sparse two-hop dynamics.**  rotorlb/vlb cases share one
   dense-VOQ loop (vlb masks the direct hop), but relay work is confined
   to the circuit support rows: maintained per-(at, dst) bucket totals
   skip empty relay buckets, the drain/deliver/offload transfers are
   compact (J, n) row operations (J <= B n d_hat) instead of the
   reference's O(n^3) tensors, and grouped ``add.reduceat`` recovers the
   per-node and per-destination reductions.

4. **Offset-based water-filling.**  Per-flow processor-sharing credit
   keeps active flows sorted by (pair, stored size) and exploits that a
   water-fill subtracts the *same* level from every surviving flow of a
   pair: per-pair offsets advance in O(1) (``true_rem = stored - off``),
   the level is solved on a bounded sorted-prefix pad with an exact
   fallback, and completions pop the sorted prefix via tombstone counters
   with periodic compaction.  No per-pair Python loop, no dict
   bookkeeping, and per-slot cost independent of queue depth.

5. **Sweep API.**  :func:`run_sweep` takes a list of
   ``(schedule, workload, mode)`` cases (see :class:`SweepCase`), batches
   single-hop and two-hop groups through the engines above, so one call
   evaluates an ``n × load × mode`` grid.  ``backend="jax"`` covers every
   routing mode with jitted ``jax.lax.scan`` kernels *including per-flow
   FCTs*: single-hop cases run the padded circuit-support ``singlehop``
   kernel, whose per-slot delivered amounts the host replays through the
   exact f64 flow-credit ledger (drain flags + ``_F32_DRAIN_REL``
   reconcile f32 serving with the ledger, so FCT multisets match the
   NumPy engine exactly on golden cases); small-n rotorlb/vlb batches run
   the ``twohop_fct`` kernel, which keeps the per-source relay
   attribution and emits per-slot delivered (src, dst) matrices for the
   same replay.  Larger two-hop batches fall back to the aggregate relay
   kernels, which carry relay state as per-(at, dst) bucket *totals* (the
   source-attribution axis exists only to credit flows, so it drops out
   of the aggregate dynamics exactly) and pick between a dense einsum
   formulation (small n) and padded circuit-support gathers +
   ``segment_sum`` over the same :class:`_SupportPlans` LUT the NumPy
   engine uses (large n); their ``fct_slots`` stay all-inf.  Kernels jit
   once per padded shape bucket through a module-level compile cache —
   repeated same-shape sweeps never retrace
   (:func:`compile_cache_stats` introspects traces / hits / buckets).

Backend selection
=================
``backend="numpy"`` (default) is exact f64, supports every feature —
faults, repair, ``collision="fullest"``, activation jitter, ``measured``
construction charging — and wins on one-off small grids where jit
compilation would dominate.  ``backend="jax"`` serves in f32 on the
accelerator and wins on repeated or wide grids (same padded shape →
compile once, then several-times-faster slot loops; the adaptive
disagreement sweep drops from minutes to seconds): both :func:`run_sweep`
and :func:`run_adaptive` accept it, and both emit per-flow FCT
percentiles (two-hop modes only up to ``_TWOHOP_FCT_MAX_N``).  The jax
adaptive path replays the control plane host-side (decision-identical to
numpy — the epoch counters are arrivals-only) and batches every case's
serving through ONE device scan; configurations needing per-slot host
decisions inside the serving loop stay NumPy-only: fault injection
raises ``NotImplementedError``, and repair / ``fullest`` / jitter raise
``ValueError``.  Aggregates match
numpy to f32 tolerance (~1e-3 relative); FCTs match exactly on
well-conditioned instances.

6. **Adaptive epoch layer.**  :func:`run_adaptive` (see
   :class:`AdaptiveCase`) closes the paper's estimation→schedule control
   loop on top of the per-slot engine: the horizon is partitioned into
   epochs, per-node VOQ byte counters harvested at each boundary feed the
   Appendix-A pipeline (EWMA → quantize → ring-AllGather → dequantize),
   and the recomputed ``vermilion_schedule`` is hot-swapped without
   resetting VOQ or flow state.  The control plane is *per node*: every
   ToR computes the next schedule from its own assembled matrix
   (``estimate_all_views`` + ``per_node_schedules``; identical views are
   built once, so a complete gather keeps the fabric consistent), and
   under a partial gather (``gather_steps < n - 1``) the merged port
   configuration is generally not a matching — ``_fabric_plan`` resolves
   output-port collisions (drop / lowest-index-wins / rotating receiver
   arbitration) and charges the contended capacity, with per-epoch
   disagreement and collision-loss accounting on :class:`AdaptiveRow`.
   Construction is optionally charged for real
   (``AdaptiveCase.construction_slots``): the new schedule only
   activates after the slots its construction consumed, with the stale
   schedule serving in the interim.  :func:`phase_shifting_workload`
   generates the non-stationary (phase-train) traffic that exercises it.

7. **Fault injection & degraded service.**  A timed
   :class:`repro.core.faults.FaultSchedule` threads failures through the
   sparse single-hop engine (``SweepCase.faults`` / ``simulate``) and the
   adaptive loop (``AdaptiveCase.faults``): dead planes, dead or flapping
   per-plane ports, graceful ToR drains (injection stops, forwarding
   continues until the VOQs empty — no bits lost), and abrupt ToR
   failures (rows/columns dark; the bits stranded in the dead node's
   VOQs are charged to an explicit ``fault_lost_bits`` ledger, and
   arrivals refused at a dead/draining ingress to ``fault_refused_bits``,
   so bit conservation closes as injected = delivered + queued +
   fault_lost with injected = offered - refused).  Failed circuits are
   masked per slot *after* collision arbitration (a dead input's
   configured claim still jams its output port — the conservative
   optical model), and bits queued toward a dead destination stay queued
   (capacity-side, like collision loss).  Reconfiguration itself is
   fault-shaped: only planes whose matching subsequence actually changed
   pay the ``reconfig_penalty_slots`` dark window (``planes_changed``),
   and with ``activation_jitter_slots > 0`` each ToR activates a new
   schedule at its own jittered slot, the data plane serving the mixed
   old/new port configuration through the transition with contention
   re-arbitrated per slot under the case's collision policy.  The
   control plane closes the loop when ``repair=True``: persistently
   silent gather rows mark drained/dead senders, and data-plane NACK
   counters (claims that held backlog but delivered nothing, aggregated
   per destination and per plane over an epoch) mark dead receivers and
   dead planes; detected failures are excised from the estimated matrix
   (``RingViews.excise``) and dead planes from the rebuild itself
   (schedules reconstructed over the surviving planes via
   ``_FabricPlan.plane_map``), so healthy ports reclaim the failed
   capacity through the ordinary rounding/Euler-split path.

The pre-vectorization engine is kept verbatim as
:func:`simulate_reference`; golden-trace tests pin the new engine to it on
small instances for all three modes (exact FCT equality; aggregate
quantities to ~ulp drift from the offset/bucket-total bookkeeping).

Invariants & analysis
=====================
The invariants the engines rely on are machine-checked two ways (see
:mod:`repro.analysis`):

* **Statically** — ``python -m repro.analysis.lint src tests`` enforces
  the hot-path rules by AST inspection: no dense fabric-sized
  ``(…, n, n)`` intermediates outside annotated sites (R1 — every
  deliberate dense structure here carries ``# lint: allow-dense``), jit
  hygiene for the scan kernels (R2 — scans live inside the module-level
  compile cache, never per-call), importorskip guards in jax tests (R3),
  and dtype discipline (R4).
* **At runtime** — every engine accepts ``sanitize=`` (or the
  ``REPRO_SANITIZE=1`` env var) and then self-checks per run: bits are
  conserved (injected = delivered + still-queued VOQ/relay state;
  collision loss and reconfiguration-dark windows are *capacity*-side in
  this model, so the bit ledger closes without them), every served slot
  support is a partial matching post-arbitration (per-port capacity
  within ``d_hat * bits_per_slot * (1 - recfg_frac)``), pre-merge
  per-node schedule rows are permutations, merged-plan collision loss
  never exceeds contested-claim capacity (``_FabricPlan.contested``),
  and processor-sharing credit closes against delivered bits.  The
  checks are read-only: a sanitized run is bit-identical to an
  unsanitized one (pinned in tests/test_analysis.py).
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field

import numpy as np

from ..analysis.sanitize import make_sanitizer
from .estimation import TrafficEstimator, estimate_all_views
from .faults import FaultSchedule, claims_fault_mask
from .schedule import (
    Schedule,
    effective_perms,
    oblivious_schedule,
    per_node_schedules,
    planes_changed,
    vermilion_schedule,
)
from .tracing import span
from .traffic import phase_train

__all__ = [
    "Workload",
    "websearch_workload",
    "phase_shifting_workload",
    "SimResult",
    "SweepCase",
    "SweepRow",
    "AdaptiveCase",
    "AdaptiveRow",
    "simulate",
    "simulate_reference",
    "run_sweep",
    "run_adaptive",
    "simulate_aggregate_jax",
    "compile_cache_stats",
    "WEBSEARCH_CDF",
]

# DCTCP websearch flow-size CDF (bytes, cumulative prob) — standard benchmark
WEBSEARCH_CDF = np.array([
    (6_000, 0.15), (13_000, 0.30), (19_000, 0.40), (33_000, 0.53),
    (53_000, 0.60), (133_000, 0.70), (667_000, 0.80), (1_467_000, 0.90),
    (2_107_000, 0.95), (6_667_000, 0.98), (20_000_000, 1.00),
])

_MODES = ("single_hop", "rotorlb", "vlb")


@dataclass(frozen=True)
class Workload:
    src: np.ndarray          # (F,) int
    dst: np.ndarray          # (F,) int
    size: np.ndarray         # (F,) float, bits
    arrival: np.ndarray      # (F,) int, slot index (sorted)
    n: int
    horizon: int             # slots

    @property
    def num_flows(self) -> int:
        return len(self.src)

    def arrival_matrix(self) -> np.ndarray:
        """(horizon, n, n) dense bits arriving per slot (small n only)."""
        a = np.zeros((self.horizon, self.n, self.n))  # lint: allow-dense
        np.add.at(a, (self.arrival, self.src, self.dst), self.size)
        return a

    def demand_matrix(self) -> np.ndarray:
        """Average offered rate per pair, bits/slot (Vermilion's input)."""
        m = np.zeros((self.n, self.n))
        np.add.at(m, (self.src, self.dst), self.size)
        return m / self.horizon


def _sample_websearch(rng: np.random.Generator, size: int) -> np.ndarray:
    u = rng.random(size)
    sizes_b, probs = WEBSEARCH_CDF[:, 0], WEBSEARCH_CDF[:, 1]
    lo_p = np.concatenate([[0.0], probs[:-1]])
    lo_s = np.concatenate([[100.0], sizes_b[:-1]])
    idx = np.searchsorted(probs, u, side="left")
    frac = (u - lo_p[idx]) / (probs[idx] - lo_p[idx])
    return (lo_s[idx] + frac * (sizes_b[idx] - lo_s[idx])) * 8.0  # bits


def websearch_workload(
    n: int,
    load: float,
    horizon: int,
    bits_per_slot: float,
    d_hat: int = 1,
    seed: int = 0,
    pattern: str = "rack_permutation",
) -> Workload:
    """Poisson flow arrivals at ``load`` fraction of each node's egress
    capacity (d_hat * bits_per_slot per slot), websearch sizes.

    ``rack_permutation`` is the paper's pair-wise rack communication pattern;
    ``uniform`` sprays destinations uniformly.
    """
    rng = np.random.default_rng(seed)
    mean_size = float(np.mean(_sample_websearch(rng, 20000)))
    lam = load * d_hat * bits_per_slot / mean_size  # flows/slot/node
    srcs, dsts, sizes, arrs = [], [], [], []
    shift = 1 + int(rng.integers(0, n - 1))
    perm = (np.arange(n) + shift) % n
    for s in range(n):
        k = rng.poisson(lam * horizon)
        t = rng.integers(0, horizon, size=k)
        srcs.append(np.full(k, s))
        arrs.append(t)
        sizes.append(_sample_websearch(rng, k))
        if pattern == "rack_permutation":
            dsts.append(np.full(k, perm[s]))
        elif pattern == "uniform":
            d = rng.integers(0, n - 1, size=k)
            dsts.append(np.where(d >= s, d + 1, d))
        else:
            raise ValueError(pattern)
    order = np.argsort(np.concatenate(arrs), kind="stable")
    return Workload(
        src=np.concatenate(srcs)[order].astype(np.int64),
        dst=np.concatenate(dsts)[order].astype(np.int64),
        size=np.concatenate(sizes)[order],
        arrival=np.concatenate(arrs)[order].astype(np.int64),
        n=n,
        horizon=horizon,
    )


def phase_shifting_workload(
    n: int,
    load: float,
    horizon: int,
    bits_per_slot: float,
    d_hat: int = 1,
    seed: int = 0,
    phases: tuple[str, ...] = ("permutation", "uniform", "dlrm"),
    shift_period: int | None = None,
) -> Workload:
    """Non-stationary websearch traffic: the destination pattern follows a
    phase train (see :func:`repro.core.traffic.phase_train`), shifting every
    ``shift_period`` slots (default: the horizon split evenly across the
    phases, cycling if it is longer).

    Within a phase with hose-normalized demand matrix ``m``, node ``s``
    opens Poisson flow arrivals at ``load * rowsum(m)[s]`` of its egress
    capacity (``d_hat * bits_per_slot``/slot), websearch flow sizes, and
    destinations drawn from ``m[s]``'s profile — so the *offered* matrix of
    each phase tracks its demand matrix while flow-level burstiness stays.
    """
    rng = np.random.default_rng(seed)
    mean_size = float(np.mean(_sample_websearch(rng, 20000)))
    if shift_period is None:
        shift_period = -(-horizon // len(phases))
    if shift_period <= 0:
        raise ValueError("shift_period must be positive")
    mats = phase_train(n, tuple(phases), seed=seed)
    srcs, dsts, sizes, arrs = [], [], [], []
    for t0 in range(0, horizon, shift_period):
        t1 = min(t0 + shift_period, horizon)
        m = mats[(t0 // shift_period) % len(mats)]
        row_tot = m.sum(axis=1)
        for s in range(n):
            if row_tot[s] <= 0:
                continue
            lam = load * d_hat * bits_per_slot * row_tot[s] / mean_size
            kf = int(rng.poisson(lam * (t1 - t0)))
            if kf == 0:
                continue
            srcs.append(np.full(kf, s))
            arrs.append(rng.integers(t0, t1, size=kf))
            sizes.append(_sample_websearch(rng, kf))
            dsts.append(rng.choice(n, size=kf, p=m[s] / row_tot[s]))
    if not srcs:
        srcs, dsts = [np.empty(0, np.int64)], [np.empty(0, np.int64)]
        sizes, arrs = [np.empty(0)], [np.empty(0, np.int64)]
    order = np.argsort(np.concatenate(arrs), kind="stable")
    return Workload(
        src=np.concatenate(srcs)[order].astype(np.int64),
        dst=np.concatenate(dsts)[order].astype(np.int64),
        size=np.concatenate(sizes)[order],
        arrival=np.concatenate(arrs)[order].astype(np.int64),
        n=n,
        horizon=horizon,
    )


@dataclass
class SimResult:
    fct_slots: np.ndarray        # (F,) float; np.inf if unfinished at horizon
    flow_size: np.ndarray        # (F,) bits
    utilization: float           # delivered / ideal egress capacity
    delivered_bits: float
    offered_bits: float
    avg_hops: float = 1.0
    fault_lost_bits: float = 0.0     # VOQ bits stranded by abrupt failures
    fault_refused_bits: float = 0.0  # offered bits refused at a dead or
                                     # draining ingress (never injected)

    def fct_percentile(self, q: float, short_cutoff: float | None = None,
                       long_cutoff: float | None = None) -> float:
        m = np.isfinite(self.fct_slots)
        if short_cutoff is not None:
            m &= self.flow_size <= short_cutoff
        if long_cutoff is not None:
            m &= self.flow_size > long_cutoff
        if not m.any():
            return float("nan")
        return float(np.percentile(self.fct_slots[m], q))

    @property
    def completed_frac(self) -> float:
        if len(self.fct_slots) == 0:
            return float("nan")
        return float(np.isfinite(self.fct_slots).mean())


# ---------------------------------------------------------------------------
# Reference engine (pre-vectorization) — kept as the golden-trace oracle
# ---------------------------------------------------------------------------

class _FlowTracker:
    """Round-robin (processor-sharing) completion bookkeeping, matching the
    paper's end-host flow scheduling: bits delivered for a pair in a slot are
    water-filled equally across that pair's active flows."""

    def __init__(self, wl: Workload):
        self.wl = wl
        self.remaining = wl.size.astype(np.float64).copy()
        self.fct = np.full(wl.num_flows, np.inf)
        self.active: dict[tuple[int, int], list[int]] = {}

    def arrive(self, flow_ids: np.ndarray) -> None:
        for f in flow_ids:
            p = (int(self.wl.src[f]), int(self.wl.dst[f]))
            self.active.setdefault(p, []).append(int(f))

    def credit(self, delivered: np.ndarray, slot: int) -> None:
        """delivered: (n, n) bits landed at destinations this slot."""
        for u, v in zip(*np.nonzero(delivered > 1e-9)):
            p = (int(u), int(v))
            flows = self.active.get(p)
            if not flows:
                continue
            s = float(delivered[u, v])
            rems = self.remaining[flows]
            s = min(s, float(rems.sum()))
            # water level L: sum_i min(rem_i, L) == s
            order = np.argsort(rems)
            sorted_r = rems[order]
            csum = np.cumsum(sorted_r)
            m = len(flows)
            # find smallest j where giving everyone sorted_r[j] exceeds s
            fill = csum + sorted_r * np.arange(m - 1, -1, -1)
            j = int(np.searchsorted(fill, s, side="left"))
            level = (
                sorted_r[-1]
                if j >= m
                else (s - (csum[j - 1] if j else 0.0)) / (m - j)
            )
            got = np.minimum(rems, level)
            self.remaining[flows] = rems - got
            still = []
            for f, r in zip(flows, rems - got):
                if r <= 1e-6:
                    self.fct[f] = slot + 1 - self.wl.arrival[f]
                else:
                    still.append(f)
            self.active[p] = still


def simulate_reference(
    sched: Schedule,
    wl: Workload,
    bits_per_slot: float,
    mode: str = "single_hop",
    sanitize: bool | None = None,
) -> SimResult:
    """Run ``wl`` over ``sched`` for ``wl.horizon`` slots (scalar engine).

    ``sanitize``: run the :mod:`repro.analysis.sanitize` contract checks
    (default: the ``REPRO_SANITIZE`` env var); results are bit-identical
    either way.
    """
    n = wl.n
    if sched.n != n:
        raise ValueError("schedule/workload size mismatch")
    caps = sched.capacity_per_slot(bits_per_slot)  # (n_slots, n, n)
    ns = caps.shape[0]
    two_hop = mode in ("rotorlb", "vlb")
    if mode not in _MODES:
        raise ValueError(mode)
    san = make_sanitizer(sanitize)
    if san is not None:
        san.check_workload(wl)
        san.check_schedule(sched)
        san.check_caps_dense(
            caps, sched.d_hat, bits_per_slot * (1.0 - sched.recfg_frac),
            label="reference:caps")

    voq = np.zeros((n, n))
    # the reference oracle is deliberately dense ((n, n, n) relay tensor —
    # it only ever runs at golden-trace scale)  # lint: allow-dense
    relay = np.zeros((n, n, n)) if two_hop else None  # [at, src, dst]
    tracker = _FlowTracker(wl)
    splits = np.searchsorted(wl.arrival, np.arange(1, wl.horizon))
    arr_idx = np.split(np.arange(wl.num_flows), splits)

    delivered_total = 0.0
    second_hop_bits = 0.0
    eps = 1e-12

    for slot in range(wl.horizon):
        f = arr_idx[slot]
        if len(f):
            np.add.at(voq, (wl.src[f], wl.dst[f]), wl.size[f])
            tracker.arrive(f)
        cap = caps[slot % ns].copy()
        delivered = np.zeros((n, n))

        if two_hop:
            # priority 1: second-hop relay traffic (at u, destined v)
            rsum = relay.sum(axis=1)                      # (at, dst)
            send1 = np.minimum(rsum, cap)
            frac = np.where(rsum > eps, send1 / np.maximum(rsum, eps), 0.0)
            # bits landing at v attributed to original (s, v)
            delivered += np.einsum("usv,uv->sv", relay, frac)
            second_hop_bits += send1.sum()
            relay *= (1.0 - frac)[:, None, :]
            cap -= send1

        if mode != "vlb":
            tx = np.minimum(voq, cap)
            voq -= tx
            delivered += tx
            cap -= tx

        if two_hop:
            # offload leftover capacity: proportional spray into relays
            leftover_u = cap.sum(axis=1)                  # (n,)
            queue_u = voq.sum(axis=1)
            send_u = np.minimum(leftover_u, queue_u)
            link_share = np.where(
                leftover_u[:, None] > eps, cap / np.maximum(leftover_u[:, None], eps), 0.0
            )
            q_share = np.where(
                queue_u[:, None] > eps, voq / np.maximum(queue_u[:, None], eps), 0.0
            )
            # moved[u, v, d] = send_u * link_share[u,v] * q_share[u,d]
            moved = send_u[:, None, None] * link_share[:, :, None] * q_share[:, None, :]
            voq -= moved.sum(axis=1)
            voq = np.maximum(voq, 0.0)
            # bits whose relay node IS the destination arrive immediately
            diag = moved[:, np.arange(n), np.arange(n)]   # (u, v==d)
            delivered += diag
            moved[:, np.arange(n), np.arange(n)] = 0.0
            relay += moved.transpose(1, 0, 2)             # -> [at v, src u, dst d]

        delivered_total += delivered.sum()
        tracker.credit(delivered, slot)

    offered = float(wl.size[wl.arrival < wl.horizon].sum())
    if san is not None:
        queued = float(voq.sum()) + (float(relay.sum()) if two_hop else 0.0)
        san.check_conservation(offered, float(delivered_total), queued,
                               label="reference:conservation")
        alive = np.isinf(tracker.fct)
        san.check_credit_closure(
            offered, float(delivered_total),
            float(tracker.remaining[alive].sum()),
            int((~alive).sum()), label="reference:credit")
    ideal = wl.horizon * wl.n * sched.d_hat * bits_per_slot
    return SimResult(
        fct_slots=tracker.fct,
        flow_size=wl.size,
        utilization=delivered_total / ideal,
        delivered_bits=float(delivered_total),
        offered_bits=offered,
        avg_hops=1.0 + second_hop_bits / max(delivered_total, 1e-9)
        if two_hop else 1.0,
    )


# ---------------------------------------------------------------------------
# Vectorized batch engine
# ---------------------------------------------------------------------------

_PAD_W = 8           # water-level search depth before exact fallback
_KEY_DT = np.dtype([("p", np.int64), ("r", np.float64)])


def _ranged_arange(counts: np.ndarray) -> np.ndarray:
    """[0..c0), [0..c1), ... concatenated."""
    total = int(counts.sum())
    out = np.arange(total)
    starts = np.concatenate([[0], np.cumsum(counts[:-1])])
    return out - np.repeat(starts, counts)


class _CreditState:
    """Processor-sharing flow-completion bookkeeping, O(pairs) per slot.

    Active flows are kept in arrays sorted by (pair id, stored size).  A
    water-fill step subtracts the same level from every surviving flow of a
    pair, so the engine stores per-pair *offsets* instead of rewriting
    per-flow remainders: ``true_remaining = stored - off[pair]``.  A slot
    then costs O(1) per delivered pair (advance the offset, complete the
    sorted prefix that sank below the level) instead of O(active flows).
    Completions are tombstoned via per-pair skip counters and physically
    removed in periodic compactions, which also rebase offsets before they
    grow past float precision.

    Matches :class:`_FlowTracker.credit` semantics (per pair, bits are
    water-filled across active flows sorted by remaining size; flows
    dropping to <= 1e-6 bits complete with ``fct = slot + 1 - arrival``)
    up to ~ulp-level float drift from the offset representation.
    """

    def __init__(self, n_pairs: int, pid: np.ndarray, size: np.ndarray,
                 arrival: np.ndarray, fct: np.ndarray):
        self.pid = pid
        self.size = size
        self.arrival = arrival
        self.fct = fct
        self.off = np.zeros(n_pairs)      # per-pair water level served
        self.psum = np.zeros(n_pairs)     # approx total remaining per pair
        self.ctr = np.zeros(n_pairs, dtype=np.int64)   # tombstoned prefix
        self.keys = np.empty(0, dtype=_KEY_DT)         # (pair, stored)
        self.act = np.empty(0, dtype=np.int64)         # flow ids
        self.dead = 0

    def arrive(self, newf: np.ndarray) -> None:
        # the insert below rewrites the whole keys/act arrays, so shedding
        # tombstones first keeps every later O(active) pass proportional
        # to genuinely alive flows (the batched replay ledger otherwise
        # drags ~1/3 dead entries through each rebuild)
        if self.dead * 4 > len(self.act) and self.dead > 1024:
            self._compact()
        npid = self.pid[newf]
        stored = self.size[newf] + self.off[npid]
        o = np.lexsort((stored, npid))
        newf, npid, stored = newf[o], npid[o], stored[o]
        np.add.at(self.psum, npid, self.size[newf])
        q = np.empty(len(newf), dtype=_KEY_DT)
        q["p"] = npid
        q["r"] = stored
        if self.keys.size:
            # hand-rolled sorted insert (np.insert x2 costs several passes)
            K, A = len(q), len(self.keys)
            tgt = np.searchsorted(self.keys, q, side="left") + np.arange(K)
            keys = np.empty(A + K, dtype=_KEY_DT)
            act = np.empty(A + K, dtype=np.int64)
            keep = np.ones(A + K, dtype=bool)
            keep[tgt] = False
            keys[tgt] = q
            act[tgt] = newf
            keys[keep] = self.keys
            act[keep] = self.act
            self.keys, self.act = keys, act
        else:
            self.keys = q
            self.act = newf.copy()

    def remaining_active(self) -> tuple[float, int]:
        """(total bits still stored for uncompleted flows, completed count)
        — the sanitizer's credit-closure probe; read-only."""
        completed = int(np.isfinite(self.fct).sum())
        if not self.act.size:
            return 0.0, completed
        alive = np.isinf(self.fct[self.act])
        rem = (self.keys["r"][alive]
               - self.off[self.keys["p"][alive]])
        return float(np.maximum(rem, 0.0).sum()), completed

    def _compact(self) -> None:
        alive = np.isinf(self.fct[self.act])
        self.act = self.act[alive]
        self.keys = self.keys[alive]
        self.ctr[:] = 0
        self.dead = 0
        # rebase offsets into stored values before they swamp the mantissa
        if self.off.max() > 1e9 and self.act.size:
            self.keys["r"] -= self.off[self.keys["p"]]
            self.off[:] = 0.0

    def credit(self, delivered_flat: np.ndarray, slot: int,
               drain_rel: float = 0.0, level_rel: float = 0.0) -> None:
        pids = np.flatnonzero(delivered_flat > 1e-9)
        self.credit_pairs(pids, delivered_flat[pids], slot,
                          drain_rel=drain_rel, level_rel=level_rel)

    def credit_pairs(self, pids: np.ndarray, s: np.ndarray,
                     slot: int, drain: np.ndarray | None = None,
                     drain_rel: float = 0.0,
                     level_rel: float = 0.0) -> None:
        """Credit ``s`` bits to each (unique) pair in ``pids`` — the sparse
        entry point for engines that know the delivered support.

        ``drain``/``drain_rel`` reconcile float32 engines with the f64
        ledger: a pair flagged in ``drain`` (the device observed the queue
        empty) or whose credit lands within ``drain_rel`` of its exact
        remaining total is forced to complete fully, so f32 rounding in the
        delivered amounts cannot leave 1-ulp residues that stall FCTs.
        """
        if not self.act.size or not pids.size:
            return
        keep = s > 1e-9
        if drain is not None:
            keep |= drain
        if not keep.all():
            pids, s = pids[keep], s[keep]
            if drain is not None:
                drain = drain[keep]
        if not pids.size:
            return
        kp = self.keys["p"]
        lo = np.searchsorted(kp, pids, side="left") + self.ctr[pids]
        hi = np.searchsorted(kp, pids, side="right")
        m = hi - lo
        g = m > 0
        if not g.all():
            if not g.any():
                return
            pids, lo, hi, m, s = pids[g], lo[g], hi[g], m[g], s[g]
            if drain is not None:
                drain = drain[g]
        S = len(pids)
        off_g = self.off[pids]
        stored = self.keys["r"]

        # fast path: when the pair's smallest remaining (the head of its
        # sorted run) sits above the no-completion water level s/m plus
        # every epsilon the slow path could apply, nothing completes:
        # head_rem > s/m implies head_rem*m > s >= s_eff so no flow sinks
        # (j = 0), the level is exactly s/m — the same float op the full
        # path performs as (s - 0.0) / max(m - 0, 1) — and head_rem
        # clearing the guard keeps k = 0 and every drain_rel force off
        head_rem = stored[lo] - off_g
        lvl = s / m
        guard = 1e-6 + 1.01 * drain_rel * s
        if level_rel:
            guard = guard + level_rel * (lvl + off_g)
        easy = head_rem > lvl + guard
        if drain is not None:
            easy &= ~drain
        if easy.all():
            self.off[pids] = off_g + lvl
            self.psum[pids] -= s
            return
        if easy.any():
            pe = pids[easy]
            self.off[pe] = off_g[easy] + lvl[easy]
            self.psum[pe] -= s[easy]
            hard = ~easy
            pids, lo, hi, m, s = (pids[hard], lo[hard], hi[hard], m[hard],
                                  s[hard])
            off_g = off_g[hard]
            if drain is not None:
                drain = drain[hard]
            S = len(pids)

        # exact remaining totals only where the budget might drain the pair
        s_eff = s
        need_mask = 4.0 * s >= np.maximum(self.psum[pids], 0.0)
        if drain is not None:
            need_mask |= drain
        need = np.flatnonzero(need_mask)
        if need.size:
            mm = m[need]
            flat = np.repeat(lo[need], mm) + _ranged_arange(mm)
            bounds = np.concatenate([[0], np.cumsum(mm[:-1])])
            tot = (np.add.reduceat(stored[flat], bounds)
                   - mm * off_g[need])
            s_eff = s.copy()
            s_eff[need] = np.minimum(s[need], tot)
            # force full completion where the device saw the queue drain, or
            # where f32 rounding left the credit within drain_rel of exact
            force = np.zeros(need.size, dtype=bool)
            if drain is not None:
                force |= drain[need]
            if drain_rel > 0.0:
                force |= (tot >= 0.0) & (tot - s[need] <= drain_rel * tot)
            if force.any():
                s_eff[need[force]] = np.maximum(tot[force], 0.0)

        # water level from the sorted prefix (true rem = stored - off)
        W = min(_PAD_W, int(m.max()))
        col = np.arange(W)
        valid = col[None, :] < np.minimum(m, W)[:, None]
        safe = np.where(valid, lo[:, None] + col[None, :], 0)
        r_pre = np.where(valid, stored[safe] - off_g[:, None], 0.0)
        csum = np.cumsum(r_pre, axis=1)
        fill = csum + r_pre * (m[:, None] - 1 - col[None, :])
        below = (fill < s_eff[:, None]) & valid
        j = below.sum(axis=1)

        full = j >= m                                  # drain: level = max
        r_last = stored[hi - 1] - off_g
        prev = np.where(j > 0, csum[np.arange(S), np.maximum(j - 1, 0)], 0.0)
        level = np.where(full, r_last,
                         (s_eff - prev) / np.maximum(m - j, 1))
        # completion epsilon: exact engines (level_rel=0) use the absolute
        # 1e-6 sliver; f32 pro-rata replays widen it by the accumulated
        # drift scale (rounding in the credited amounts grows with the
        # pair's cumulative water level), so a residue cannot stall a
        # completion past its f64 slot.  Engines with per-pair drain flags
        # (single-hop) keep level_rel=0 — their boundary is already exact.
        eps = 1e-6 + level_rel * (np.maximum(level, 0.0) + off_g)
        k = ((r_pre <= (level + eps)[:, None]) & valid).sum(axis=1)
        k[full] = m[full]

        # level search (or completion count) overran the pad: exact solve
        ovf = np.flatnonzero(((j >= W) | (k >= W)) & (m > W))
        for i in ovf:
            r_g = stored[lo[i]:hi[i]] - off_g[i]
            mi = int(m[i])
            c_g = np.cumsum(r_g)
            f_g = c_g + r_g * np.arange(mi - 1, -1, -1)
            ji = int(np.searchsorted(f_g, s_eff[i], side="left"))
            level[i] = (r_g[-1] if ji >= mi else
                        (s_eff[i] - (c_g[ji - 1] if ji else 0.0)) / (mi - ji))
            eps_i = 1e-6 + level_rel * (max(level[i], 0.0) + off_g[i])
            k[i] = mi if ji >= mi else int(
                np.searchsorted(r_g, level[i] + eps_i, side="right"))

        # complete the sunken prefix, advance offsets and totals
        self.off[pids] = off_g + level
        self.psum[pids] = np.where(k == m, 0.0, self.psum[pids] - s_eff)
        if k.any():
            kc = np.minimum(k, W)
            fmask = (col[None, :] < kc[:, None]) & valid
            done = self.act[safe[fmask]]
            big = np.flatnonzero(k > W)
            if big.size:
                ext = np.repeat(lo[big] + W, k[big] - W)                     + _ranged_arange(k[big] - W)
                done = np.concatenate([done, self.act[ext]])
            self.fct[done] = slot + 1 - self.arrival[done]
            self.ctr[pids] += k
            self.dead += int(k.sum())
            if self.dead * 2 > len(self.act) and self.dead > 4096:
                self._compact()


class _SupportPlans:
    """Per-slot circuit-support plans for the two-hop cases of a batch.

    Per (two-hop case, period slot), the <= n*d_hat (at, dst) pairs with
    nonzero capacity; relay drain/fill only ever touches these rows
    (everything else is an exact multiply-by-one / add-zero), so the
    per-slot relay work is O(n^2 d_hat), not O(n^3).  ``tmap[b2]`` maps a
    two-hop-local case index to its global batch index: ``row``/``bv``
    (global) address the shared cap/voq/delivered tensors; ``row_l`` /
    ``bv_l`` (local) address the relay tensor, which only exists for
    two-hop cases.  The merged plan for a slot depends only on
    ``slot % ns_b`` per case (the residue tuple :meth:`key`), so plans are
    memoized on that tuple.

    One builder serves both backends: the NumPy relay loop consumes the
    memoized merged dicts (:meth:`plan`), the JAX backend densifies the
    same merged plans into its padded ``(plan, J_pad)`` LUT, deduplicated
    by :meth:`key` and scanned by per-slot plan index.
    """

    _CAT = ("b", "row", "v", "bv", "row_l", "bv_l", "at")

    def __init__(self, caps_list: list[np.ndarray], n: int,
                 tmap: list[int], B: int):
        self.ns = [caps_list[g].shape[0] for g in tmap]
        self.per_case: list[list[dict]] = []
        for b2, g in enumerate(tmap):
            plans = []
            for ps in range(caps_list[g].shape[0]):
                at, v = np.nonzero(caps_list[g][ps])  # lex-sorted by (at, v)
                plans.append({
                    "J": len(at), "b": np.full(len(at), g),
                    "row": g * n + at, "v": v, "bv": g * n + v,
                    "row_l": b2 * n + at, "bv_l": b2 * n + v, "at": at,
                })
            self.per_case.append(plans)
        self._memo: dict[tuple, dict] = {}

    def key(self, slot: int) -> tuple:
        return tuple(slot % p for p in self.ns)

    def plan(self, slot: int) -> dict:
        key = self.key(slot)
        plan = self._memo.get(key)
        if plan is not None:
            return plan
        sd = [self.per_case[b2][key[b2]]
              for b2 in range(len(self.per_case))]
        plan = {k: np.concatenate([d[k] for d in sd]) for k in self._CAT}
        plan["J"] = int(sum(d["J"] for d in sd))
        if len(self._memo) < 1024:  # bound memory for long aperiodic batches
            self._memo[key] = plan
        return plan


def _concat_flows(
    cases: list[tuple[Schedule, Workload]],
    n: int,
    horizons: np.ndarray,
    H: int,
):
    """Concatenate the batch's flows and build the shared credit state and
    arrival buckets (one stable sort, contiguous slices per slot; flows
    arriving at/after their case's horizon are never injected — they are
    excluded from offered_bits too).

    Returns (f_off, pid, f_size, fct, credit, order, bucket).
    """
    B = len(cases)
    f_off = np.concatenate(
        [[0], np.cumsum([wl.num_flows for _, wl in cases])]).astype(np.int64)
    f_item = np.concatenate(
        [np.full(wl.num_flows, b, dtype=np.int64)
         for b, (_, wl) in enumerate(cases)])
    f_src = np.concatenate([wl.src for _, wl in cases]).astype(np.int64)
    f_dst = np.concatenate([wl.dst for _, wl in cases]).astype(np.int64)
    f_size = np.concatenate([wl.size for _, wl in cases]).astype(np.float64)
    f_arr = np.concatenate([wl.arrival for _, wl in cases]).astype(np.int64)
    pid = (f_item * n + f_src) * n + f_dst
    fct = np.full(len(f_size), np.inf)
    credit = _CreditState(B * n * n, pid, f_size, f_arr, fct)

    valid = f_arr < horizons[f_item]
    order = np.argsort(f_arr, kind="stable")
    order = order[valid[order]]
    bucket = np.searchsorted(f_arr[order], np.arange(H + 1))
    return f_off, pid, f_size, fct, credit, order, bucket


def _simulate_batch_singlehop(
    cases: list[tuple[Schedule, Workload]],
    bits_per_slot: float,
    san=None,
    faults: list | None = None,
) -> list[SimResult]:
    """Sparse single-hop engine: a slot only moves bits over its <= n*d_hat
    circuits, so the whole slot step is O(B n d_hat) scalar ops on the
    circuit support — no dense (B, n, n) work at all.  VOQ dynamics are
    element-for-element identical to the dense path.

    ``faults`` optionally carries one :class:`FaultSchedule` (or None) per
    case.  A case's timeline stays on the memoized fault-free plans until
    its first event fires (bit-identical prefix); after that its slot
    supports are rebuilt from the schedule's matching block with failed
    circuits masked (memoized per (case, period slot, fault version)).
    Bits stranded by ``tor_fail`` flushes go to the per-case
    ``fault_lost_bits`` ledger; arrivals at a non-injecting ingress are
    refused into ``fault_refused_bits`` and never enter the fabric."""
    B = len(cases)
    n = cases[0][1].n
    for sched, wl in cases:
        if wl.n != n:
            raise ValueError("all workloads in a batch must share n")
        if sched.n != n:
            raise ValueError("schedule/workload size mismatch")
    horizons = np.array([wl.horizon for _, wl in cases], dtype=np.int64)
    H = int(horizons.max())

    # circuit support per (case, period slot): pair ids + capacities,
    # straight from the sparse plan (no dense (n_slots, n, n) array)
    ns = [sched.n_slots for sched, _ in cases]
    per_case = []
    for b, (sched, wl) in enumerate(cases):
        if san is not None:
            san.check_workload(wl)
            san.check_schedule(sched)
        plans = []
        w_b = bits_per_slot * (1.0 - sched.recfg_frac)
        for ps, (at, v, cap) in enumerate(sched.slot_circuits(bits_per_slot)):
            if san is not None:
                san.check_support(at, v, cap, n, sched.d_hat, w_b,
                                  label=f"singlehop:case{b}:slot{ps}")
            plans.append({
                "pid": (b * n + at) * n + v,
                "cap": cap,
                "case": np.full(len(at), b, dtype=np.int64),
            })
        per_case.append(plans)
    memo: dict[tuple, dict] = {}

    def plan_for(slot: int) -> dict:
        key = tuple(slot % p for p in ns)
        plan = memo.get(key)
        if plan is None:
            sd = [per_case[b][key[b]] for b in range(B)]
            plan = {k: np.concatenate([d[k] for d in sd])
                    for k in ("pid", "cap", "case")}
            if len(memo) < 1024:
                memo[key] = plan
        return plan

    # fault timelines: only cases with a nonempty schedule pay anything
    tl_items: list[tuple[int, "object"]] = []
    if faults:
        for b, fs in enumerate(faults):
            if fs:
                tl_items.append((b, fs.compile(n, cases[b][0].d_hat)))
    tl_by_case = dict(tl_items)
    fault_lost = np.zeros(B)
    fault_refused = np.zeros(B)
    src0 = np.arange(n)
    fmemo: dict[tuple, dict] = {}

    def masked_case_plan(b: int, ps: int, tl) -> dict:
        """Case b's period-slot-ps support under its current fault state:
        rebuilt from the matching block (plane identity needed for the
        mask), parallel surviving circuits accumulated, self-loops
        dropped — the same pairs slot_circuits emits, minus dead ones."""
        key = (b, ps, tl.version)
        plan = fmemo.get(key)
        if plan is None:
            sched = cases[b][0]
            blk = sched.perms[ps * sched.d_hat:(ps + 1) * sched.d_hat]
            keep = claims_fault_mask(blk, tl.link_ok()) & (blk != src0)
            cpid = ((b * n + np.broadcast_to(src0, blk.shape)) * n
                    + blk)[keep]
            upid, inv = np.unique(cpid, return_inverse=True)
            w_b = bits_per_slot * (1.0 - sched.recfg_frac)
            cap = np.bincount(inv, weights=np.full(len(cpid), w_b),
                              minlength=len(upid))
            plan = {"pid": upid, "cap": cap,
                    "case": np.full(len(upid), b, dtype=np.int64)}
            if san is not None:
                san.check_plan_pairs(upid % (n * n), cap, n, sched.d_hat,
                                     w_b, label=f"singlehop:case{b}:"
                                                f"slot{ps}:faulted")
            if len(fmemo) < 4096:
                fmemo[key] = plan
        return plan

    f_off, pid, f_size, fct, credit, order, bucket = _concat_flows(
        cases, n, horizons, H)

    voq_flat = np.zeros(B * n * n)   # per-pair VOQ state  # lint: allow-dense
    delivered_total = np.zeros(B)
    all_live = bool(np.all(horizons == H))

    for slot in range(H):
        newf = order[bucket[slot]:bucket[slot + 1]]
        dirty = False
        if tl_items:
            for b, tl in tl_items:
                for node in tl.advance(slot):
                    base = (b * n + int(node)) * n
                    fault_lost[b] += float(voq_flat[base:base + n].sum())
                    voq_flat[base:base + n] = 0.0
                dirty = dirty or not tl.clean
            if newf.size and dirty:
                ok = np.ones(len(newf), dtype=bool)
                fsrc = (pid[newf] // n) % n
                fcase = pid[newf] // (n * n)
                for b, tl in tl_items:
                    if not tl.inject_ok.all():
                        sel = fcase == b
                        ok[sel] = tl.inject_ok[fsrc[sel]]
                if not ok.all():
                    np.add.at(fault_refused, fcase[~ok], f_size[newf[~ok]])
                    newf = newf[ok]
        if newf.size:
            np.add.at(voq_flat, pid[newf], f_size[newf])
            credit.arrive(newf)

        if dirty:
            parts = []
            for b in range(B):
                tl = tl_by_case.get(b)
                if tl is None or tl.clean:
                    parts.append(per_case[b][slot % ns[b]])
                else:
                    parts.append(masked_case_plan(b, slot % ns[b], tl))
            plan = {k: np.concatenate([d[k] for d in parts])
                    for k in ("pid", "cap", "case")}
        else:
            plan = plan_for(slot)
        spid = plan["pid"]
        scap = plan["cap"]
        if not all_live:
            scap = scap * (slot < horizons[plan["case"]])
        q = voq_flat[spid]
        tx = np.minimum(q, scap)
        voq_flat[spid] = q - tx
        np.add.at(delivered_total, plan["case"], tx)
        credit.credit_pairs(spid, tx, slot)

    out = []
    voq_case = voq_flat.reshape(B, n * n).sum(axis=1)
    for b, (sched, wl) in enumerate(cases):
        sl = slice(f_off[b], f_off[b + 1])
        offered = float(wl.size[wl.arrival < wl.horizon].sum())
        injected = offered - float(fault_refused[b])
        if san is not None:
            san.check_conservation(
                injected, float(delivered_total[b]), float(voq_case[b]),
                label=f"singlehop:case{b}:conservation",
                fault_lost=float(fault_lost[b]))
        ideal = wl.horizon * n * sched.d_hat * bits_per_slot
        out.append(SimResult(
            fct_slots=fct[sl],
            flow_size=wl.size,
            utilization=float(delivered_total[b]) / ideal,
            delivered_bits=float(delivered_total[b]),
            offered_bits=offered,
            fault_lost_bits=float(fault_lost[b]),
            fault_refused_bits=float(fault_refused[b]),
        ))
    if san is not None:
        rem, completed = credit.remaining_active()
        injected = sum(r.offered_bits - r.fault_refused_bits for r in out)
        # flushed (fault-lost) bits stay on their never-completing flows,
        # so they sit in remaining_active and drop out of the credit —
        # the closure holds with no fault term
        san.check_credit_closure(injected, float(delivered_total.sum()),
                                 rem, completed, label="singlehop:credit")
    return out


def _simulate_batch(
    cases: list[tuple[Schedule, Workload]],
    bits_per_slot: float,
    modes: list[str],
    san=None,
) -> list[SimResult]:
    """Advance every (schedule, workload) case in one slot loop with a
    leading batch axis.  Routing modes mix freely: relay state exists only
    for the two-hop cases, and vlb cases mask out the direct hop."""
    for m in modes:
        if m not in _MODES:
            raise ValueError(m)
    B = len(cases)
    n = cases[0][1].n
    for sched, wl in cases:
        if wl.n != n:
            raise ValueError("all workloads in a batch must share n")
        if sched.n != n:
            raise ValueError("schedule/workload size mismatch")
        if san is not None:
            san.check_workload(wl)
            san.check_schedule(sched)
    horizons = np.array([wl.horizon for _, wl in cases], dtype=np.int64)
    H = int(horizons.max())

    # periodic capacity LUT, concatenated over cases
    caps_list = [sched.capacity_per_slot(bits_per_slot) for sched, _ in cases]
    if san is not None:
        for b, (sched, _) in enumerate(cases):
            san.check_caps_dense(
                caps_list[b], sched.d_hat,
                bits_per_slot * (1.0 - sched.recfg_frac),
                label=f"twohop:case{b}:caps")
    ns = np.array([c.shape[0] for c in caps_list], dtype=np.int64)
    offs = np.concatenate([[0], np.cumsum(ns[:-1])])
    caps_flat = np.concatenate(caps_list, axis=0)
    cap_idx = offs[:, None] + (np.arange(H)[None, :] % ns[:, None])  # (B, H)

    tmap = [b for b, m in enumerate(modes) if m in ("rotorlb", "vlb")]
    two_hop = bool(tmap)
    if two_hop:
        plan_for = _SupportPlans(caps_list, n, tmap, B).plan
        direct_mask = np.array(
            [0.0 if m == "vlb" else 1.0 for m in modes])[:, None, None]
        all_direct = bool(np.all(direct_mask == 1.0))

    f_off, pid, f_size, fct, credit, order, bucket = _concat_flows(
        cases, n, horizons, H)

    voq_flat = np.zeros(B * n * n)   # per-pair VOQ state  # lint: allow-dense
    voq = voq_flat.reshape(B, n, n)
    # relay state only for the two-hop cases: [(b2, at), src, dst] — the
    # offload fill then lands on contiguous rows (the strided drain
    # gather/assign is several times cheaper than a strided fancy +=).
    # RS maintains per-(at, dst) bucket totals so empty buckets are O(1).
    # Inherent two-hop state (source attribution for FCTs), not a temporary.
    R3 = np.zeros((len(tmap) * n, n, n)) if two_hop else None  # lint: allow-dense
    RS = np.zeros((len(tmap) * n, n)) if two_hop else None
    delivered_total = np.zeros(B)
    second_hop_bits = np.zeros(B)
    eps = 1e-12
    all_live = bool(np.all(horizons == H))

    for slot in range(H):
        newf = order[bucket[slot]:bucket[slot + 1]]
        if newf.size:
            np.add.at(voq_flat, pid[newf], f_size[newf])
            credit.arrive(newf)

        cap = caps_flat[cap_idx[:, slot]]                # (B, n, n), fresh
        if not all_live:
            cap *= (slot < horizons)[:, None, None]      # finished cases idle
        cap3 = cap.reshape(B * n, n)
        delivered = None

        p = plan_for(slot) if two_hop else None
        have_circuits = two_hop and p["J"] > 0

        if have_circuits:
            s_row, s_v, s_rl = p["row"], p["v"], p["row_l"]

            # priority 1: second-hop relay traffic (at u, destined v).  The
            # maintained per-bucket totals RS say which circuits actually
            # hold relayed bits, so empty buckets cost O(1), not O(n).
            rs = RS[s_rl, s_v]                           # (J,)
            cap_j = cap3[s_row, s_v]
            send1 = np.minimum(rs, cap_j)
            frac = np.where(rs > eps, send1 / np.maximum(rs, eps), 0.0)
            ai = np.flatnonzero(frac > 0.0)
            if ai.size:
                rl_a, v_a = s_rl[ai], s_v[ai]
                rel_rows = R3[rl_a, :, v_a]              # (Ja, n) over src
                contrib = rel_rows * frac[ai, None]
                # land bits at dst, attributed to the original (src, dst)
                o = np.argsort(p["bv_l"][ai], kind="stable")
                bvs = p["bv"][ai][o]
                co = contrib[o]
                starts = np.flatnonzero(np.r_[True, bvs[1:] != bvs[:-1]])
                dtmp = np.zeros((B * n, n))              # [(b, dst), src]
                dtmp[bvs[starts]] = np.add.reduceat(co, starts, axis=0)
                delivered = np.ascontiguousarray(
                    dtmp.reshape(B, n, n).transpose(0, 2, 1))
                R3[rl_a, :, v_a] = rel_rows * (1.0 - frac[ai])[:, None]
            np.add.at(second_hop_bits, p["b"], send1)
            RS[s_rl, s_v] = rs - send1
            cap3[s_row, s_v] = cap_j - send1

        tx = np.minimum(voq, cap)
        if two_hop and not all_direct:
            tx *= direct_mask                            # vlb: no direct hop
        voq -= tx
        if delivered is None:
            delivered = tx        # no relay bits landed: direct is everything
        else:
            delivered += tx

        if have_circuits:
            cap -= tx
            # offload leftover capacity: proportional spray into relays;
            # moved[u, v, d] = send_u * link_share[u,v] * q_share[u,d] is
            # supported on circuit rows (u, v) with both leftover capacity
            # and queued bits — keep it compact over just those rows
            voq3 = voq_flat.reshape(B * n, n)
            leftover_u = cap3.sum(axis=1)                # (B*n,)
            queue_u = voq3.sum(axis=1)
            send_u = np.minimum(leftover_u, queue_u)
            lo_j = leftover_u[s_row]
            ls_j = np.where(
                lo_j > eps, cap3[s_row, s_v] / np.maximum(lo_j, eps), 0.0)
            coeff = send_u[s_row] * ls_j
            nz = np.flatnonzero(coeff > 0.0)
            if nz.size:
                row_z, v_z = s_row[nz], s_v[nz]
                q_z = queue_u[row_z]
                qs_rows = np.where(
                    (q_z > eps)[:, None],
                    voq3[row_z] / np.maximum(q_z, eps)[:, None], 0.0)
                moved_c = coeff[nz][:, None] * qs_rows
                stz = np.flatnonzero(np.r_[True, row_z[1:] != row_z[:-1]])
                dec = np.add.reduceat(moved_c, stz, axis=0)
                voq3[row_z[stz]] -= dec
                np.maximum(voq, 0.0, out=voq)
                # bits whose relay node IS the destination arrive at once
                j_all = np.arange(len(nz))
                delivered.reshape(B * n, n)[row_z, v_z] += moved_c[j_all, v_z]
                moved_c[j_all, v_z] = 0.0
                bvz, atz = p["bv_l"][nz], p["at"][nz]
                R3[bvz, atz, :] += moved_c          # -> [at v, src u, dst]
                np.add.at(RS, bvz, moved_c)

        delivered_total += delivered.sum(axis=(1, 2))
        credit.credit(delivered.reshape(-1), slot)

    out = []
    voq_case = voq.reshape(B, n * n).sum(axis=1)
    for b, (sched, wl) in enumerate(cases):
        sl = slice(f_off[b], f_off[b + 1])
        offered = float(wl.size[wl.arrival < wl.horizon].sum())
        case_two_hop = modes[b] in ("rotorlb", "vlb")
        if san is not None:
            queued = float(voq_case[b])
            if case_two_hop:
                b2 = tmap.index(b)
                queued += float(R3[b2 * n:(b2 + 1) * n].sum())
            san.check_conservation(
                offered, float(delivered_total[b]), queued,
                label=f"twohop:case{b}:conservation")
        ideal = wl.horizon * n * sched.d_hat * bits_per_slot
        out.append(SimResult(
            fct_slots=fct[sl],
            flow_size=wl.size,
            utilization=float(delivered_total[b]) / ideal,
            delivered_bits=float(delivered_total[b]),
            offered_bits=offered,
            avg_hops=1.0 + float(second_hop_bits[b])
            / max(float(delivered_total[b]), 1e-9) if case_two_hop else 1.0,
        ))
    if san is not None:
        rem, completed = credit.remaining_active()
        injected = sum(r.offered_bits for r in out)
        san.check_credit_closure(injected, float(delivered_total.sum()),
                                 rem, completed, label="twohop:credit")
    return out


def simulate(
    sched: Schedule,
    wl: Workload,
    bits_per_slot: float,
    mode: str = "single_hop",
    sanitize: bool | None = None,
    faults: FaultSchedule | None = None,
) -> SimResult:
    """Run ``wl`` over ``sched`` for ``wl.horizon`` slots (vectorized).

    ``sanitize``: run the :mod:`repro.analysis.sanitize` contract checks
    (default: the ``REPRO_SANITIZE`` env var); results are bit-identical
    either way.

    ``faults``: an optional :class:`repro.core.faults.FaultSchedule` of
    timed failure events (single_hop mode only — the two-hop relay planes
    don't model per-circuit failure).  An empty schedule is bit-identical
    to passing None.
    """
    san = make_sanitizer(sanitize)
    if faults:
        if not isinstance(faults, FaultSchedule):
            raise ValueError("faults must be a FaultSchedule "
                             f"(got {type(faults).__name__})")
        if mode != "single_hop":
            raise ValueError(
                "fault injection is only supported on the single_hop "
                f"engine (got mode={mode!r})")
        faults.validate(wl.n, sched.d_hat)
        return _simulate_batch_singlehop([(sched, wl)], bits_per_slot,
                                         san=san, faults=[faults])[0]
    if mode == "single_hop":
        return _simulate_batch_singlehop([(sched, wl)], bits_per_slot,
                                         san=san)[0]
    return _simulate_batch([(sched, wl)], bits_per_slot, [mode], san=san)[0]


# ---------------------------------------------------------------------------
# Sweep API
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SweepCase:
    """One (schedule, workload, mode) point of a sweep grid.

    ``faults`` optionally injects a timed
    :class:`repro.core.faults.FaultSchedule` (single_hop cases, numpy
    backend only); an empty schedule behaves exactly like None.
    Malformed cases — unknown mode, bad fault events — raise
    ``ValueError`` at construction.
    """
    sched: Schedule
    wl: Workload
    mode: str = "single_hop"
    label: str = ""
    meta: dict = field(default_factory=dict)
    faults: FaultSchedule | None = None

    def __post_init__(self) -> None:
        if self.mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES} "
                             f"(got {self.mode!r})")
        if self.faults is not None:
            if not isinstance(self.faults, FaultSchedule):
                raise ValueError("faults must be a FaultSchedule "
                                 f"(got {type(self.faults).__name__})")
            if self.faults and self.mode != "single_hop":
                raise ValueError(
                    "fault injection is only supported on single_hop "
                    f"cases (got mode={self.mode!r})")
            self.faults.validate(self.wl.n, self.sched.d_hat)


@dataclass
class SweepRow:
    label: str
    mode: str
    result: SimResult
    meta: dict


def run_sweep(
    cases: list[SweepCase],
    bits_per_slot: float,
    backend: str = "numpy",
    sanitize: bool | None = None,
) -> list[SweepRow]:
    """Evaluate a grid of simulation cases, batching within engine kind.

    Single-hop cases (per node count) advance through one sparse batched
    slot loop, two-hop cases (``rotorlb`` / ``vlb`` mix freely) through one
    dense-relay loop; results come back in input order.  With
    ``backend="jax"``, every routing mode runs as a jitted ``jax.lax.scan``
    on the accelerator — single-hop cases through the padded circuit-support
    VOQ kernel, two-hop cases through the relay kernel (dense einsum at
    small n, padded circuit-support gathers + segment_sum beyond).  The jax
    backend now emits per-flow FCTs too: the device scan returns the
    per-slot delivered support and the host replays it through the exact
    flow-credit ledger (single-hop always; two-hop when the per-(at, src,
    dst) attribution tensor fits — see ``_twohop_fct_ok`` — otherwise
    ``fct_slots`` stays all-inf and aggregates are unchanged).  Kernels jit
    once per padded shape signature (see :func:`compile_cache_stats`), so
    repeated same-shape sweeps never recompile.

    ``sanitize``: run the :mod:`repro.analysis.sanitize` contract checks on
    every batch (default: the ``REPRO_SANITIZE`` env var); results are
    bit-identical either way.

    Unsupported configurations (unknown backend / mode, fault injection on
    the jax backend) raise ``ValueError`` here, before any case runs.
    """
    if backend not in ("numpy", "jax"):
        raise ValueError(
            f"backend must be 'numpy' or 'jax' (got {backend!r})")
    for i, c in enumerate(cases):
        if c.mode not in _MODES:
            raise ValueError(c.mode)
        if c.faults and backend == "jax":
            raise NotImplementedError(
                f"cases[{i}] ({c.label!r}): fault injection is not "
                "implemented on the jax backend — the jax kernels have no "
                "per-slot fault mask; use backend='numpy' for this case")
    with span("fabric.sweep", cases=len(cases)):
        san = make_sanitizer(sanitize)
        groups: dict[tuple, list[int]] = {}
        for i, c in enumerate(cases):
            groups.setdefault((c.wl.n, c.mode == "single_hop"), []).append(i)
        rows: list[SweepRow | None] = [None] * len(cases)
        for (_, single), idxs in groups.items():
            batch = [(cases[i].sched, cases[i].wl) for i in idxs]
            modes = [cases[i].mode for i in idxs]
            batch_faults = [cases[i].faults for i in idxs]
            if backend == "jax":
                results = (_singlehop_batch_jax(batch, bits_per_slot, san=san)
                           if single
                           else _twohop_batch_jax(batch, bits_per_slot, modes,
                                                  san=san))
            elif single:
                results = _simulate_batch_singlehop(
                    batch, bits_per_slot, san=san,
                    faults=batch_faults if any(batch_faults) else None)
            else:
                results = _simulate_batch(batch, bits_per_slot, modes,
                                          san=san)
            for i, r in zip(idxs, results):
                rows[i] = SweepRow(label=cases[i].label, mode=cases[i].mode,
                                   result=r, meta=dict(cases[i].meta))
    return rows  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Adaptive epoch-driven scheduling (closed estimation -> schedule loop)
# ---------------------------------------------------------------------------

_POLICIES = ("adaptive", "oracle", "stale", "oblivious")
_COLLISIONS = ("drop", "lowest", "receiver", "fullest")


@dataclass(frozen=True)
class _FabricPlan:
    """The fabric's merged per-slot circuit plan when every input port
    follows its own node's schedule, with output-port collisions already
    resolved.  ``plans[s]`` is the period-slot-s ``(pair_id, capacity)``
    support the per-slot engine consumes; ``lost[s]`` the capacity (bits)
    that slot loses to contention; ``disagreement`` the contested fraction
    of (matching, port) claims (see ``schedule_disagreement``).  A
    consistent fabric (one schedule) has zero loss and zero disagreement
    and its plans are byte-identical to ``Schedule.slot_circuits``.

    ``contested[s]`` counts slot s's contested traffic-carrying claims
    (src != dst inputs whose output port at least one other input also
    claims) — the capacity ``contested * w`` bounds ``lost`` from above
    for every arbitration policy, which is the disagreement-accounting
    closure the sanitizer enforces.

    ``eff``/``nonself``/``win`` carry the raw (T, n) claim structure so
    the degraded-service paths (fault masks, partially-dark planes, mixed
    old/new activation) can rebuild any slot's support from first
    principles: ``eff[t, i]`` the port input i is tuned to, ``win`` the
    statically-arbitrated winners.  ``win`` (and ``plans``) are ``None``
    for queue-aware arbitration (``collision="fullest"`` under
    disagreement), where winners depend on per-slot VOQ depth and the
    engine resolves each served slot dynamically.  ``plane_map`` maps the
    plan's logical plane rows to physical fabric planes — the identity
    except for repaired schedules rebuilt over the surviving planes."""

    plans: list | None
    n_slots: int
    disagreement: float
    lost: np.ndarray
    groups: int
    contested: np.ndarray | None = None
    eff: np.ndarray | None = None      # (T, n) effective port claims
    nonself: np.ndarray | None = None  # (T, n) claim would carry traffic
    win: np.ndarray | None = None      # (T, n) static winners; None=dynamic
    w: float = 0.0                     # bits per circuit-slot after guard
    plane_map: np.ndarray | None = None


def _resolve_slot_claims(
    claims: np.ndarray,
    valid: np.ndarray,
    planes: np.ndarray,
    rot: np.ndarray,
    collision: str,
    voq: np.ndarray,
    n: int,
) -> tuple[np.ndarray, int]:
    """Arbitrate one slot's output-port contention dynamically.

    ``claims``/``valid``: (R, n) configured output ports and which of
    them exist (async transitions stack old- and new-plan rows, with
    validity selecting each node's side); ``planes``: (R,) the physical
    plane of each claim row — contention groups by (physical plane,
    output port), so old- and new-plan claims on the same plane jam each
    other exactly like same-row claims; ``rot``: (R,) the rotating-
    priority base (matching index mod n) for ``"receiver"``.
    ``"fullest"`` grants a contested port to the claiming input with the
    deepest VOQ backlog toward it (ties to the lowest input index) —
    queue-aware arbitration needs the live ``voq`` and so cannot be
    precomputed.  Self-loop claims contend (they jam the receiver) but
    never carry traffic, matching the static path.

    Returns ``(win, lost_claims)``: the (R, n) winner mask among valid
    claims, and the number of traffic-carrying (nonself) claims that
    lost to contention.
    """
    rr, ii = np.nonzero(valid)
    cv = claims[rr, ii]
    key = planes[rr] * n + cv
    uk, inv = np.unique(key, return_inverse=True)
    contested = np.bincount(inv)[inv] > 1
    if collision == "drop":
        wflat = ~contested
    else:
        if collision == "lowest":
            order = np.argsort(inv, kind="stable")   # input index ascending
        elif collision == "receiver":
            prio = (ii - rot[rr]) % n
            order = np.lexsort((prio, inv))
        else:  # fullest: deepest VOQ toward the claimed port wins
            depth = voq[ii * n + cv]
            order = np.lexsort((ii, -depth, inv))
        io = inv[order]
        first = np.r_[True, io[1:] != io[:-1]]
        wflat = np.zeros(len(rr), dtype=bool)
        wflat[order[first]] = True
    win = np.zeros_like(valid)
    win[rr, ii] = wflat
    lost_claims = int(((cv != ii) & ~wflat).sum())
    return win, lost_claims


def _fabric_plan(
    scheds: list[Schedule],
    owner: np.ndarray,
    bits_per_slot: float,
    collision: str,
    plane_map: np.ndarray | None = None,
) -> _FabricPlan:
    """Merge per-node schedules into the fabric's effective circuit plan.

    With one schedule (all nodes agree) this is exactly the consistent
    plan of ``Schedule.slot_circuits`` — the historical single-leader
    path, preserved bit-for-bit.  With several, each input port i is
    configured by *its own* node's matching row, so a merged row is
    generally not a permutation: two or more inputs can claim the same
    output port of the same plane.  ``collision`` picks the data-plane
    resolution:

      * ``"drop"``     — every contested claim is lost (an optical
        receiver locked by two carriers recovers neither); the
        pessimistic, arbitration-free fabric.
      * ``"lowest"``   — the lowest-index input wins the port (a fixed-
        priority electrical arbiter); deterministic but unfair.
      * ``"receiver"`` — receiver-plane arbitration with rotating
        priority: matching t's port grants the contender whose index is
        next at/after ``t mod n``, spreading wins evenly over a period.

    Self-loop claims (the configuration model allows them) contend for
    the output port like any other claim but never carry traffic —
    matching the consistent path, where self-loops are dropped from the
    circuit support.  Lost capacity counts only claims that would have
    carried traffic (src != dst) had the port not been contested.

    ``"fullest"`` (queue-aware arbitration) cannot be precomputed — the
    winner depends on per-slot VOQ depth — so under disagreement the
    returned plan is *dynamic*: ``plans``/``win`` are None, ``lost`` is
    zero (the engine charges collision loss per served slot via
    :func:`_resolve_slot_claims`), and the static claim structure
    (``eff``/``nonself``/``contested``/disagreement) is still carried for
    the engine and the accounting.

    ``plane_map`` records which physical planes the schedules' logical
    plane rows occupy (identity by default) — repaired schedules rebuilt
    over the surviving planes of a degraded fabric pass the survivors.
    """
    if collision not in _COLLISIONS:
        raise ValueError(f"collision must be one of {_COLLISIONS} "
                         f"(got {collision!r})")
    if plane_map is None:
        plane_map = np.arange(scheds[0].d_hat, dtype=np.int64)
    if len(scheds) == 1:
        sched = scheds[0]
        n = sched.n
        plans = [(at * n + v, cap)
                 for at, v, cap in sched.slot_circuits(bits_per_slot)]
        perms = sched.perms
        return _FabricPlan(plans=plans, n_slots=sched.n_slots,
                           disagreement=0.0,
                           lost=np.zeros(sched.n_slots), groups=1,
                           contested=np.zeros(sched.n_slots),
                           eff=perms, nonself=perms != np.arange(n)[None, :],
                           win=np.ones(perms.shape, dtype=bool),
                           w=bits_per_slot * (1.0 - sched.recfg_frac),
                           plane_map=plane_map)

    base = scheds[0]
    n, T, d_hat, n_slots = base.n, base.T, base.d_hat, base.n_slots
    for s in scheds[1:]:
        # effective_perms (below) checks the (T, n, d_hat) footprint;
        # capacity pricing additionally needs one reconfiguration fraction
        if s.recfg_frac != base.recfg_frac:
            raise ValueError(
                "per-node schedules must share recfg_frac to be merged: "
                f"{s.recfg_frac} != {base.recfg_frac}")
    eff = effective_perms(scheds, owner)                 # (T, n)
    w = bits_per_slot * (1.0 - base.recfg_frac)
    src = np.arange(n)
    kf = (np.arange(T)[:, None] * n + eff).reshape(-1)   # claim key (t, v)
    claims = np.bincount(kf, minlength=T * n)
    contested = (claims[kf] > 1).reshape(T, n)
    nonself = eff != src[None, :]
    slot_of = np.arange(T) // d_hat
    # same claim counting as schedule_disagreement(scheds, owner), reused
    contested_n = np.bincount(
        slot_of, weights=(nonself & contested).sum(axis=1),
        minlength=n_slots)

    if collision == "fullest":
        # queue-aware winners are a per-slot function of VOQ state: the
        # engine resolves each served slot dynamically and charges its
        # collision loss there
        return _FabricPlan(plans=None, n_slots=n_slots,
                           disagreement=float(contested.mean()),
                           lost=np.zeros(n_slots), groups=len(scheds),
                           contested=contested_n,
                           eff=eff, nonself=nonself, win=None, w=w,
                           plane_map=plane_map)

    if collision == "drop":
        win = ~contested
    else:
        if collision == "lowest":
            order = np.argsort(kf, kind="stable")        # src asc per claim
        else:  # receiver: rotating priority (t mod n) over source index
            prio = (src[None, :] - np.arange(T)[:, None] % n) % n
            order = np.lexsort((prio.reshape(-1), kf))
        ks = kf[order]
        first = np.r_[True, ks[1:] != ks[:-1]]
        win = np.zeros(T * n, dtype=bool)
        win[order[first]] = True
        win = win.reshape(T, n)

    live = win & nonself
    lost = np.bincount(slot_of, weights=(nonself & ~live).sum(axis=1) * w,
                       minlength=n_slots)

    t_idx, s_idx = np.nonzero(live)
    key = slot_of[t_idx] * (n * n) + s_idx * n + eff[t_idx, s_idx]
    upid, inv = np.unique(key, return_inverse=True)
    cap = np.bincount(inv, weights=np.full(len(key), w))
    bounds = np.searchsorted(upid // (n * n), np.arange(n_slots + 1))
    pid_u = upid % (n * n)
    plans = [(pid_u[bounds[s]:bounds[s + 1]], cap[bounds[s]:bounds[s + 1]])
             for s in range(n_slots)]
    return _FabricPlan(plans=plans, n_slots=n_slots,
                       disagreement=float(contested.mean()),
                       lost=lost, groups=len(scheds),
                       contested=contested_n,
                       eff=eff, nonself=nonself, win=win, w=w,
                       plane_map=plane_map)


def _quantizer_unit(
    epoch_slots: int, k: int, d_hat: int, bits_per_slot: float
) -> float:
    """Quantization unit for an epoch's VOQ byte counters.

    A1's quantizer clips at 65535 ticks; raw epoch totals reach
    ``epoch_slots * d_hat`` slot-equivalents, which for long epochs would
    saturate silently and flatten the estimate toward uniform.  Coarsen the
    unit just enough that one epoch at line rate stays representable —
    the schedule is scale-invariant, so resolution is all that changes.
    """
    full_ticks = epoch_slots * d_hat * k / (k - 1)
    return bits_per_slot * max(1.0, full_ticks / 65535.0)


@dataclass(frozen=True)
class AdaptiveCase:
    """One closed-loop simulation case for :func:`run_adaptive`.

    ``policy``:
      * ``"adaptive"``  — cold-start on the oblivious round-robin, then at
        every epoch boundary run the Appendix-A estimation round over the
        epoch's VOQ byte counters and hot-swap to the recomputed
        ``vermilion_schedule``.
      * ``"oracle"``    — clairvoyant: recompute each epoch from the *next*
        epoch's true offered matrix (upper bound for any estimator).
      * ``"stale"``     — the oracle schedule of epoch 0, never recomputed
        (what an open control loop actually ships).
      * ``"oblivious"`` — round-robin baseline, never recomputed.

    ``gather_steps``: AllGather slots executed per estimation round; fewer
    than ``n - 1`` models a partial (mid-phase-failure) gather.  Appendix A
    has *every* ToR compute the next schedule from its own assembled
    matrix, so under a partial gather the per-node views differ (missing
    rows zero at each node) and the loop runs a true per-node control
    plane: each node hot-swaps to the schedule of *its* view (identical
    views deduplicated — a complete gather builds exactly one schedule,
    reproducing the single-leader loop bit-for-bit), and the data plane
    serves the merged, generally non-matching port configuration with
    output-port contention resolved per ``collision``.

    ``collision``: how the data plane resolves two input ports of one
    plane claiming the same output port (only possible under
    disagreement): ``"drop"`` loses every contested claim (optical
    receiver jammed by two carriers — the pessimistic default),
    ``"lowest"`` grants the lowest-index input (fixed-priority arbiter),
    ``"receiver"`` grants with rotating per-matching priority (fair
    receiver-plane arbitration).  See ``_fabric_plan``.

    ``oracle_demand``: optional (n_epochs, n, n) true demand-*rate*
    matrices for the oracle/stale policies (e.g. the generating phase-train
    matrices).  Without it they fall back to each epoch's realized offered
    matrix, which carries the heavy-tailed flow-size sampling noise an
    actual oracle of the rates would not see.

    ``construction_slots`` charges schedule construction for real: a
    recomputed schedule only takes effect that many slots into the epoch,
    with the previous (stale) schedule serving in the interim.  ``0`` (the
    default) is the free-construction idealization — the epoch layer's
    dynamics are then bit-identical to the uncharged (PR 2) control loop
    given the same schedules (note the decomposition default is now the
    Euler fast path; pass ``method="hk"`` to reproduce PR 2's schedules
    matching-for-matching as well).  Pass ``"measured"`` to charge each recompute its actual
    wall-clock construction time, converted at ``slot_seconds`` seconds per
    slot (the paper's 4.5 us slots at 100G).  A charge of a full epoch or
    more means the loop never catches up: every schedule is superseded
    before activation and the fabric serves on the cold-start plan forever
    — the epoch-length / construction-cost tradeoff the fast decomposition
    path exists to win.  Under per-node disagreement every ToR builds only
    its own schedule, all concurrently, so the measured charge is one
    local construction (total wall-clock / unique views) while
    ``AdaptiveRow.construction_s`` still accounts the fabric-wide total.
    That total is the charge the simulated dynamics see, not the host time
    construction took: the jax path's schedule cache charges a cached
    construction's time again on every hit.  The host time is the
    ``fabric.construct`` spans of :mod:`repro.core.tracing`.

    ``method`` selects the ``vermilion_schedule`` decomposition
    (``"euler"`` fast path vs ``"hk"`` reference) — combined with
    ``construction_slots="measured"`` this exposes the construction-latency
    tradeoff end to end.

    ``reconfig_penalty_slots`` charges the optical fabric's reconfiguration
    at each hot-swap: for that many slots after a new schedule activates,
    every circuit is dark (no capacity; arrivals, VOQ counters, and the
    slot rotation keep running).  Distinct from per-slot ``recfg_frac``
    (the within-slot guard band) and from ``construction_slots`` (computing
    the schedule): this is the cost of physically retargeting the switches,
    paid even for an instantly-computed schedule.  Default 0 keeps the
    epoch-layer dynamics bit-identical to the uncharged loop.  Together
    with ``epoch_slots`` it exposes the epoch-length tradeoff (short epochs
    track phases faster but pay the dark window more often) — swept in
    ``benchmarks/adaptive_bench.py run_epoch_tradeoff()``.  The dark
    window is *per plane*: only planes whose matching subsequence
    actually changed at the swap go dark (``planes_changed``); untouched
    planes keep serving through the swap.

    ``faults``: an optional timed
    :class:`repro.core.faults.FaultSchedule` injected into the run (see
    module docstring §7).  An empty schedule is bit-identical to None.

    ``activation_jitter_slots``: per-node asynchronous activation — each
    ToR activates a newly-swapped schedule at its own slot, drawn
    uniformly from the window after the swap (seeded from ``seed``).  The
    data plane serves the mixed old/new configuration through the
    transition, with output-port contention between the two generations
    re-arbitrated per slot under ``collision``.  0 (default) restores the
    synchronous all-at-once swap bit-identically.

    ``repair``: close the detection/repair loop (``policy="adaptive"``
    only).  The control plane excises senders whose gather rows stay
    silent for ``repair_after_epochs`` consecutive epochs and — from the
    data plane's per-destination / per-plane NACK counters — dead
    receivers and dead planes, then rebuilds schedules on the surviving
    matrix and planes so healthy ports reclaim the failed capacity.

    ``swap_tv_threshold``: schedule-churn hysteresis.  When > 0, an
    epoch's recompute is skipped while the normalized estimate's total-
    variation distance from the last installed estimate stays below the
    threshold *and* the repair state (excisions, surviving planes) is
    unchanged — a converged stationary estimate then stops paying the
    reconfiguration dark window, while a phase shift or a repair event
    still triggers an immediate rebuild.  0 (default) recomputes every
    epoch, the historical behavior.
    """

    wl: Workload
    epoch_slots: int
    policy: str = "adaptive"
    k: int = 3
    d_hat: int = 1
    recfg_frac: float = 0.0
    alpha: float = 0.3                # EWMA weight of the newest epoch
    gather_steps: int | None = None
    collision: str = "drop"
    normalize: str = "hose"
    seed: int = 0
    oracle_demand: np.ndarray | None = None
    construction_slots: int | str = 0
    slot_seconds: float = 4.5e-6
    method: str = "euler"
    reconfig_penalty_slots: int = 0
    faults: FaultSchedule | None = None
    activation_jitter_slots: int = 0
    repair: bool = False
    repair_after_epochs: int = 2
    swap_tv_threshold: float = 0.0
    label: str = ""
    meta: dict = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.policy not in _POLICIES:
            raise ValueError(f"policy must be one of {_POLICIES} "
                             f"(got {self.policy!r})")
        if not isinstance(self.epoch_slots, (int, np.integer)) \
                or self.epoch_slots < 1:
            raise ValueError(f"epoch_slots must be an int >= 1 "
                             f"(got {self.epoch_slots!r})")
        if self.collision not in _COLLISIONS:
            raise ValueError(f"collision must be one of {_COLLISIONS} "
                             f"(got {self.collision!r})")
        cs = self.construction_slots
        if cs != "measured" and not (isinstance(cs, (int, np.integer))
                                     and cs >= 0):
            raise ValueError(
                "construction_slots must be a nonnegative int or "
                f"'measured' (got {cs!r})")
        if self.slot_seconds <= 0:
            raise ValueError(f"slot_seconds must be positive "
                             f"(got {self.slot_seconds!r})")
        if not isinstance(self.reconfig_penalty_slots, (int, np.integer)) \
                or self.reconfig_penalty_slots < 0:
            raise ValueError(
                "reconfig_penalty_slots must be a nonnegative int "
                f"(got {self.reconfig_penalty_slots!r})")
        gs = self.gather_steps
        if gs is not None and not (0 <= gs <= self.wl.n - 1):
            raise ValueError(
                f"gather_steps must be in [0, n - 1] = [0, {self.wl.n - 1}] "
                f"— a ring AllGather finishes in n - 1 steps (got {gs!r})")
        if not isinstance(self.activation_jitter_slots, (int, np.integer)) \
                or self.activation_jitter_slots < 0:
            raise ValueError(
                "activation_jitter_slots must be a nonnegative int "
                f"(got {self.activation_jitter_slots!r})")
        if not isinstance(self.repair_after_epochs, (int, np.integer)) \
                or self.repair_after_epochs < 1:
            raise ValueError(f"repair_after_epochs must be an int >= 1 "
                             f"(got {self.repair_after_epochs!r})")
        if self.swap_tv_threshold < 0:
            raise ValueError(f"swap_tv_threshold must be nonnegative "
                             f"(got {self.swap_tv_threshold!r})")
        if self.repair and self.policy != "adaptive":
            raise ValueError(
                "repair requires policy='adaptive' (the other policies "
                f"never recompute; got policy={self.policy!r})")
        if self.faults is not None:
            if not isinstance(self.faults, FaultSchedule):
                raise ValueError("faults must be a FaultSchedule "
                                 f"(got {type(self.faults).__name__})")
            self.faults.validate(self.wl.n, self.d_hat)


@dataclass
class AdaptiveRow:
    label: str
    policy: str
    result: SimResult
    epoch_utilization: np.ndarray   # (n_epochs,) delivered / epoch capacity
    epoch_estimate_tv: np.ndarray   # (n_epochs,) estimate-vs-truth total-
                                    # variation distance (nan if no estimate)
    recomputes: int                 # schedule recomputations performed
    meta: dict
    stale_slots: int = 0            # slots served by an outdated schedule
                                    # while construction was still running
    construction_s: float = 0.0     # the modelled construction charge
                                    # (summed over all unique per-node
                                    # views), not a layer time: on the jax
                                    # path a sched_cache hit adds the
                                    # cached construction's time again
    dark_slots: int = 0             # slots lost to reconfiguration darkness
                                    # (reconfig_penalty_slots per hot-swap)
    epoch_disagreement: np.ndarray = None   # type: ignore[assignment]
                                    # (n_epochs,) contested fraction of the
                                    # installed plan's (matching, port)
                                    # claims, time-weighted over the epoch's
                                    # slots (reconfiguration-dark slots
                                    # serve nothing and contribute zero,
                                    # same time base as collision loss)
    epoch_collision_loss: np.ndarray = None  # type: ignore[assignment]
                                    # (n_epochs,) fraction of the epoch's
                                    # fabric capacity lost to output-port
                                    # collisions
    collision_lost_bits: float = 0.0  # total capacity lost to collisions
    schedule_groups_max: int = 1    # most distinct per-node schedules that
                                    # were ever live at once (1 = the fabric
                                    # never disagreed)
    fault_lost_bits: float = 0.0    # VOQ bits stranded by abrupt tor_fail
    fault_refused_bits: float = 0.0  # arrivals refused at drained/dead ToRs
    dark_plane_slots: float = 0.0   # plane-slots dark to reconfiguration
                                    # (per-plane dark: a full-fabric swap
                                    # charges d_hat per dark slot)
    excised_nodes: int = 0          # ToRs the repair loop excised
    excised_planes: int = 0         # planes the repair loop excised


def _run_adaptive_case(case: AdaptiveCase, bits_per_slot: float,
                       san=None) -> AdaptiveRow:
    if case.policy not in _POLICIES:
        raise ValueError(case.policy)
    if case.epoch_slots <= 0:
        raise ValueError("epoch_slots must be positive")
    cs = case.construction_slots
    measured = cs == "measured"
    if not measured and not (isinstance(cs, (int, np.integer)) and cs >= 0):
        raise ValueError(
            "construction_slots must be a nonnegative int or 'measured'")
    if measured and case.slot_seconds <= 0:
        raise ValueError("slot_seconds must be positive")
    penalty = int(case.reconfig_penalty_slots)
    if penalty < 0:
        raise ValueError("reconfig_penalty_slots must be nonnegative")
    if case.collision not in _COLLISIONS:
        raise ValueError(f"collision must be one of {_COLLISIONS} "
                         f"(got {case.collision!r})")
    wl, n = case.wl, case.wl.n
    E, H = case.epoch_slots, wl.horizon
    n_epochs = -(-H // E)
    if san is not None:
        # any violation below names the offending case of the grid
        san.set_context(f"case={case.label}")
        san.check_workload(wl)
    san_w = bits_per_slot * (1.0 - case.recfg_frac)

    # flow state shared across epochs — a schedule hot-swap never resets it
    pid = (wl.src * n + wl.dst).astype(np.int64)
    f_size = wl.size.astype(np.float64)
    fct = np.full(wl.num_flows, np.inf)
    credit = _CreditState(n * n, pid, f_size, wl.arrival, fct)
    valid = wl.arrival < H
    order = np.argsort(wl.arrival, kind="stable")
    order = order[valid[order]]
    bucket = np.searchsorted(wl.arrival[order], np.arange(H + 1))
    voq = np.zeros(n * n)

    # true per-epoch offered matrices (oracle policy + estimate-error
    # metric); dense by design: the O(n^2) control plane owns these
    true_epoch = np.zeros((n_epochs, n, n))  # lint: allow-dense
    np.add.at(true_epoch,
              (wl.arrival[order] // E, wl.src[order], wl.dst[order]),
              f_size[order])
    oracle_m = case.oracle_demand
    if oracle_m is not None and oracle_m.shape != (n_epochs, n, n):
        raise ValueError(
            f"oracle_demand shape {oracle_m.shape} != {(n_epochs, n, n)}")
    if oracle_m is None:
        oracle_m = true_epoch / E

    # per-node VOQ byte counters, accumulated over the running epoch (A2);
    # one fleet estimator batches all n per-node EWMAs (row i = node i)
    counters = np.zeros((n, n))
    fleet = TrafficEstimator.fleet(n, alpha=case.alpha)
    q_unit = _quantizer_unit(E, case.k, case.d_hat, bits_per_slot)

    construction_s = 0.0
    last_construction = 0.0

    def consistent_plan(sched: Schedule,
                        plane_map: np.ndarray | None = None) -> _FabricPlan:
        fp = _fabric_plan([sched], np.zeros(n, dtype=np.int64),
                          bits_per_slot, case.collision, plane_map=plane_map)
        if san is not None:
            san.check_schedule(sched)
            san.check_fabric_plan(fp, n, sched.d_hat, san_w)
        return fp

    def vsched(m: np.ndarray, seed: int) -> Schedule:
        nonlocal construction_s, last_construction
        t0 = time.perf_counter()
        s = vermilion_schedule(
            m, k=case.k, d_hat=case.d_hat, recfg_frac=case.recfg_frac,
            seed=seed, normalize=case.normalize, method=case.method)
        last_construction = time.perf_counter() - t0
        construction_s += last_construction
        return s

    def vsched_per_node(views, seed: int, unique, d_hat: int | None = None,
                        plane_map: np.ndarray | None = None) -> _FabricPlan:
        nonlocal construction_s, last_construction
        dh = case.d_hat if d_hat is None else d_hat
        t0 = time.perf_counter()
        scheds, owner = per_node_schedules(
            views, k=case.k, d_hat=dh, recfg_frac=case.recfg_frac,
            seed=seed, normalize=case.normalize, method=case.method,
            unique=unique)
        dt = time.perf_counter() - t0
        construction_s += dt
        # every ToR builds only its own schedule, all concurrently: the
        # fabric waits for one local construction, estimated as the mean
        # over the (equal-sized) unique views rather than the sum (with a
        # complete gather there is exactly one view, so this reduces to
        # the single-schedule charge exactly)
        last_construction = dt / len(scheds)
        fp = _fabric_plan(scheds, owner, bits_per_slot, case.collision,
                          plane_map=plane_map)
        if san is not None:
            for s in scheds:       # pre-merge: every row a permutation
                san.check_schedule(s)
            san.check_fabric_plan(fp, n, dh, san_w)
        return fp

    if case.policy in ("oracle", "stale"):
        fp = consistent_plan(vsched(oracle_m[0], case.seed))
    else:  # adaptive cold start (no estimate yet) and oblivious baseline
        fp = consistent_plan(oblivious_schedule(n, d_hat=case.d_hat,
                                                recfg_frac=case.recfg_frac))
    sched_t0 = 0                    # slot the current plan was installed
    pending: tuple[int, _FabricPlan] | None = None

    delivered_ep = np.zeros(n_epochs)
    est_tv = np.full(n_epochs, np.nan)
    dis_ep = np.zeros(n_epochs)     # summed per-slot plan disagreement
    coll_ep = np.zeros(n_epochs)    # bits of capacity lost to collisions
    recomputes = 0
    stale_slots = 0
    dark_slots = 0
    groups_max = 1
    injected_cum = 0.0              # sanitizer's running bit ledger

    # --- degraded-service state (all inert on the historical fast path) --
    src0 = np.arange(n)
    tl = case.faults.compile(n, case.d_hat) if case.faults else None
    fault_lost = 0.0                # VOQ bits stranded by tor_fail
    fault_refused = 0.0             # arrivals refused at drained/dead ToRs
    plane_dark_until = np.zeros(case.d_hat, dtype=np.int64)
    dark_plane_slots = 0.0
    jit = int(case.activation_jitter_slots)
    act_rng = np.random.default_rng([abs(int(case.seed)), 0xAC7])
    # (old_fp, old_t0, per-node activation slots, end slot) while a
    # jittered swap is mid-transition, else None
    transition: tuple[_FabricPlan, int, np.ndarray, int] | None = None
    # repair-loop detection state
    tx_silent = np.zeros(n, dtype=np.int64)   # consecutive silent epochs
    excised_tx = np.zeros(n, dtype=bool)
    excised_rx = np.zeros(n, dtype=bool)
    plane_alive = np.ones(case.d_hat, dtype=bool)  # repair's fabric view
    rx_want = np.zeros(n)
    rx_nack = np.zeros(n)
    plane_want = np.zeros(case.d_hat)
    plane_nack = np.zeros(case.d_hat)
    # churn hysteresis: normalized estimate + repair state at last rebuild
    last_est: np.ndarray | None = None
    last_sig: tuple | None = None

    def activate(new_fp: _FabricPlan, s: int) -> None:
        """Install a newly built plan at slot ``s``: darken only the
        planes whose matchings actually changed, and (under activation
        jitter) open the mixed old/new transition window."""
        nonlocal fp, sched_t0, transition, groups_max
        if penalty:
            om, nm = fp.plane_map, new_fp.plane_map
            if (fp.eff is None or new_fp.eff is None
                    or fp.eff.shape != new_fp.eff.shape
                    or not np.array_equal(om, nm)):
                plane_dark_until[nm] = s + penalty   # everything retargets
            else:
                ch = planes_changed(fp.eff, new_fp.eff, len(nm))
                plane_dark_until[nm[ch]] = s + penalty
        if jit:
            act = s + act_rng.integers(0, jit + 1, size=n)
            transition = (fp, sched_t0, act, s + jit + 1)
        fp, sched_t0 = new_fp, s
        groups_max = max(groups_max, new_fp.groups)

    for slot in range(H):
        if pending is not None and slot >= pending[0]:
            swap_fp = pending[1]
            pending = None
            activate(swap_fp, slot)
        if slot and slot % E == 0:
            epoch = slot // E
            if san is not None:
                san.set_context(
                    f"case={case.label} epoch={epoch} slot={slot}")
                # per-epoch bit ledger: collision loss and dark windows are
                # capacity-side, so queued bits close the ledger exactly;
                # tor_fail strands bits, charged to the fault_lost term
                san.check_conservation(
                    injected_cum, float(delivered_ep.sum()),
                    float(voq.sum()), fault_lost=fault_lost,
                    label=f"adaptive:epoch{epoch - 1}:conservation")
            repair_now = case.repair and case.policy == "adaptive"
            if repair_now:
                # dead senders: gather rows silent for repair_after_epochs
                # consecutive epochs (the fleet EWMA would otherwise keep
                # allocating circuits to a row that stopped refreshing)
                silent = counters.sum(axis=1) <= 0.0
                tx_silent[:] = np.where(silent, tx_silent + 1, 0)
                excised_tx |= tx_silent >= case.repair_after_epochs
                # dead receivers / planes: the data plane counts wanting
                # circuits whose far side never carried (fault-masked) as
                # NACKs; a near-total NACK ratio flags the target.  A dead
                # plane NACKs ~all its claims, a dead ToR ~all claims
                # toward it on every plane; a single dead port sits at
                # ~1/d_hat on both counters and is left in place
                # (degraded service, no excision).
                excised_rx |= (rx_want > 10) & (rx_nack > 0.9 * rx_want)
                plane_alive &= ~((plane_want > 10)
                                 & (plane_nack > 0.9 * plane_want))
                rx_want[:] = 0.0
                rx_nack[:] = 0.0
                plane_want[:] = 0.0
                plane_nack[:] = 0.0
            swap = None
            if case.policy == "adaptive":
                views = estimate_all_views(
                    counters, fleet, case.k, q_unit,
                    steps=case.gather_steps)
                if san is not None:
                    san.check_views(views)
                if repair_now and (excised_tx.any() or excised_rx.any()):
                    # excise failed senders/receivers from the estimate so
                    # the rebuild allocates their capacity to healthy ports
                    views = views.excise(excised_tx, excised_rx)
                t = true_epoch[epoch - 1]
                masks, owner = views.unique()
                # estimate error: per-node TV distance vs the epoch truth,
                # averaged over nodes (one term per unique view, weighted
                # by its group size — a complete gather has one group and
                # reduces to the historical single-estimate metric).  The
                # per-view normalizations differ, so the metric is
                # inherently O(G n^2); G == 1 on the consistent path, and
                # under full disagreement (G == n) schedule construction
                # already dominates this same order of work.
                counts = np.bincount(owner, minlength=masks.shape[0])
                t_sum = t.sum()
                tn = t / t_sum if t_sum > 0 else None
                # cheap emptiness predicate per group (exact for
                # nonnegative rows); the actual normalizer below keeps the
                # historical full-matrix summation order bit-for-bit
                nonempty = (masks @ views.rows.sum(axis=1)) > 0
                tvs, wts = [], []
                for g in range(masks.shape[0]):
                    if tn is not None and nonempty[g]:
                        est_g = views.rows * masks[g][:, None]
                        tvs.append(0.5 * np.abs(
                            est_g / est_g.sum() - tn).sum())
                        wts.append(counts[g])
                if tvs:
                    est_tv[epoch - 1] = float(np.average(tvs, weights=wts))
                build = views.rows.sum() > 0
                if build and case.swap_tv_threshold > 0.0:
                    # churn hysteresis: skip the rebuild while the
                    # estimate hasn't materially moved and the repair
                    # state (excisions, surviving planes) is unchanged —
                    # a converged stationary estimate stops paying the
                    # reconfiguration dark window
                    cur = views.rows / views.rows.sum()
                    sig = (plane_alive.tobytes(), excised_tx.tobytes(),
                           excised_rx.tobytes())
                    if (last_est is not None and sig == last_sig
                            and 0.5 * np.abs(cur - last_est).sum()
                                < case.swap_tv_threshold):
                        build = False
                    else:
                        last_est, last_sig = cur, sig
                if build:
                    if repair_now and not plane_alive.all():
                        dl = int(plane_alive.sum())
                        if dl > 0:  # rebuild over the surviving planes
                            swap = vsched_per_node(
                                views, case.seed + epoch, (masks, owner),
                                d_hat=dl,
                                plane_map=np.nonzero(plane_alive)[0])
                    else:
                        swap = vsched_per_node(views, case.seed + epoch,
                                               (masks, owner))
            elif case.policy == "oracle":
                if oracle_m[epoch].sum() > 0:
                    swap = consistent_plan(
                        vsched(oracle_m[epoch], case.seed + epoch))
            if swap is not None:
                recomputes += 1
                charge = (int(np.ceil(last_construction / case.slot_seconds))
                          if measured else int(cs))
                if charge == 0:
                    pending = None   # a zero-cost swap supersedes any pending
                    activate(swap, slot)
                else:
                    # the stale schedule keeps serving until construction
                    # finishes; a recompute next epoch supersedes this one
                    pending = (slot + charge, swap)
            counters[:] = 0.0
        if pending is not None:
            stale_slots += 1

        if tl is not None:
            for f in tl.advance(slot):  # abrupt death strands the VOQs
                fail_row = voq[f * n:(f + 1) * n]
                fault_lost += float(fail_row.sum())
                fail_row[:] = 0.0

        newf = order[bucket[slot]:bucket[slot + 1]]
        if newf.size and tl is not None and not tl.clean:
            ok = tl.inject_ok[wl.src[newf]]
            if not ok.all():        # refused at the ingress: never a VOQ bit
                fault_refused += float(f_size[newf[~ok]].sum())
                newf = newf[ok]
        if newf.size:
            np.add.at(voq, pid[newf], f_size[newf])
            np.add.at(counters, (wl.src[newf], wl.dst[newf]), f_size[newf])
            credit.arrive(newf)
            if san is not None:
                injected_cum += float(f_size[newf].sum())

        dark = plane_dark_until[fp.plane_map] > slot
        if dark.all():              # every plane retargeting: nothing runs
            dark_slots += 1         # (fully dark slots serve nothing, so
            dark_plane_slots += float(dark.sum())
            continue                # they contribute zero disagreement and
                                    # zero collision loss — one time base
                                    # for both per-epoch metrics)
        if transition is not None and slot >= transition[3]:
            transition = None

        faulty = tl is not None and not tl.clean
        if (not faulty and transition is None and not dark.any()
                and fp.plans is not None):
            # historical fast path, bit-identical to the pre-fault engine
            dis_ep[slot // E] += fp.disagreement
            ps = (slot - sched_t0) % fp.n_slots
            coll_ep[slot // E] += fp.lost[ps]
            spid, scap = fp.plans[ps]
            q = voq[spid]
            tx = np.minimum(q, scap)
            voq[spid] = q - tx
            delivered_ep[slot // E] += tx.sum()
            credit.credit_pairs(spid, tx, slot)
            continue

        # --- degraded-service path: rebuild this slot from raw claims ---
        dark_plane_slots += float(dark.sum())
        dis_ep[slot // E] += fp.disagreement
        if transition is None:
            dl = len(fp.plane_map)
            lo = ((slot - sched_t0) % fp.n_slots) * dl
            hi = min(lo + dl, fp.eff.shape[0])
            rows = fp.eff[lo:hi]
            planes = fp.plane_map[:hi - lo]
            live = (plane_dark_until[planes] <= slot)[:, None]
            nonself = fp.nonself[lo:hi]
            if fp.win is not None:  # static arbitration, precomputed
                win = fp.win[lo:hi]
                lost_bits = float((nonself & live & ~win).sum()) * fp.w
            else:                   # queue-aware: resolve on live VOQs
                win, lost_claims = _resolve_slot_claims(
                    rows, np.broadcast_to(live, rows.shape).copy(),
                    planes, (lo + np.arange(hi - lo)) % n,
                    case.collision, voq, n)
                lost_bits = lost_claims * fp.w
            served = win & nonself & live
        else:
            # mixed old/new activation: each node serves its own
            # generation; contention between the generations on the same
            # physical plane is re-arbitrated per slot
            ofp, ot0, act, _ = transition
            blocks = []
            for p, t0 in ((ofp, ot0), (fp, sched_t0)):
                dlp = len(p.plane_map)
                lo = ((slot - t0) % p.n_slots) * dlp
                hi = min(lo + dlp, p.eff.shape[0])
                blocks.append((p.eff[lo:hi], p.plane_map[:hi - lo],
                               (lo + np.arange(hi - lo)) % n))
            rows = np.vstack([b[0] for b in blocks])
            planes = np.concatenate([b[1] for b in blocks])
            rot = np.concatenate([b[2] for b in blocks])
            gen_new = np.zeros(len(rows), dtype=bool)
            gen_new[len(blocks[0][0]):] = True
            on = act <= slot
            vmask = np.where(gen_new[:, None], on[None, :], ~on[None, :])
            vmask &= (plane_dark_until[planes] <= slot)[:, None]
            win, lost_claims = _resolve_slot_claims(
                rows, vmask, planes, rot, case.collision, voq, n)
            lost_bits = lost_claims * fp.w
            nonself = rows != src0[None, :]
            served = win & nonself
        coll_ep[slot // E] += lost_bits

        if faulty:                  # fault masking after arbitration: a
            lok = tl.link_ok()      # dead claim still jams its port
            txok = lok.T[planes]
            rxok = lok[rows, planes[:, None]]
            if case.repair:
                pidb = src0[None, :] * n + rows
                wanting = served & (voq[pidb] > 0.0)
                np.add.at(plane_want, planes,
                          wanting.sum(axis=1).astype(float))
                np.add.at(plane_nack, planes,
                          (wanting & ~(txok & rxok)).sum(axis=1)
                          .astype(float))
                np.add.at(rx_want, rows[wanting], 1.0)
                np.add.at(rx_nack, rows[wanting & ~rxok], 1.0)
            served &= txok & rxok

        srr, sii = np.nonzero(served)
        if srr.size:
            spid, inv = np.unique(sii * n + rows[srr, sii],
                                  return_inverse=True)
            scap = np.bincount(inv).astype(np.float64) * fp.w
            q = voq[spid]
            tx = np.minimum(q, scap)
            voq[spid] = q - tx
            delivered_ep[slot // E] += tx.sum()
            credit.credit_pairs(spid, tx, slot)

    if san is not None:
        delivered_all = float(delivered_ep.sum())
        san.check_conservation(injected_cum, delivered_all,
                               float(voq.sum()), fault_lost=fault_lost,
                               label="adaptive:final:conservation")
        rem, completed = credit.remaining_active()
        # bits stranded by tor_fail stay on their never-completing flows
        # inside remaining_active, so the closure needs no fault term
        san.check_credit_closure(injected_cum, delivered_all, rem,
                                 completed, label="adaptive:credit")
        san.set_context(None)
    ep_len = np.minimum(E, H - E * np.arange(n_epochs))
    ep_cap = ep_len * n * case.d_hat * bits_per_slot
    ideal = H * n * case.d_hat * bits_per_slot
    result = SimResult(
        fct_slots=fct,
        flow_size=wl.size,
        utilization=float(delivered_ep.sum()) / ideal,
        delivered_bits=float(delivered_ep.sum()),
        offered_bits=float(wl.size[valid].sum()),
        fault_lost_bits=fault_lost,
        fault_refused_bits=fault_refused,
    )
    return AdaptiveRow(
        label=case.label, policy=case.policy, result=result,
        epoch_utilization=delivered_ep / ep_cap, epoch_estimate_tv=est_tv,
        recomputes=recomputes, meta=dict(case.meta),
        stale_slots=stale_slots, construction_s=construction_s,
        dark_slots=dark_slots,
        epoch_disagreement=dis_ep / ep_len,
        epoch_collision_loss=coll_ep / ep_cap,
        collision_lost_bits=float(coll_ep.sum()),
        schedule_groups_max=groups_max,
        fault_lost_bits=fault_lost,
        fault_refused_bits=fault_refused,
        dark_plane_slots=dark_plane_slots,
        excised_nodes=int((excised_tx | excised_rx).sum()),
        excised_planes=int((~plane_alive).sum()))


def run_adaptive(
    cases: list[AdaptiveCase], bits_per_slot: float,
    backend: str = "numpy",
    sanitize: bool | None = None,
) -> list[AdaptiveRow]:
    """Closed-loop epoch-driven simulation of each case (see
    :class:`AdaptiveCase`); results come back in input order.

    Every case advances through the same sparse single-hop per-slot engine
    as :func:`run_sweep` (``policy="oblivious"`` reproduces
    ``simulate(oblivious_schedule(n), wl)`` exactly, FCT-for-FCT); the
    epoch layer on top harvests the VOQ byte counters each boundary, runs
    the estimation round, and swaps in the recomputed circuit plan while
    VOQs, in-flight flows, and the processor-sharing credit state carry
    over untouched.  Each node swaps to the schedule of *its own*
    (possibly partial) view; when views disagree the served plan is the
    collision-resolved merge of the per-node schedules (see
    :class:`AdaptiveCase` — ``gather_steps``, ``collision``) and the rows
    report per-epoch disagreement and collision-loss alongside
    utilization.

    ``backend="jax"`` runs the whole grid through one jitted device scan
    per node count: the control plane (estimation → per-node schedules →
    collision-resolved plans → activation/dark windows) is replayed
    host-side exactly — the counters that drive it accumulate *arrivals*
    only, so the full epoch trajectory is computable before any serving —
    and the resulting per-slot circuit plans for every case batch through
    the shared single-hop kernel, with per-flow FCTs recovered by the
    host credit replay.  Cases the device path cannot express raise up
    front — ``NotImplementedError`` for fault injection (a numpy-only
    feature, ROADMAP follow-up), ``ValueError`` for ``repair=True``,
    ``collision="fullest"``, and activation jitter; use the numpy backend
    for those.

    ``sanitize``: run the :mod:`repro.analysis.sanitize` contract checks —
    per-epoch bit conservation, fabric-plan validity, disagreement closure
    — on every case (default: the ``REPRO_SANITIZE`` env var); results are
    bit-identical either way.
    """
    if backend not in ("numpy", "jax"):
        raise ValueError(
            f"backend must be 'numpy' or 'jax' (got {backend!r})")
    san = make_sanitizer(sanitize)
    if backend == "jax":
        for i, case in enumerate(cases):
            _check_adaptive_jax_supported(case, i)
        rows_out: list[AdaptiveRow | None] = [None] * len(cases)
        groups: dict[int, list[int]] = {}
        for i, case in enumerate(cases):
            groups.setdefault(case.wl.n, []).append(i)
        for idxs in groups.values():
            batch_rows = _run_adaptive_batch_jax(
                [cases[i] for i in idxs], bits_per_slot, san=san)
            for i, row in zip(idxs, batch_rows):
                rows_out[i] = row
        return rows_out  # type: ignore[return-value]
    return [_run_adaptive_case(case, bits_per_slot, san=san)
            for case in cases]


def _check_adaptive_jax_supported(case: "AdaptiveCase", i: int) -> None:
    """Raise for AdaptiveCase features the jax backend cannot express
    (they need per-slot host decisions inside the serving loop).

    Fault injection raises ``NotImplementedError`` — the feature exists on
    the numpy backend and is an acknowledged gap on this one (ROADMAP's
    fullest/faults follow-up; pinned in tests/test_faults.py).  The other
    rejections stay ``ValueError`` (invalid configuration for this
    backend)."""
    if case.faults:
        raise NotImplementedError(
            f"cases[{i}] ({case.label!r}): fault injection is not "
            "implemented on the jax backend — it requires per-slot host "
            "decisions the device scan cannot replay; use backend='numpy' "
            "for this case")
    reason = None
    if case.repair:
        reason = "the repair loop (repair=True)"
    elif case.collision == "fullest":
        reason = "queue-aware arbitration (collision='fullest')"
    elif case.activation_jitter_slots > 0:
        reason = "per-node activation jitter"
    if reason is not None:
        raise ValueError(
            f"cases[{i}] ({case.label!r}): {reason} is only supported on "
            "the numpy backend — it requires per-slot host decisions the "
            "device scan cannot replay; use backend='numpy' for this case")


# ---------------------------------------------------------------------------
# JAX backend: jitted scan kernels + shared compile cache
# ---------------------------------------------------------------------------

# The kernels are built (and jit-wrapped) ONCE per process, so jax's own
# shape-keyed trace cache persists across run_sweep calls: repeated
# same-shape sweeps reuse the compiled executable instead of retracing the
# scan body each call.  All inputs are padded into shape buckets so
# near-miss sizes share a compile — one compile per (B, n, H_pad, ...)
# signature.  _JAX_TRACES counts actual retraces (the kernel's Python body
# only runs while jax traces it); a regression test pins it.
_JAX_FNS: dict[str, "callable"] = {}
_JAX_TRACES = {"agg": 0, "twohop_dense": 0, "twohop_sparse": 0,
               "singlehop": 0, "twohop_fct": 0}
# Per-kernel call counts and the padded shape buckets seen, for
# compile_cache_stats(): hits = calls - traces (a call whose padded
# signature was already compiled never re-enters the traced Python body).
_JAX_CALLS: dict[str, int] = {}
_JAX_SHAPES: dict[str, set] = {}

_PAD_H = 128         # horizon           -> multiple of 128 slots
_PAD_K = 32          # arrivals per slot -> multiple of 32 flows
_PAD_J = 64          # circuit support   -> multiple of 64 pairs

# f32 serving vs f64 flow ledger: when a credited amount lands within this
# relative distance of a pair's exact remaining bits, treat the pair as
# fully drained (f32 has ~1.2e-7 ulp; slack covers a few hundred slots of
# accumulated rounding in the per-slot tx sums).
_F32_DRAIN_REL = 2e-5

# Water-fill completion-boundary forgiveness for the pro-rata relay replay
# (no per-pair drain observation there): scaled by the pair's cumulative
# water level, since that is where credited-amount rounding accumulates.
# Kept an order of magnitude above measured drift (~2.5e-8 of the level)
# but tight enough that deep-backlog levels do not complete flows early.
_F32_LEVEL_REL = 1e-6

# The two-hop FCT kernel carries the full per-(at, src, dst) relay
# attribution tensor (B, n, n, n) and emits per-slot (B, n, n) delivered
# matrices — affordable at small n only.  Beyond these bounds the jax
# two-hop path stays aggregate-only (fct_slots all inf).
_TWOHOP_FCT_MAX_N = 64


def _twohop_fct_ok(B: int, n: int, H_pad: int) -> bool:
    return n <= _TWOHOP_FCT_MAX_N and H_pad * B * n * n * 4 <= (1 << 27)


def _record_call(kernel: str, bucket: tuple) -> None:
    _JAX_CALLS[kernel] = _JAX_CALLS.get(kernel, 0) + 1
    _JAX_SHAPES.setdefault(kernel, set()).add(bucket)


def compile_cache_stats() -> dict:
    """Introspect the jax compile cache: per-kernel trace counts, call
    counts, cache hits (calls that reused a compiled executable), and the
    padded shape buckets seen so far this process.

    A healthy sweep shows ``traces == len(shape_buckets)`` and hits
    growing with every repeated same-shape call; a trace count above the
    bucket count means the padding discipline regressed (see the
    ``assert_no_retrace`` fixture).
    """
    stats = {}
    for kernel, traces in _JAX_TRACES.items():
        calls = _JAX_CALLS.get(kernel, 0)
        stats[kernel] = {
            "traces": traces,
            "calls": calls,
            "hits": max(calls - traces, 0),
            "shape_buckets": sorted(_JAX_SHAPES.get(kernel, set())),
        }
    return stats


# Dimension names of each kernel's _record_call bucket tuple, in order —
# the contract between the compile cache and the IR analyzer
# (repro.analysis.ir traces kernels at these padded signatures).
KERNEL_BUCKET_DIMS = {
    "agg": ("B", "n", "H_pad"),
    "twohop_dense": ("B", "n", "H_pad", "K"),
    "twohop_fct": ("B", "n", "H_pad", "K"),
    "twohop_sparse": ("B", "n", "H_pad", "K", "J", "P"),
    "singlehop": ("B", "n", "H_pad", "K", "Jtot"),
}


def kernel_abstract_inputs(
    kernel: str, *, B: int = 2, n: int = 8, H_pad: int | None = None,
    ns: int | None = None, K: int | None = None, J: int | None = None,
    P: int | None = None, Jtot: int | None = None,
) -> tuple:
    """Abstract input specs (``jax.ShapeDtypeStruct``) for a cached kernel.

    Mirrors, shape- and dtype-exactly, the padded runtime signature the
    engines feed each ``_JAX_FNS`` kernel (same ``_PAD_H``/``_PAD_K``/
    ``_PAD_J`` bucketing discipline), so ``jax.make_jaxpr`` over these
    specs reproduces the jaxpr the compile cache actually traces.  This is
    the entry point of the IR analyzer (:mod:`repro.analysis.ir`).

    Dimensions: ``B`` cases, ``n`` nodes, ``H_pad`` padded horizon, ``ns``
    capacity-LUT rows (sum of per-case ``n_slots``; any positive value is
    shape-valid), ``K`` padded arrivals per slot, ``(P, J)`` two-hop
    support plans x padded support size, ``Jtot`` total padded circuit
    columns of the single-hop plan.
    """
    import jax
    import jax.numpy as jnp
    if kernel not in _JAX_TRACES:
        raise ValueError(
            f"unknown kernel {kernel!r} (have {sorted(_JAX_TRACES)})")
    H_pad = _PAD_H if H_pad is None else int(H_pad)
    ns = B * n if ns is None else int(ns)
    K = _PAD_K if K is None else int(K)
    J = _PAD_J if J is None else int(J)
    P = 1 if P is None else int(P)
    Jtot = B * _pad_to(n, _PAD_J) if Jtot is None else int(Jtot)
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    caps_flat = S((ns, n, n), f32)
    cap_idx = S((H_pad, B), i32)
    apos = S((H_pad, K, 3), i32)
    asz = S((H_pad, K), f32)
    live = S((H_pad, B), f32)
    direct = S((B, 1, 1), f32)
    if kernel == "agg":
        return (caps_flat, cap_idx, S((H_pad, B, n, n), f32), live)
    if kernel in ("twohop_dense", "twohop_fct"):
        return (caps_flat, cap_idx, apos, asz, live, direct)
    if kernel == "twohop_sparse":
        return (caps_flat, cap_idx, apos, asz, live, S((H_pad,), i32),
                S((P, J), i32), S((P, J), i32), S((P, J), i32),
                S((P, J), jnp.bool_), direct)
    # singlehop
    return (S((B * n * n,), f32), S((H_pad, K), i32), S((H_pad, K), f32),
            S((H_pad, Jtot), i32), S((H_pad, Jtot), f32))


def kernel_bucket_inputs(kernel: str, bucket: tuple) -> tuple:
    """Abstract specs from a live ``compile_cache_stats`` shape bucket."""
    dims = dict(zip(KERNEL_BUCKET_DIMS[kernel], bucket))
    return kernel_abstract_inputs(kernel, **dims)


def jax_kernels() -> dict:
    """Public handle on the jitted kernel table (for the IR analyzer and
    benchmarks); builds the kernels on first use."""
    return _jax_fns()

# Dense (einsum over the full (B, n, n) relay-bucket matrix) vs sparse
# (padded circuit-support gathers + segment_sum) two-hop kernel crossover,
# picked by n like ``round_matrices`` picks its batching: the dense step's
# O(n^3) offload einsum lowers to a batched matmul and beats the sparse
# step's O(n^2 d_hat) scalarized gather/scatter constants until n is large
# (benchmarks/fct_bench.py ``twohop_table`` on the 2-core CI CPU: dense
# ~1.6x ahead at n = 128, ~par at 256, behind from n ~ 384 on).
_TWOHOP_DENSE_MAX_N = 256

_JEPS = 1e-12


def _pad_to(x: int, q: int) -> int:
    return max(q, -(-x // q) * q)


def _jax_fns() -> dict:
    """Build (once) the jitted scan kernels behind ``backend="jax"``."""
    if _JAX_FNS:
        return _JAX_FNS
    import jax
    import jax.numpy as jnp

    # Kernels return their final carry alongside the per-slot outputs so
    # the sanitizer can close the bit ledger (injected = delivered +
    # queued) without re-running anything; the carry is aggregate VOQ /
    # relay state the scan holds anyway.

    def agg(caps_flat, cap_idx, arr, live):
        _JAX_TRACES["agg"] += 1
        B, n = arr.shape[1], arr.shape[2]

        def step(voq, inp):
            idx, a, lv = inp
            voq = voq + a
            cap = caps_flat[idx] * lv[:, None, None]
            tx = jnp.minimum(voq, cap)
            return voq - tx, tx.sum(axis=(1, 2))

        voq_f, delivered = jax.lax.scan(
            step,
            jnp.zeros((B, n, n), jnp.float32),  # lint: allow-dense
            (cap_idx, arr, live))
        return delivered, voq_f

    # Both two-hop kernels carry relay state as per-(at, dst) bucket
    # TOTALS only (the NumPy engine's maintained RS array, without the
    # per-source relay tensor behind it): the jax backend reports
    # aggregates, so the source-attribution axis — which exists in the
    # NumPy engine purely to credit per-flow completions, and whose
    # strided drain kept the PR 1 two-hop speedup under target — drops
    # out exactly.  Every transferred quantity below (drain = min(total,
    # cap), offload splits, immediate landings) depends on bucket totals
    # alone, so delivered bits / second-hop bits match the full engine
    # float-for-float while the scan carry shrinks from O(B n^3) to
    # O(B n^2) and the strided scatters disappear entirely.

    def twohop_dense(caps_flat, cap_idx, apos, asz, live, direct):
        _JAX_TRACES["twohop_dense"] += 1
        B, n = cap_idx.shape[1], caps_flat.shape[1]

        def step(carry, inp):
            voq, RS = carry                      # RS[b, at, dst] totals
            cidx, pos, sz, lv = inp
            voq = voq.at[pos[:, 0], pos[:, 1], pos[:, 2]].add(sz)
            cap = caps_flat[cidx] * lv[:, None, None]
            # priority 1: second-hop relay traffic (at u, destined v)
            send1 = jnp.minimum(RS, cap)
            RS = RS - send1
            second = send1.sum(axis=(1, 2))
            deliv = second
            cap = cap - send1
            tx = jnp.minimum(voq, cap) * direct  # vlb: no direct hop
            voq = voq - tx
            deliv = deliv + tx.sum(axis=(1, 2))
            cap = cap - tx
            # offload leftover capacity: proportional spray into relays;
            # moved[u, v, d] = send_u * link_share[u, v] * q_share[u, d],
            # summed over u straight into the relay buckets
            leftover = cap.sum(axis=2)
            queue = voq.sum(axis=2)
            send_u = jnp.minimum(leftover, queue)
            ls = jnp.where(leftover[:, :, None] > _JEPS,
                           cap / jnp.maximum(leftover, _JEPS)[:, :, None],
                           0.0)
            qs = jnp.where(queue[:, :, None] > _JEPS,
                           voq / jnp.maximum(queue, _JEPS)[:, :, None], 0.0)
            # dense-by-design small-n kernel (see _TWOHOP_DENSE_MAX_N)
            mvd = jnp.einsum(  # lint: allow-dense
                "buv,bud->bvd", send_u[:, :, None] * ls, qs,
                precision=jax.lax.Precision.HIGHEST)
            voq = jnp.maximum(voq - send_u[:, :, None] * qs, 0.0)
            # bits whose relay node IS the destination arrive at once
            diag = jnp.diagonal(mvd, axis1=1, axis2=2)     # mvd[b, v, v]
            deliv = deliv + diag.sum(axis=1)
            mvd = mvd * (1.0 - jnp.eye(n, dtype=mvd.dtype))
            RS = RS + mvd
            return (voq, RS), (deliv, second)

        carry, out = jax.lax.scan(
            step,
            (jnp.zeros((B, n, n), jnp.float32),   # lint: allow-dense
             jnp.zeros((B, n, n), jnp.float32)),  # lint: allow-dense
            (cap_idx, apos, asz, live))
        return out, carry

    def twohop_sparse(caps_flat, cap_idx, apos, asz, live, plan_idx,
                      p_row, p_v, p_b, p_valid, direct):
        _JAX_TRACES["twohop_sparse"] += 1
        B, n = cap_idx.shape[1], caps_flat.shape[1]

        def step(carry, inp):
            # RS[(b, at), dst]: row-major bucket totals, so the drain reads
            # and the offload fill both land on contiguous rows.  Padded
            # support entries carry valid=False -> zero capacity -> every
            # transfer below is an exact add-zero for them.
            voq, RS = carry
            cidx, pos, sz, lv, pi = inp
            voq = voq.at[pos[:, 0], pos[:, 1], pos[:, 2]].add(sz)
            cap3 = (caps_flat[cidx] * lv[:, None, None]).reshape(B * n, n)
            row, v, b, valid = p_row[pi], p_v[pi], p_b[pi], p_valid[pi]
            bv = b * n + v
            # priority 1: drain relayed bits over the support circuits
            rs = jnp.where(valid, RS[row, v], 0.0)
            cap_j = jnp.where(valid, cap3[row, v], 0.0)
            send1 = jnp.minimum(rs, cap_j)
            RS = RS.at[row, v].add(-send1)
            cap3 = cap3.at[row, v].add(-send1)
            second = jax.ops.segment_sum(send1, b, num_segments=B)
            deliv = second
            # direct hop (vlb cases masked)
            cap = cap3.reshape(B, n, n)
            tx = jnp.minimum(voq, cap) * direct
            voq = voq - tx
            deliv = deliv + tx.sum(axis=(1, 2))
            cap3 = (cap - tx).reshape(B * n, n)
            voq3 = voq.reshape(B * n, n)
            # offload leftover capacity, support rows only
            leftover = cap3.sum(axis=1)
            queue = voq3.sum(axis=1)
            send_u = jnp.minimum(leftover, queue)
            lo_j = leftover[row]
            ls = jnp.where(valid & (lo_j > _JEPS),
                           cap3[row, v] / jnp.maximum(lo_j, _JEPS), 0.0)
            coeff = send_u[row] * ls
            q_j = queue[row]
            qs = jnp.where((q_j > _JEPS)[:, None],
                           voq3[row, :] / jnp.maximum(q_j, _JEPS)[:, None],
                           0.0)
            moved = coeff[:, None] * qs          # (J, n) over dst
            dec = jax.ops.segment_sum(coeff, row, num_segments=B * n)
            scale = jnp.where(queue > _JEPS,
                              dec / jnp.maximum(queue, _JEPS), 0.0)
            voq3 = jnp.maximum(voq3 - voq3 * scale[:, None], 0.0)
            # bits whose relay node IS the destination arrive at once
            dd = jnp.take_along_axis(moved, v[:, None], axis=1)[:, 0]
            deliv = deliv + jax.ops.segment_sum(dd, b, num_segments=B)
            moved = jnp.where(jnp.arange(n)[None, :] == v[:, None],
                              0.0, moved)
            RS = RS.at[bv, :].add(moved)         # -> bucket [(b, at v), dst]
            return (voq3.reshape(B, n, n), RS), (deliv, second)

        carry, out = jax.lax.scan(
            step,
            (jnp.zeros((B, n, n), jnp.float32),  # lint: allow-dense
             jnp.zeros((B * n, n), jnp.float32)),
            (cap_idx, apos, asz, live, plan_idx))
        return out, carry

    def singlehop(voq0, apid, asz, p_pid, p_cap):
        # Sparse single-hop serving over a padded per-slot circuit plan:
        # one flat (B n^2) VOQ carry, per-slot arrival scatter at global
        # flat pair ids, then tx = min(voq, cap) gathered over the plan
        # columns.  Emits the per-slot delivered support (tx) and a
        # drained flag per plan entry so the host credit replay can
        # reconcile f32 serving with the exact f64 flow ledger.  The same
        # kernel serves run_sweep's single-hop jax path and the whole
        # adaptive jax backend (whose host-compiled epoch plans are just
        # per-slot (pid, cap) rows).
        _JAX_TRACES["singlehop"] += 1

        def step(voq, inp):
            ap, av, pid, cap = inp
            voq = voq.at[ap].add(av)
            q = voq[pid]
            tx = jnp.minimum(q, cap)
            voq = voq.at[pid].add(-tx)
            drained = (tx >= q) & (tx > jnp.float32(0.0))
            return voq, (tx, drained)

        voq_f, out = jax.lax.scan(step, voq0, (apid, asz, p_pid, p_cap))
        return voq_f, out

    def twohop_fct(caps_flat, cap_idx, apos, asz, live, direct):
        # Small-n two-hop kernel that KEEPS the per-source relay
        # attribution the aggregate kernels drop: R3[b, at, src, dst]
        # carries whose bits sit in each relay bucket, and the per-slot
        # output is the full (B, n, n) delivered-per-(src, dst) matrix the
        # host credit replay needs for per-flow FCTs.  Relay drains and
        # offload sprays are proportional within a bucket, matching the
        # NumPy engine's water-fill attribution float-for-float.
        _JAX_TRACES["twohop_fct"] += 1
        B, n = cap_idx.shape[1], caps_flat.shape[1]

        def step(carry, inp):
            voq, R3 = carry
            cidx, pos, sz, lv = inp
            voq = voq.at[pos[:, 0], pos[:, 1], pos[:, 2]].add(sz)
            cap = caps_flat[cidx] * lv[:, None, None]
            # priority 1: drain relay buckets, attributed pro-rata to src
            RS = R3.sum(axis=2)                       # [b, at, dst] totals
            send1 = jnp.minimum(RS, cap)
            frac = jnp.where(RS > _JEPS,
                             send1 / jnp.maximum(RS, _JEPS), 0.0)
            dp = jnp.einsum(  # lint: allow-dense
                "busv,buv->bsv", R3, frac,
                precision=jax.lax.Precision.HIGHEST)
            R3 = R3 * (1.0 - frac)[:, :, None, :]
            second = send1.sum(axis=(1, 2))
            cap = cap - send1
            # direct hop (vlb cases masked) — already (src, dst) resolved
            tx = jnp.minimum(voq, cap) * direct
            voq = voq - tx
            dp = dp + tx
            cap = cap - tx
            # offload leftover capacity into relays, keeping src labels
            leftover = cap.sum(axis=2)
            queue = voq.sum(axis=2)
            send_u = jnp.minimum(leftover, queue)
            ls = jnp.where(leftover[:, :, None] > _JEPS,
                           cap / jnp.maximum(leftover, _JEPS)[:, :, None],
                           0.0)
            qs = jnp.where(queue[:, :, None] > _JEPS,
                           voq / jnp.maximum(queue, _JEPS)[:, :, None], 0.0)
            # moved[b, u, v, d] = send_u * link_share[u, v] * q_share[u, d]
            moved = ((send_u[:, :, None] * ls)[:, :, :, None]
                     * qs[:, :, None, :])  # lint: allow-dense
            voq = jnp.maximum(voq - send_u[:, :, None] * qs, 0.0)
            # bits whose relay node IS the destination arrive at once,
            # delivered for (src = u, dst = v)
            diag = jnp.diagonal(moved, axis1=2, axis2=3)   # moved[b,u,v,v]
            dp = dp + diag
            moved = moved * (1.0 - jnp.eye(n, dtype=moved.dtype)
                             )[None, None, :, :]
            # relay bucket at v gains src-u bits destined d
            R3 = R3 + moved.transpose(0, 2, 1, 3)
            return (voq, R3), (dp, second)

        carry, out = jax.lax.scan(
            step,
            (jnp.zeros((B, n, n), jnp.float32),     # lint: allow-dense
             jnp.zeros((B, n, n, n), jnp.float32)),  # lint: allow-dense
            (cap_idx, apos, asz, live))
        return out, carry

    _JAX_FNS.update(
        agg=jax.jit(agg),
        twohop_dense=jax.jit(twohop_dense),
        twohop_sparse=jax.jit(twohop_sparse),
        singlehop=jax.jit(singlehop),
        twohop_fct=jax.jit(twohop_fct),
    )
    return _JAX_FNS


def _jax_batch_inputs(
    cases: list[tuple[Schedule, Workload]], bits_per_slot: float
):
    """Shared numpy-side prep for the jax engines: the periodic capacity
    LUT, per-slot liveness, and padded per-slot arrival scatter lists.

    Horizon is padded to a ``_PAD_H`` bucket (padded slots carry zero
    capacity, zero liveness, and no arrivals — exact no-ops), arrivals per
    slot to a ``_PAD_K`` bucket (padding scatters 0 bits at pair (0,0,0)),
    so the jit cache compiles once per bucket signature.
    """
    B = len(cases)
    n = cases[0][1].n
    for sched, wl in cases:
        if wl.n != n:
            raise ValueError("all workloads in a batch must share n")
        if sched.n != n:
            raise ValueError("schedule/workload size mismatch")
    horizons = np.array([wl.horizon for _, wl in cases], dtype=np.int64)
    H = int(horizons.max())
    H_pad = _pad_to(H, _PAD_H)

    caps_list = [sched.capacity_per_slot(bits_per_slot)
                 for sched, _ in cases]
    ns = np.array([c.shape[0] for c in caps_list], dtype=np.int64)
    offs = np.concatenate([[0], np.cumsum(ns[:-1])])
    caps_flat = np.concatenate(caps_list, axis=0).astype(np.float32)
    cap_idx = np.zeros((H_pad, B), dtype=np.int32)
    cap_idx[:H] = offs[None, :] + (np.arange(H)[:, None] % ns[None, :])
    live = np.zeros((H_pad, B), dtype=np.float32)
    live[:H] = np.arange(H)[:, None] < horizons[None, :]

    f_item = np.concatenate(
        [np.full(wl.num_flows, b, dtype=np.int64)
         for b, (_, wl) in enumerate(cases)])
    f_src = np.concatenate([wl.src for _, wl in cases]).astype(np.int64)
    f_dst = np.concatenate([wl.dst for _, wl in cases]).astype(np.int64)
    f_size = np.concatenate([wl.size for _, wl in cases]).astype(np.float64)
    f_arr = np.concatenate([wl.arrival for _, wl in cases]).astype(np.int64)
    valid = f_arr < horizons[f_item]
    order = np.argsort(f_arr, kind="stable")
    order = order[valid[order]]
    bucket = np.searchsorted(f_arr[order], np.arange(H + 1))
    counts = np.diff(bucket)
    K = _pad_to(int(counts.max()) if counts.size else 0, _PAD_K)
    apos = np.zeros((H_pad, K, 3), dtype=np.int32)
    asz = np.zeros((H_pad, K), dtype=np.float32)
    rows_i = np.repeat(np.arange(H), counts)
    cols_i = _ranged_arange(counts)
    apos[rows_i, cols_i, 0] = f_item[order]
    apos[rows_i, cols_i, 1] = f_src[order]
    apos[rows_i, cols_i, 2] = f_dst[order]
    asz[rows_i, cols_i] = f_size[order]
    return caps_list, caps_flat, cap_idx, apos, asz, live, H


def _fetch(*outputs) -> list[np.ndarray]:
    """Host copies of kernel outputs, given as ``(array, dtype)`` pairs,
    under one ``fabric.fetch`` span that counts the bytes copied back.
    The first conversion waits for the kernel to finish."""
    with span("fabric.fetch") as sp:
        sp.add("d2h_bytes", sum(int(a.nbytes) for a, _ in outputs))
        return [np.asarray(a, dt) for a, dt in outputs]


def _staged(sp, *inputs) -> None:
    """Count on the ``fabric.stage`` span ``sp`` the bytes of every array
    handed to a kernel."""
    sp.add("h2d_bytes", sum(int(a.nbytes) for a in inputs))


def _jax_results(
    cases, delivered, second, bits_per_slot, modes=None
) -> list[SimResult]:
    """Wrap the aggregate kernels' per-slot outputs, fetched to the host in
    float64, into SimResults (fct_slots all inf)."""
    n = cases[0][1].n
    delivered_total = delivered.sum(axis=0)
    second_total = second.sum(axis=0) if second is not None else None
    out = []
    for b, (sched, wl) in enumerate(cases):
        offered = float(wl.size[wl.arrival < wl.horizon].sum())
        ideal = wl.horizon * n * sched.d_hat * bits_per_slot
        two_hop = modes is not None and modes[b] in ("rotorlb", "vlb")
        out.append(SimResult(
            fct_slots=np.full(wl.num_flows, np.inf),
            flow_size=wl.size,
            utilization=float(delivered_total[b]) / ideal,
            delivered_bits=float(delivered_total[b]),
            offered_bits=offered,
            avg_hops=1.0 + float(second_total[b])
            / max(float(delivered_total[b]), 1e-9) if two_hop else 1.0,
        ))
    return out


def _sanitize_jax_batch(
    san, cases, caps_list, bits_per_slot, results,
    voq_f: np.ndarray, relay_queued: np.ndarray | None = None,
) -> None:
    """Shared post-run sanitizer pass for the jax engines: entry contracts
    plus per-case float32 bit conservation from the kernels' final carry."""
    n = cases[0][1].n
    for b, (sched, wl) in enumerate(cases):
        san.check_workload(wl)
        san.check_schedule(sched)
        san.check_caps_dense(
            caps_list[b], sched.d_hat,
            bits_per_slot * (1.0 - sched.recfg_frac),
            label=f"jax:case{b}:caps")
        queued = float(voq_f[b].sum())
        if relay_queued is not None:
            queued += float(relay_queued[b])
        san.check_conservation(
            results[b].offered_bits, results[b].delivered_bits, queued,
            label=f"jax:case{b}:conservation", float32=True)


def _twohop_fct_results(
    cases, modes, bits_per_slot, caps_list, dp64, second64,
    voq_f, r3_f, H: int, san,
) -> list[SimResult]:
    """Host side of the ``twohop_fct`` path: replay the per-slot delivered
    (src, dst) matrices (``dp64``, fetched in float64) through the exact
    flow-credit ledger and wrap real per-flow FCTs into the SimResults.
    The final carry (``voq_f``, ``r3_f``) is fetched only for the
    sanitizer."""
    B = len(cases)
    n = cases[0][1].n
    horizons = np.array([wl.horizon for _, wl in cases], dtype=np.int64)
    f_off, _, _, fct, credit, order, bucket = _concat_flows(
        cases, n, horizons, H)
    with span("fabric.replay") as sp:
        sp.add("flows_arrived", int(bucket[H] - bucket[0]))
        arrive_ns = credited = 0
        for slot in range(H):
            newf = order[bucket[slot]:bucket[slot + 1]]
            if newf.size:
                t = time.perf_counter_ns()
                credit.arrive(newf)
                arrive_ns += time.perf_counter_ns() - t
            row = dp64[slot].reshape(-1)
            pids = np.flatnonzero(row > 1e-9)
            credited += pids.size
            credit.credit_pairs(pids, row[pids], slot,
                                drain_rel=_F32_DRAIN_REL,
                                level_rel=_F32_LEVEL_REL)
        sp.add("pairs_credited", credited)
        sp.add("arrive_ns", arrive_ns)
    with span("fabric.results"):
        results = []
        for b, (sched, wl) in enumerate(cases):
            delivered = float(dp64[:H, b].sum())
            sec = float(second64[:H, b].sum())
            offered = float(wl.size[wl.arrival < wl.horizon].sum())
            ideal = wl.horizon * n * sched.d_hat * bits_per_slot
            results.append(SimResult(
                fct_slots=fct[f_off[b]:f_off[b + 1]],
                flow_size=wl.size,
                utilization=delivered / ideal,
                delivered_bits=delivered,
                offered_bits=offered,
                avg_hops=1.0 + sec / max(delivered, 1e-9),
            ))
        if san is not None:
            voq64, r3_64 = _fetch((voq_f, np.float64), (r3_f, np.float64))
            relay_queued = r3_64.reshape(B, -1).sum(axis=1)
            _sanitize_jax_batch(san, cases, caps_list, bits_per_slot,
                                results, voq64, relay_queued)
            rem, completed = credit.remaining_active()
            san.check_credit_closure(
                sum(r.offered_bits for r in results),
                sum(r.delivered_bits for r in results), rem, completed,
                label="jax:twohop_fct:credit", float32=True)
    return results


def _singlehop_jax_flows(
    wls: list[Workload], n: int, horizons: np.ndarray, H: int, H_pad: int,
):
    """Concatenated flow state + padded per-slot arrival scatter lists for
    the single-hop jax paths (sweep and adaptive): flat global pair ids
    ``(case * n + src) * n + dst``, arrivals per slot padded to a
    ``_PAD_K`` bucket (padding scatters 0 bits at pair id 0 — exact
    no-op).  Returns (f_off, fct, credit, order, bucket, apid, asz)."""
    B = len(wls)
    f_off = np.concatenate(
        [[0], np.cumsum([wl.num_flows for wl in wls])]).astype(np.int64)
    f_item = np.concatenate(
        [np.full(wl.num_flows, b, dtype=np.int64)
         for b, wl in enumerate(wls)])
    f_src = np.concatenate([wl.src for wl in wls]).astype(np.int64)
    f_dst = np.concatenate([wl.dst for wl in wls]).astype(np.int64)
    f_size = np.concatenate([wl.size for wl in wls]).astype(np.float64)
    f_arr = np.concatenate([wl.arrival for wl in wls]).astype(np.int64)
    pid = (f_item * n + f_src) * n + f_dst
    fct = np.full(len(f_size), np.inf)
    credit = _CreditState(B * n * n, pid, f_size, f_arr, fct)
    valid = f_arr < horizons[f_item]
    order = np.argsort(f_arr, kind="stable")
    order = order[valid[order]]
    bucket = np.searchsorted(f_arr[order], np.arange(H + 1))
    counts = np.diff(bucket)
    K = _pad_to(int(counts.max()) if counts.size else 0, _PAD_K)
    apid = np.zeros((H_pad, K), dtype=np.int32)
    asz = np.zeros((H_pad, K), dtype=np.float32)
    rows_i = np.repeat(np.arange(H), counts)
    cols_i = _ranged_arange(counts)
    apid[rows_i, cols_i] = pid[order]
    asz[rows_i, cols_i] = f_size[order]
    return f_off, fct, credit, order, bucket, apid, asz


def _replay_credit(credit: _CreditState, order: np.ndarray,
                   bucket: np.ndarray, p_pid: np.ndarray, tx64: np.ndarray,
                   dr: np.ndarray, H: int) -> None:
    """Replay the device scan's per-slot delivered support (``tx64`` and
    the ``dr`` drain flags, fetched to the host) through the exact f64
    flow-credit ledger: arrivals enter in the same stable order as the
    numpy engines, then each slot's (pid, tx) support is credited with
    drain reconciliation (``drain`` flags + ``_F32_DRAIN_REL``)."""
    with span("fabric.replay") as sp:
        pid64 = np.asarray(p_pid, np.int64)
        # one vectorized pass extracts each slot's nonzero support
        # (np.nonzero is row-major, so per-slot runs are contiguous); the
        # loop then feeds credit_pairs pre-filtered columns and skips
        # dark/empty slots outright
        live = (tx64[:H] > 1e-9) | dr[:H]
        nz_row, nz_col = np.nonzero(live)
        bnd = np.concatenate([[0], np.cumsum(live.sum(axis=1))])
        pid_nz = pid64[nz_row, nz_col]
        s_nz = tx64[nz_row, nz_col]
        dr_nz = dr[nz_row, nz_col]
        sp.add("flows_arrived", int(bucket[H] - bucket[0]))
        sp.add("pairs_credited", len(nz_row))
        arrive_ns = 0
        for slot in range(H):
            newf = order[bucket[slot]:bucket[slot + 1]]
            if newf.size:
                t = time.perf_counter_ns()
                credit.arrive(newf)
                arrive_ns += time.perf_counter_ns() - t
            a, b = bnd[slot], bnd[slot + 1]
            if a == b:
                continue
            credit.credit_pairs(pid_nz[a:b], s_nz[a:b], slot,
                                drain=dr_nz[a:b], drain_rel=_F32_DRAIN_REL)
        sp.add("arrive_ns", arrive_ns)


def _singlehop_batch_jax(
    cases: list[tuple[Schedule, Workload]], bits_per_slot: float,
    san=None,
) -> list[SimResult]:
    """Single-hop dynamics for a batch via the jitted ``singlehop`` scan
    (compile cache shared with the adaptive jax backend), with per-flow
    FCTs: the device serves the padded per-slot circuit support in f32 and
    the host replays the delivered amounts through the exact f64
    processor-sharing credit ledger.  Delivered bits / utilization match
    the NumPy engine to f32 tolerance; FCT multisets match exactly on
    well-conditioned instances (drain reconciliation absorbs f32 ulp
    residues)."""
    fns = _jax_fns()
    B = len(cases)
    n = cases[0][1].n
    for sched, wl in cases:
        if wl.n != n:
            raise ValueError("all workloads in a batch must share n")
        if sched.n != n:
            raise ValueError("schedule/workload size mismatch")
    horizons = np.array([wl.horizon for _, wl in cases], dtype=np.int64)
    H = int(horizons.max())
    H_pad = _pad_to(H, _PAD_H)
    with span("fabric.batch", kernel="singlehop", B=B, n=n, H_pad=H_pad):
        with span("fabric.stage") as sp:
            # per-case padded circuit plans -> per-case column blocks of
            # one (H_pad, J_total) plan; capacities zero past a case's
            # horizon
            padded = [sched.slot_circuits_padded(bits_per_slot,
                                                 pair_base=b * n * n,
                                                 j_pad=_PAD_J)
                      for b, (sched, _) in enumerate(cases)]
            offs = np.concatenate(
                [[0], np.cumsum([p[0].shape[1] for p in padded])]
            ).astype(np.int64)
            Jtot = int(offs[-1])
            p_pid = np.zeros((H_pad, Jtot), dtype=np.int32)
            p_cap = np.zeros((H_pad, Jtot), dtype=np.float32)
            slots = np.arange(H)
            for b, (ppid, pcap) in enumerate(padded):
                ps = slots % ppid.shape[0]
                h_b = int(horizons[b])
                p_pid[:H, offs[b]:offs[b + 1]] = ppid[ps]
                p_cap[:h_b, offs[b]:offs[b + 1]] = pcap[ps[:h_b]]

            f_off, fct, credit, order, bucket, apid, asz = (
                _singlehop_jax_flows([wl for _, wl in cases], n, horizons,
                                     H, H_pad))
            voq0 = np.zeros(B * n * n, dtype=np.float32)  # lint: allow-dense
            _staged(sp, voq0, apid, asz, p_pid, p_cap)
        with span("fabric.dispatch"):
            _record_call("singlehop", (B, n, H_pad, apid.shape[1], Jtot))
            voq_f, (tx, drained) = fns["singlehop"](voq0, apid, asz, p_pid,
                                                    p_cap)
        tx64, dr = _fetch((tx, np.float64), (drained, bool))
        _replay_credit(credit, order, bucket, p_pid, tx64, dr, H)

        with span("fabric.results"):
            results = []
            for b, (sched, wl) in enumerate(cases):
                cols = slice(int(offs[b]), int(offs[b + 1]))
                delivered = float(tx64[:int(horizons[b]), cols].sum())
                offered = float(wl.size[wl.arrival < wl.horizon].sum())
                ideal = wl.horizon * n * sched.d_hat * bits_per_slot
                results.append(SimResult(
                    fct_slots=fct[f_off[b]:f_off[b + 1]],
                    flow_size=wl.size,
                    utilization=delivered / ideal,
                    delivered_bits=delivered,
                    offered_bits=offered,
                    avg_hops=1.0,
                ))
            if san is not None:
                voq64, = _fetch((voq_f, np.float64))
                for b, (sched, wl) in enumerate(cases):
                    san.check_workload(wl)
                    san.check_schedule(sched)
                    queued = float(voq64[b * n * n:(b + 1) * n * n].sum())
                    san.check_conservation(
                        results[b].offered_bits, results[b].delivered_bits,
                        queued, label=f"jax:case{b}:conservation",
                        float32=True)
                rem, completed = credit.remaining_active()
                san.check_credit_closure(
                    sum(r.offered_bits for r in results),
                    sum(r.delivered_bits for r in results), rem, completed,
                    label="jax:singlehop:credit", float32=True)
    return results


def _twohop_batch_jax(
    cases: list[tuple[Schedule, Workload]],
    bits_per_slot: float,
    modes: list[str],
    kernel: str | None = None,
    san=None,
) -> list[SimResult]:
    """Two-hop (rotorlb / vlb, mixed freely) relay dynamics for a batch via
    a jitted ``jax.lax.scan`` — the accelerated counterpart of
    :func:`_simulate_batch`'s relay loop.

    When the per-(at, src, dst) attribution tensor fits
    (``_twohop_fct_ok``; default kernel selection only), the batch runs
    the ``twohop_fct`` kernel, which emits per-slot delivered (src, dst)
    matrices, and the host replays them through the exact flow-credit
    ledger — fct_slots are real.  Otherwise aggregate quantities only
    (utilization / delivered bits / avg_hops match the NumPy engine;
    fct_slots all inf).  ``kernel`` forces the ``"dense"`` einsum or
    ``"sparse"`` padded-support formulation (both aggregate-only); by
    default the crossover picks dense for n <= ``_TWOHOP_DENSE_MAX_N``.
    The sparse kernel scans a per-period-residue circuit-support LUT built
    by the same :class:`_SupportPlans` merge the NumPy engine uses.
    """
    for m in modes:
        if m not in ("rotorlb", "vlb"):
            raise ValueError(f"not a two-hop mode: {m}")
    fns = _jax_fns()
    B = len(cases)
    n = cases[0][1].n
    H_pad = _pad_to(max(wl.horizon for _, wl in cases), _PAD_H)
    fct_path = kernel is None and _twohop_fct_ok(B, n, H_pad)
    if kernel is None:
        kernel = "dense" if n <= _TWOHOP_DENSE_MAX_N else "sparse"
    if kernel not in ("dense", "sparse"):
        raise ValueError(kernel)
    name = "twohop_fct" if fct_path else f"twohop_{kernel}"
    with span("fabric.batch", kernel=name, B=B, n=n, H_pad=H_pad):
        with span("fabric.stage") as sp:
            caps_list, caps_flat, cap_idx, apos, asz, live, H = (
                _jax_batch_inputs(cases, bits_per_slot))
            direct = np.array([0.0 if m == "vlb" else 1.0 for m in modes],
                              dtype=np.float32).reshape(B, 1, 1)
            lut = (_sparse_plan_lut(caps_list, n, B, H, H_pad)
                   if name == "twohop_sparse" else [])
            inputs = [caps_flat, cap_idx, apos, asz, live, *lut, direct]
            _staged(sp, *inputs)
        with span("fabric.dispatch"):
            bucket = (B, n, H_pad, asz.shape[1])
            if lut:
                bucket += lut[1].shape[::-1]      # (J, P) of the plan LUT
            _record_call(name, bucket)
            out, (voq_f, relay_f) = fns[name](*inputs)
        delivered, second = _fetch((out[0], np.float64),
                                   (out[1], np.float64))
        if fct_path:
            return _twohop_fct_results(
                cases, modes, bits_per_slot, caps_list, delivered, second,
                voq_f, relay_f, H, san)
        with span("fabric.results"):
            results = _jax_results(cases, delivered, second, bits_per_slot,
                                   modes)
            if san is not None:
                voq64, rs64 = _fetch((voq_f, np.float64),
                                     (relay_f, np.float64))
                _sanitize_jax_batch(san, cases, caps_list, bits_per_slot,
                                    results, voq64,
                                    rs64.reshape(B, -1).sum(axis=1))
    return results


def _sparse_plan_lut(caps_list, n: int, B: int, H: int,
                     H_pad: int) -> list[np.ndarray]:
    """The ``twohop_sparse`` kernel's circuit-support LUT: one padded plan
    per distinct period-residue tuple (the same :class:`_SupportPlans`
    merge the NumPy engine uses) and each slot's index into it.  Returns
    ``[plan_idx, p_row, p_v, p_b, p_valid]``."""
    plans = _SupportPlans(caps_list, n, list(range(B)), B)
    keys: dict[tuple, int] = {}
    plan_idx = np.zeros(H_pad, dtype=np.int32)
    plan_list: list[dict] = []
    for slot in range(H):
        key = plans.key(slot)
        pi = keys.get(key)
        if pi is None:
            pi = keys[key] = len(plan_list)
            plan_list.append(plans.plan(slot))
        plan_idx[slot] = pi
    J = _pad_to(max((p["J"] for p in plan_list), default=0), _PAD_J)
    # pad the plan count to a power-of-two bucket: coprime period mixes
    # multiply distinct residue tuples toward lcm(periods), and an unpadded
    # P would make every mix a fresh jit signature (the LUT itself stays
    # bounded by H — at most one plan per slot)
    P = 1 << (max(len(plan_list), 1) - 1).bit_length()
    p_row = np.zeros((P, J), dtype=np.int32)
    p_v = np.zeros((P, J), dtype=np.int32)
    p_b = np.zeros((P, J), dtype=np.int32)
    p_valid = np.zeros((P, J), dtype=bool)
    for i, p in enumerate(plan_list):
        j = p["J"]
        p_row[i, :j] = p["row"]
        p_v[i, :j] = p["v"]
        p_b[i, :j] = p["b"]
        p_valid[i, :j] = True
    return [plan_idx, p_row, p_v, p_b, p_valid]


def simulate_aggregate_jax(
    sched: Schedule, arrivals: np.ndarray, bits_per_slot: float
):
    """Single-hop aggregate dynamics on the accelerator.
    Returns (delivered_per_slot, final_voq).

    ``arrivals``: (horizon, n, n) bits arriving per slot.

    Runs as a B = 1 batch through the module's cached ``agg`` scan kernel
    (horizon padded to the ``_PAD_H`` bucket with dead slots — exact
    no-ops), so repeated calls at the same padded shape never retrace;
    the PR 4 compile-cache discipline applies here too.
    """
    fns = _jax_fns()
    arrivals = np.asarray(arrivals, dtype=np.float32)
    horizon, n = arrivals.shape[0], arrivals.shape[1]
    caps_flat = sched.capacity_per_slot(bits_per_slot).astype(np.float32)
    ns = caps_flat.shape[0]
    H_pad = _pad_to(horizon, _PAD_H)
    cap_idx = np.zeros((H_pad, 1), dtype=np.int32)
    cap_idx[:horizon, 0] = np.arange(horizon) % ns
    live = np.zeros((H_pad, 1), dtype=np.float32)
    live[:horizon, 0] = 1.0
    arr = np.zeros((H_pad, 1, n, n), dtype=np.float32)  # lint: allow-dense
    arr[:horizon, 0] = arrivals
    _record_call("agg", (1, n, H_pad))
    delivered, voq_f = fns["agg"](caps_flat, cap_idx, arr, live)
    return np.asarray(delivered)[:horizon, 0], np.asarray(voq_f)[0]


# ---------------------------------------------------------------------------
# JAX adaptive backend: host-compiled control plane + one device scan
# ---------------------------------------------------------------------------

def _compile_adaptive_plan(case: AdaptiveCase, bits_per_slot: float,
                           san=None, sched_cache: dict | None = None):
    """Host-side replay of the adaptive control loop WITHOUT serving.

    The epoch counters that drive the control plane accumulate *arrival*
    bits only — never served bits — so for every jax-supported case the
    whole control trajectory (fleet EWMA → quantized ring gather →
    per-node schedules → collision-resolved fabric plans → construction
    charging → activation dark windows → churn hysteresis) is computable
    before any serving happens.  This mirrors :func:`_run_adaptive_case`
    decision-for-decision (bit-identical counters: one ``np.add.at`` over
    the epoch's stable-ordered arrival slice reproduces the per-slot
    accumulation element-for-element) and emits, per slot, an index into a
    registry of ``(pair_id, capacity)`` circuit plans the device scan then
    serves.  Registry id 0 is the empty plan (fully-dark slots).

    ``sched_cache`` (shared across a batch) memoizes schedule
    *construction* on the exact estimator inputs — the expensive
    ``vermilion_schedule`` / ``per_node_schedules`` calls — so a grid that
    varies only the collision policy pays construction once; the (cheap,
    collision-specific) ``_fabric_plan`` merge always runs.  Disabled for
    ``construction_slots="measured"``, where the charge is the actual
    wall-clock of a fresh construction.
    """
    wl, n = case.wl, case.wl.n
    E, H = case.epoch_slots, wl.horizon
    n_epochs = -(-H // E)
    cs = case.construction_slots
    measured = cs == "measured"
    if measured:
        sched_cache = None
    penalty = int(case.reconfig_penalty_slots)
    if san is not None:
        san.set_context(f"case={case.label}")
        san.check_workload(wl)
    san_w = bits_per_slot * (1.0 - case.recfg_frac)

    f_size = wl.size.astype(np.float64)
    valid = wl.arrival < H
    order = np.argsort(wl.arrival, kind="stable")
    order = order[valid[order]]
    bucket = np.searchsorted(wl.arrival[order], np.arange(H + 1))

    true_epoch = np.zeros((n_epochs, n, n))  # lint: allow-dense
    np.add.at(true_epoch,
              (wl.arrival[order] // E, wl.src[order], wl.dst[order]),
              f_size[order])
    oracle_m = case.oracle_demand
    if oracle_m is not None and oracle_m.shape != (n_epochs, n, n):
        raise ValueError(
            f"oracle_demand shape {oracle_m.shape} != {(n_epochs, n, n)}")
    if oracle_m is None:
        oracle_m = true_epoch / E

    fleet = TrafficEstimator.fleet(n, alpha=case.alpha)
    q_unit = _quantizer_unit(E, case.k, case.d_hat, bits_per_slot)

    construction_s = 0.0
    last_construction = 0.0
    cache_key_base = (case.k, case.d_hat, case.recfg_frac, case.normalize,
                      case.method)

    def consistent_plan(sched: Schedule) -> _FabricPlan:
        fp = _fabric_plan([sched], np.zeros(n, dtype=np.int64),
                          bits_per_slot, case.collision)
        if san is not None:
            san.check_schedule(sched)
            san.check_fabric_plan(fp, n, sched.d_hat, san_w)
        return fp

    def vsched(m: np.ndarray, seed: int) -> Schedule:
        nonlocal construction_s, last_construction
        key = None
        if sched_cache is not None:
            key = ("v", m.tobytes(), seed) + cache_key_base
            hit = sched_cache.get(key)
            if hit is not None:
                s, dt = hit
                last_construction = dt
                construction_s += dt
                return s
        t0 = time.perf_counter()
        s = vermilion_schedule(
            m, k=case.k, d_hat=case.d_hat, recfg_frac=case.recfg_frac,
            seed=seed, normalize=case.normalize, method=case.method)
        last_construction = time.perf_counter() - t0
        construction_s += last_construction
        if key is not None:
            sched_cache[key] = (s, last_construction)
        return s

    def vsched_per_node(views, seed: int, unique) -> _FabricPlan:
        nonlocal construction_s, last_construction
        masks, owner = unique
        key = None
        if sched_cache is not None:
            key = ("pn", views.rows.tobytes(), masks.tobytes(),
                   owner.tobytes(), seed) + cache_key_base
            hit = sched_cache.get(key)
            if hit is not None:
                scheds, sowner, dt = hit
            else:
                hit = None
        if sched_cache is None or hit is None:
            t0 = time.perf_counter()
            scheds, sowner = per_node_schedules(
                views, k=case.k, d_hat=case.d_hat,
                recfg_frac=case.recfg_frac, seed=seed,
                normalize=case.normalize, method=case.method, unique=unique)
            dt = time.perf_counter() - t0
            if key is not None:
                sched_cache[key] = (scheds, sowner, dt)
        construction_s += dt
        # the fabric waits for one local construction (see
        # _run_adaptive_case.vsched_per_node)
        last_construction = dt / len(scheds)
        fp = _fabric_plan(scheds, sowner, bits_per_slot, case.collision)
        if san is not None:
            for s in scheds:
                san.check_schedule(s)
            san.check_fabric_plan(fp, n, case.d_hat, san_w)
        return fp

    if case.policy in ("oracle", "stale"):
        fp = consistent_plan(vsched(oracle_m[0], case.seed))
    else:
        fp = consistent_plan(oblivious_schedule(n, d_hat=case.d_hat,
                                                recfg_frac=case.recfg_frac))
    sched_t0 = 0
    pending: tuple[int, _FabricPlan] | None = None

    est_tv = np.full(n_epochs, np.nan)
    dis_slot = np.zeros(H)
    coll_slot = np.zeros(H)
    plan_ids = np.zeros(H, dtype=np.int32)
    registry: list[tuple[np.ndarray, np.ndarray]] = [
        (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.float64))]
    memo: dict[tuple, int] = {}
    keep_alive: list = [fp]        # plans are memo-keyed by id(); pin them
    recomputes = 0
    stale_slots = 0
    dark_slots = 0
    dark_plane_slots = 0.0
    groups_max = 1
    plane_dark_until = np.zeros(case.d_hat, dtype=np.int64)
    counters = np.zeros((n, n))
    last_est: np.ndarray | None = None
    last_sig: tuple | None = None

    def activate(new_fp: _FabricPlan, s: int) -> None:
        nonlocal fp, sched_t0, groups_max
        if penalty:
            om, nm = fp.plane_map, new_fp.plane_map
            if (fp.eff is None or new_fp.eff is None
                    or fp.eff.shape != new_fp.eff.shape
                    or not np.array_equal(om, nm)):
                plane_dark_until[nm] = s + penalty
            else:
                ch = planes_changed(fp.eff, new_fp.eff, len(nm))
                plane_dark_until[nm[ch]] = s + penalty
        fp, sched_t0 = new_fp, s
        keep_alive.append(new_fp)
        groups_max = max(groups_max, new_fp.groups)

    slot = 0
    while slot < H:
        if pending is not None and slot >= pending[0]:
            swap_fp = pending[1]
            pending = None
            activate(swap_fp, slot)
        if slot and slot % E == 0:
            epoch = slot // E
            if san is not None:
                san.set_context(
                    f"case={case.label} epoch={epoch} slot={slot}")
            # bit-identical counter replica: the numpy loop adds each
            # slot's stable-ordered arrival slice via one np.add.at; one
            # np.add.at over the epoch's concatenated slice performs the
            # identical element-ordered float accumulation
            swap = None
            if case.policy == "adaptive":
                # the estimation round and its TV-accuracy metric are
                # collision-independent, so a grid varying only the
                # data-plane resolution computes each epoch's views once
                # (keyed per epoch: the fleet EWMA is stateful, so a case
                # either hits every epoch of a cached trajectory or
                # replays the whole chain itself)
                ctl_key = None
                ctl = None
                if sched_cache is not None and san is None:
                    ctl_key = ("ctl", id(wl), epoch, case.gather_steps,
                               case.alpha, E, case.seed) + cache_key_base
                    ctl = sched_cache.get(ctl_key)
                if ctl is None:
                    counters[:] = 0.0
                    seg = order[bucket[(epoch - 1) * E]:bucket[epoch * E]]
                    np.add.at(counters, (wl.src[seg], wl.dst[seg]),
                              f_size[seg])
                    views = estimate_all_views(
                        counters, fleet, case.k, q_unit,
                        steps=case.gather_steps)
                    if san is not None:
                        san.check_views(views)
                    t = true_epoch[epoch - 1]
                    masks, owner = views.unique()
                    counts = np.bincount(owner, minlength=masks.shape[0])
                    t_sum = t.sum()
                    tn = t / t_sum if t_sum > 0 else None
                    nonempty = (masks @ views.rows.sum(axis=1)) > 0
                    tvs, wts = [], []
                    for g in range(masks.shape[0]):
                        if tn is not None and nonempty[g]:
                            est_g = views.rows * masks[g][:, None]
                            tvs.append(0.5 * np.abs(
                                est_g / est_g.sum() - tn).sum())
                            wts.append(counts[g])
                    tv_val = (float(np.average(tvs, weights=wts))
                              if tvs else None)
                    if ctl_key is not None:
                        sched_cache[ctl_key] = (views, masks, owner, tv_val)
                else:
                    views, masks, owner, tv_val = ctl
                if tv_val is not None:
                    est_tv[epoch - 1] = tv_val
                build = views.rows.sum() > 0
                if build and case.swap_tv_threshold > 0.0:
                    cur = views.rows / views.rows.sum()
                    sig = (b"", b"", b"")   # no repair state on this path
                    if (last_est is not None and sig == last_sig
                            and 0.5 * np.abs(cur - last_est).sum()
                                < case.swap_tv_threshold):
                        build = False
                    else:
                        last_est, last_sig = cur, sig
                if build:
                    swap = vsched_per_node(views, case.seed + epoch,
                                           (masks, owner))
            elif case.policy == "oracle":
                if oracle_m[epoch].sum() > 0:
                    swap = consistent_plan(
                        vsched(oracle_m[epoch], case.seed + epoch))
            if swap is not None:
                recomputes += 1
                charge = (int(np.ceil(last_construction
                                      / case.slot_seconds))
                          if measured else int(cs))
                if charge == 0:
                    pending = None
                    activate(swap, slot)
                else:
                    pending = (slot + charge, swap)
        # per-slot state (fabric, pending status, per-plane darkness) is
        # constant until the next control event, so the whole run of slots
        # up to it is classified and filled in one vectorized pass — the
        # numpy engine cannot do this because serving (VOQ evolution,
        # collision outcomes) feeds back into its per-slot decisions
        nxt = min(H, (slot // E + 1) * E)
        if pending is not None:
            nxt = min(nxt, int(pending[0]))
        for t in plane_dark_until[fp.plane_map]:
            if slot < t < nxt:
                nxt = int(t)
        seg = np.arange(slot, nxt)
        if pending is not None:
            stale_slots += nxt - slot

        dark = plane_dark_until[fp.plane_map] > slot
        if dark.all():                 # plan id 0: fully-dark, serve nothing
            dark_slots += nxt - slot
            dark_plane_slots += float(dark.sum()) * (nxt - slot)
            slot = nxt
            continue
        ps_arr = (seg - sched_t0) % fp.n_slots
        ids_u = np.zeros(fp.n_slots, dtype=np.int32)
        if not dark.any() and fp.plans is not None:
            # fast path: the precomputed period-slot plans
            dis_slot[seg] = fp.disagreement
            coll_slot[seg] = fp.lost[ps_arr]
            for p in np.unique(ps_arr):
                key = (id(fp), int(p))
                idx = memo.get(key)
                if idx is None:
                    idx = memo[key] = len(registry)
                    registry.append(fp.plans[int(p)])
                ids_u[p] = idx
            plan_ids[seg] = ids_u[ps_arr]
            slot = nxt
            continue
        # partially-dark slots: rebuild from raw claims with the statically
        # arbitrated winners ("fullest" was rejected at entry)
        dark_plane_slots += float(dark.sum()) * (nxt - slot)
        dis_slot[seg] = fp.disagreement
        dl = len(fp.plane_map)
        coll_u = np.zeros(fp.n_slots)
        for p in np.unique(ps_arr):
            lo = int(p) * dl
            hi = min(lo + dl, fp.eff.shape[0])
            rows_e = fp.eff[lo:hi]
            planes = fp.plane_map[:hi - lo]
            live = (plane_dark_until[planes] <= slot)[:, None]
            nonself = fp.nonself[lo:hi]
            win = fp.win[lo:hi]
            coll_u[p] = float((nonself & live & ~win).sum()) * fp.w
            key = (id(fp), lo, live.tobytes())
            idx = memo.get(key)
            if idx is None:
                served = win & nonself & live
                srr, sii = np.nonzero(served)
                if srr.size:
                    spid, inv = np.unique(sii * n + rows_e[srr, sii],
                                          return_inverse=True)
                    scap = np.bincount(inv).astype(np.float64) * fp.w
                else:
                    spid = np.empty(0, dtype=np.int64)
                    scap = np.empty(0, dtype=np.float64)
                idx = memo[key] = len(registry)
                registry.append((spid, scap))
            ids_u[p] = idx
        coll_slot[seg] = coll_u[ps_arr]
        plan_ids[seg] = ids_u[ps_arr]
        slot = nxt

    if san is not None:
        san.set_context(None)
    return {
        "registry": registry, "plan_ids": plan_ids,
        "dis_slot": dis_slot, "coll_slot": coll_slot, "est_tv": est_tv,
        "recomputes": recomputes, "stale_slots": stale_slots,
        "dark_slots": dark_slots, "dark_plane_slots": dark_plane_slots,
        "groups_max": groups_max, "construction_s": construction_s,
        "n_epochs": n_epochs, "keep_alive": keep_alive,
    }


def _run_adaptive_batch_jax(
    cases: list[AdaptiveCase], bits_per_slot: float, san=None,
) -> list[AdaptiveRow]:
    """The jax adaptive backend: compile every case's control trajectory
    host-side (:func:`_compile_adaptive_plan`, construction shared across
    cases via the batch schedule cache), pack the per-slot circuit plans
    into per-case column blocks of one padded ``(H_pad, J)`` plan, serve
    the whole batch in ONE ``singlehop`` device scan, and recover exact
    per-flow FCTs through the host credit replay."""
    fns = _jax_fns()
    B = len(cases)
    n = cases[0].wl.n
    horizons = np.array([c.wl.horizon for c in cases], dtype=np.int64)
    H = int(horizons.max())
    H_pad = _pad_to(H, _PAD_H)
    with span("fabric.batch", kernel="singlehop", B=B, n=n, H_pad=H_pad):
        sched_cache: dict = {}
        compiled = [_compile_adaptive_plan(c, bits_per_slot, san=san,
                                           sched_cache=sched_cache)
                    for c in cases]

        # cases whose compiled data plane is byte-identical (same workload
        # object, horizon and per-slot circuit plan) have identical device
        # dynamics and identical per-flow FCTs, so they are served and
        # replayed once — e.g. the complete-gather case under every collision
        # mode: a consistent fabric never invokes collision resolution.  The
        # equivalence only emerges from the compiled trajectory, which is why
        # the slot-driven numpy engine cannot exploit it.  Disabled under the
        # sanitizer so its per-case conservation/closure ledgers stay 1:1.
        rep_of = list(range(B))
        if san is None:
            seen: dict = {}
            for b, (case, cp) in enumerate(zip(cases, compiled)):
                hsh = hashlib.sha1(cp["plan_ids"].tobytes())
                for spid_l, scap_l in cp["registry"]:
                    hsh.update(spid_l.tobytes())
                    hsh.update(scap_l.tobytes())
                key = (id(case.wl), int(horizons[b]), hsh.hexdigest())
                rep_of[b] = seen.setdefault(key, b)
        reps = sorted(set(rep_of))
        uidx = {b: u for u, b in enumerate(reps)}

        with span("fabric.stage") as sp:
            col_offs = [0]
            for b in reps:
                cp = compiled[b]
                max_j = max((len(p[0]) for p in cp["registry"]), default=0)
                col_offs.append(col_offs[-1]
                                + _pad_to(max(max_j, 1), _PAD_J))
            Jtot = col_offs[-1]
            p_pid = np.zeros((H_pad, Jtot), dtype=np.int32)
            p_cap = np.zeros((H_pad, Jtot), dtype=np.float32)
            for u, b in enumerate(reps):
                cp = compiled[b]
                base = u * n * n
                cols = slice(col_offs[u], col_offs[u + 1])
                jc = col_offs[u + 1] - col_offs[u]
                reg = cp["registry"]
                ent_pid = np.full((len(reg), jc), base, dtype=np.int32)
                ent_cap = np.zeros((len(reg), jc), dtype=np.float32)
                for i, (spid_l, scap_l) in enumerate(reg):
                    ent_pid[i, :len(spid_l)] = base + spid_l
                    ent_cap[i, :len(spid_l)] = scap_l
                h_b = int(horizons[b])
                p_pid[:h_b, cols] = ent_pid[cp["plan_ids"]]
                p_cap[:h_b, cols] = ent_cap[cp["plan_ids"]]
                p_pid[h_b:, cols] = base

            f_off, fct, credit, order, bucket, apid, asz = (
                _singlehop_jax_flows([cases[b].wl for b in reps], n,
                                     horizons[reps], H, H_pad))
            voq0 = np.zeros(len(reps) * n * n,  # lint: allow-dense
                            dtype=np.float32)
            _staged(sp, voq0, apid, asz, p_pid, p_cap)
        with span("fabric.dispatch"):
            _record_call("singlehop",
                         (len(reps), n, H_pad, apid.shape[1], Jtot))
            voq_f, (tx, drained) = fns["singlehop"](voq0, apid, asz, p_pid,
                                                    p_cap)
        tx64, dr = _fetch((tx, np.float64), (drained, bool))
        _replay_credit(credit, order, bucket, p_pid, tx64, dr, H)

        with span("fabric.results"):
            if san is not None:
                voq64, = _fetch((voq_f, np.float64))
            rows = []
            for b, (case, cp) in enumerate(zip(cases, compiled)):
                wl, E = case.wl, case.epoch_slots
                h_b = int(horizons[b])
                n_epochs = cp["n_epochs"]
                u = uidx[rep_of[b]]
                cols = slice(col_offs[u], col_offs[u + 1])
                # strictly sequential per-epoch accumulation (np.add.at,
                # not reduceat: reduceat's pairwise float reduction drifts
                # ~1 ulp from the numpy loop's slot-by-slot `+=`)
                ep_idx = np.arange(h_b) // E
                per_slot = tx64[:h_b, cols].sum(axis=1)
                delivered_ep = np.zeros(n_epochs)
                np.add.at(delivered_ep, ep_idx, per_slot)
                dis_ep = np.zeros(n_epochs)
                np.add.at(dis_ep, ep_idx, cp["dis_slot"])
                coll_ep = np.zeros(n_epochs)
                np.add.at(coll_ep, ep_idx, cp["coll_slot"])
                ep_len = np.minimum(E, h_b - E * np.arange(n_epochs))
                ep_cap = ep_len * n * case.d_hat * bits_per_slot
                ideal = h_b * n * case.d_hat * bits_per_slot
                delivered = float(delivered_ep.sum())
                offered = float(wl.size[wl.arrival < h_b].sum())
                if san is not None:
                    queued = float(voq64[u * n * n:(u + 1) * n * n].sum())
                    san.check_conservation(
                        offered, delivered, queued,
                        label=f"jax:adaptive{b}:conservation", float32=True)
                result = SimResult(
                    fct_slots=fct[f_off[u]:f_off[u + 1]],
                    flow_size=wl.size,
                    utilization=delivered / ideal,
                    delivered_bits=delivered,
                    offered_bits=offered,
                )
                rows.append(AdaptiveRow(
                    label=case.label, policy=case.policy, result=result,
                    epoch_utilization=delivered_ep / ep_cap,
                    epoch_estimate_tv=cp["est_tv"],
                    recomputes=cp["recomputes"], meta=dict(case.meta),
                    stale_slots=cp["stale_slots"],
                    construction_s=cp["construction_s"],
                    dark_slots=cp["dark_slots"],
                    epoch_disagreement=dis_ep / ep_len,
                    epoch_collision_loss=coll_ep / ep_cap,
                    collision_lost_bits=float(coll_ep.sum()),
                    schedule_groups_max=cp["groups_max"],
                    dark_plane_slots=cp["dark_plane_slots"]))
            if san is not None:
                rem, completed = credit.remaining_active()
                san.check_credit_closure(
                    sum(r.result.offered_bits for r in rows),
                    sum(r.result.delivered_bits for r in rows), rem,
                    completed, label="jax:adaptive:credit", float32=True)
    return rows
