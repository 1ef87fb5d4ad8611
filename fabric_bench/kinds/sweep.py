"""The ``sweep`` request kind: one grid handed to ``run_sweep(cases,
bits_per_slot, backend="jax")``.

A request's traffic comes from the mix's fixed pool, generated in set-up
by :mod:`fabric_bench.gen` (``websearch_workload``, one workload per load
of the mix); each request relabels the racks and seeds its schedules
afresh, so no two requests of a run are the same input.  Its schedules
are built while it is served, by the program's public constructors,
since building them is part of the user's path.  The mix's ``systems``
are the cases of a request, per workload.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from .. import gen, reference
from ..harness import Cell, Served, span

SEED_MAX = 2**31 - 1
# the Vermilion schedules' demand normalization, the one under which
# reference.vermilion_violations holds them to Algorithm 1's guarantee
VERMILION_NORMALIZE = "saturate"


# --- requests ---------------------------------------------------------------

@dataclass
class Request:
    """The traffic of one request."""
    seed: int                       # the seed of its Vermilion schedules
    flows: list                     # gen.Flows, one per workload
    workloads: list                 # the program's Workload of each
    demand: list                    # average demand matrix of each

    def slots(self, cell: Cell) -> int:
        """Simulated slots times cases: the unit of ``slot_rate``."""
        return len(cell.systems) * sum(f.horizon for f in self.flows)


def make_pool(cell: Cell) -> list:
    """The traffic mix's pool: entry i holds one workload per load, each
    generated from the mix's ``pool_seeds[i]``, so every run offers the
    same sizes and arrivals."""
    w = cell.traffic["workload"]
    return [[gen.websearch_workload(cell.n, load, w["horizon"],
                                    cell.bits_per_slot, d_hat=cell.d_hat,
                                    seed=base)
             for load in w["loads"]]
            for base in cell.traffic["pool_seeds"]]


def make_request(cell: Cell, pool: list, seed: int, i: int) -> Request:
    """Request ``i`` of the run seeded ``seed``: pool entry ``i`` mod the
    pool's size, its racks relabelled by a permutation and its schedules
    seeded, both drawn from ``(seed, i)``.  The work is the pool's; the
    pairs, the demand matrices and the schedules differ from request to
    request."""
    from repro.core.simulator import Workload
    rng = np.random.default_rng([seed, i])
    s = int(rng.integers(0, SEED_MAX))
    perm = rng.permutation(cell.n)
    flows = [gen.Flows(src=perm[f.src], dst=perm[f.dst], size=f.size,
                       arrival=f.arrival, n=f.n, horizon=f.horizon)
             for f in pool[i % len(pool)]]
    return Request(
        seed=s, flows=flows,
        workloads=[Workload(src=f.src, dst=f.dst, size=f.size,
                            arrival=f.arrival, n=f.n, horizon=f.horizon)
                   for f in flows],
        demand=[f.demand_matrix() for f in flows])


# --- serving ---------------------------------------------------------------

def serve(cell: Cell, req: Request) -> Served:
    """Serve one request through the program's entry point."""
    from repro.core import schedule as S
    from repro.core import simulator as sim
    n, d_hat, recfg = cell.n, cell.d_hat, cell.recfg_frac
    schedules: dict = {}
    t0 = time.perf_counter()
    with span("fb.request"):
        with span("fb.construct"):
            cases = []
            obl = None
            for i, (wl, m) in enumerate(zip(req.workloads, req.demand)):
                for s in cell.systems:
                    spec = s["schedule"]
                    if spec["kind"] == "oblivious":
                        if obl is None:
                            obl = S.oblivious_schedule(
                                n, d_hat=d_hat, recfg_frac=recfg)
                        sched = obl
                    elif spec["kind"] == "vermilion":
                        sched = S.vermilion_schedule(
                            m, k=spec["k"], d_hat=d_hat, recfg_frac=recfg,
                            normalize=VERMILION_NORMALIZE, seed=req.seed)
                        schedules[(i, s["label"])] = sched.perms
                    else:
                        raise ValueError(
                            f"unknown schedule kind {spec['kind']!r}")
                    cases.append(sim.SweepCase(
                        sched=sched, wl=wl, mode=s["mode"], label=s["label"]))
        t1c = time.perf_counter()
        with span("fb.engine"):
            rows = sim.run_sweep(cases, cell.bits_per_slot, backend="jax")
    t1 = time.perf_counter()
    return Served(req, rows, schedules, t0, t1, t1c - t0)


def kernel_batches(cell: Cell, req: Request) -> dict:
    """The problem shapes each kernel batch of a request serves, one dict
    per case: the roofline counts read these, never the padded shapes."""
    out: dict = {"singlehop": [], "twohop": []}
    for f in req.flows:
        for s in cell.systems:
            out["singlehop" if s["mode"] == "single_hop" else "twohop"].append(
                dict(n=f.n, d_hat=cell.d_hat, horizon=f.horizon,
                     flows=int((f.arrival < f.horizon).sum())))
    return out


# --- outputs and the reference ---------------------------------------------

def fct_pairs(cell: Cell, req: Request, rng: np.random.Generator) -> list:
    """Per workload, the (src, dst) pairs whose flows the check compares:
    a sample drawn from ``rng`` of the pairs that carry flows, always
    including the pair of the largest flow."""
    want = int(cell.traffic["check"]["fct_pairs"])
    out = []
    for f in req.flows:
        pid = f.src * f.n + f.dst
        have = np.unique(pid)
        pick = rng.choice(have, size=min(want, len(have)), replace=False)
        pick = np.union1d(pick, [pid[int(np.argmax(f.size))]])
        out.append(np.stack([pick // f.n, pick % f.n], axis=1))
    return out


def program_outputs(served: Served) -> list:
    """Per case: delivered bits, mean hop count and per-flow FCTs, as the
    program returned them."""
    return [dict(label=r.label, delivered=r.result.delivered_bits,
                 hops=r.result.avg_hops, fct=np.asarray(r.result.fct_slots))
            for r in served.rows]


def reference_outputs(cell: Cell, served: Served, pairs: list,
                      rnd=None) -> tuple[list, int]:
    """The reference's outputs for the cases of ``served``, in the same
    form as :func:`program_outputs`, and the number of schedules that break
    Algorithm 1's guarantee.  FCTs are computed for the flows of ``pairs``
    only (the rest stay NaN)."""
    req = served.request
    w = cell.bits_per_slot * (1.0 - cell.recfg_frac)
    out, bad = [], 0
    for i, f in enumerate(req.flows):
        for s in cell.systems:
            spec = s["schedule"]
            if spec["kind"] == "oblivious":
                perms = reference.oblivious_perms(f.n)
            else:
                perms = served.schedules[(i, s["label"])]
                bad += reference.perm_violations(perms)
                bad += reference.vermilion_violations(perms, req.demand[i],
                                                      spec["k"])
            plan = reference.Plan(perms, cell.d_hat, w, f.n)
            o = dict(label=s["label"])
            if s["mode"] == "single_hop":
                d, tr = reference.serve_singlehop(f, plan, pairs[i], rnd)
                fct = np.full(len(f.size), np.nan)
                for j, (u, v) in enumerate(pairs[i]):
                    idx = np.nonzero((f.src == u) & (f.dst == v))[0]
                    fct[idx] = reference.pair_fcts(
                        f.size[idx], f.arrival[idx], tr[:, j])
                o.update(delivered=float(d.sum()), hops=1.0, fct=fct)
            else:
                d, sec = reference.serve_twohop(
                    f, plan, s["mode"] == "rotorlb", rnd)
                o.update(delivered=float(d.sum()),
                         hops=1.0 + float(sec.sum()) / max(float(d.sum()),
                                                           1e-9),
                         fct=None)
            out.append(o)
    return out, bad
