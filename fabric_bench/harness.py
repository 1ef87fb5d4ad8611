"""The benchmark's cells: requests built from a configuration and a traffic
mix, served through the program's entry point, and the outputs the check
compares.

A *request* is one grid handed to ``run_sweep(cases, bits_per_slot,
backend="jax")``.  Its traffic comes from the mix's fixed pool, generated
in set-up by :mod:`fabric_bench.gen`; each request relabels the racks and
seeds its schedules afresh, so no two requests of a run are the same
input.  Its schedules are built while it is served, by the program's
public constructors, since building them is part of the user's path.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import gen, reference

ROOT = Path(__file__).resolve().parent
SEED_MAX = 2**31 - 1
# the Vermilion schedules' demand normalization, the one under which
# reference.vermilion_violations holds them to Algorithm 1's guarantee
VERMILION_NORMALIZE = "saturate"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json``: a configuration (the deployment)
    under a traffic mix, with the limits its check holds outputs to."""
    name: str
    config: dict
    traffic: dict
    limits: dict = field(default_factory=dict)

    @property
    def n(self) -> int:
        return int(self.config["n"])

    @property
    def d_hat(self) -> int:
        return int(self.config["d_hat"])

    @property
    def bits_per_slot(self) -> float:
        return float(self.config["link_bps"]) * float(self.config["slot_s"])

    @property
    def recfg_frac(self) -> float:
        return float(self.config["recfg_frac"])

    @property
    def systems(self) -> list:
        """The cases of a request, per workload."""
        return self.traffic["systems"]


def load_cell(bench: dict, workload: str, root: Path) -> tuple[Cell, dict]:
    """The cell named ``workload`` and its entry in ``bench``; each of its
    files is found by the names the entry gives."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"fabric_bench: no workload {workload!r} in "
                         "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(root / conf["file"])
    traffic = load_json(ROOT / "traffic" / f"{entry['traffic']}.json")
    limits = load_json(ROOT / "limits" / f"{workload}.json")
    return Cell(workload, config, traffic, limits), entry


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

@dataclass
class Served:
    """What the timed path produced for one request, and its host times."""
    request: Request
    rows: list
    schedules: dict                 # (workload index, label) -> perms
    t0: float
    t1: float
    construct_s: float              # in the schedule constructors


@contextmanager
def span(name: str):
    """A host span the profiler's trace records (a no-op when no trace is
    running)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


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


def compare(prog: list, ref: list, bad: int) -> dict:
    """The numbers the check holds to its limits:

    * ``agg_rel``: the widest relative gap of a case's delivered bits or
      its mean hop count;
    * ``fct_differ``: the share of compared flows whose completion slot
      differs (NaN where the cell has no per-flow FCTs);
    * ``exact``: cases missing or mislabelled, and schedules that break
      Algorithm 1's guarantee.
    """
    exact = bad + abs(len(prog) - len(ref))
    agg, diff, seen = 0.0, 0, 0

    def rel(x, y):
        x, y = float(x), float(y)
        g = abs(x - y) / max(abs(y), 1.0)
        return g if np.isfinite(g) else np.inf

    for p, r in zip(prog, ref):
        exact += int(p["label"] != r["label"])
        agg = max(agg, rel(p["delivered"], r["delivered"]),
                  rel(p["hops"], r["hops"]))
        if r["fct"] is not None:
            m = ~np.isnan(r["fct"])
            pf = np.asarray(p["fct"])
            if pf.shape != r["fct"].shape:
                exact += 1
                continue
            diff += int((pf[m] != r["fct"][m]).sum())
            seen += int(m.sum())
    return dict(agg_rel=agg,
                fct_differ=(diff / seen if seen else float("nan")),
                exact=exact)
