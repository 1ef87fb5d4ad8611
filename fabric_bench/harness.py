"""The benchmark's cells: a configuration and a traffic mix, the request
kind that serves the mix through the program's entry point, and the
comparison the check makes of what it produced.

A traffic mix (``fabric_bench/traffic/<mix>.json``) names its *request
kind* under ``"kind"``, ``"sweep"`` when absent: the module
``fabric_bench/kinds/<kind>.py``, found by name as metric readers and
kernel counts are.  The request-specific half of a run is the kind's; the
functions below of the same names hand each call to the cell's kind (the
cell first, also for ``program_outputs``).  A kind module provides:

* ``make_pool(cell)``: the mix's fixed traffic, generated in set-up, so
  every run offers the same work;
* ``make_request(cell, pool, seed, i)``: request ``i`` of the run seeded
  ``seed``, a function of ``(seed, i)`` and the pool alone; its
  ``slots(cell)`` is what it counts towards ``slot_rate``: simulated slots
  times cases, unpadded;
* ``serve(cell, req) -> Served``: one request through the program's entry
  point.  ``Served.t0`` / ``t1`` are the host clock around all of it (the
  program's spans inside them are the request's), and it opens the spans
  the shared readers read: ``fb.request`` around the whole request (the
  traced window, and the clock the span readers map the program's spans
  by), ``fb.engine`` around the program's entry call (``engine_host_ms``,
  the idle gaps) and, where it builds schedules outside that call,
  ``fb.construct`` around them with their seconds in
  ``Served.construct_s``.  Each of ``Served.rows`` carries
  ``result.utilization`` (the program's ``SweepRow`` and ``AdaptiveRow``
  both do): ``run.py`` counts a request with a non-finite one as failed;
* ``kernel_batches(cell, req)``: per kernel batch name, the problem
  shapes the request hands it (``fabric_bench/kernels`` count the least
  work from these);
* ``fct_pairs(cell, req, rng)``: what the check compares of a request,
  drawn from ``rng``;
* ``program_outputs(served)`` and ``reference_outputs(cell, served,
  pairs, rnd=None) -> (outputs, bad)``: one dict per case, ``label``,
  ``delivered``, ``hops`` and ``fct`` (None where not compared), in the
  form :func:`compare` takes; ``rnd`` rounds the reference's state (the
  control).  The reference imports nothing of the program and takes
  nothing it made but what it holds to a stated guarantee (schedules).

A kind may import another kind's functions and define only what it
changes.
"""
from __future__ import annotations

import importlib
import json
import re
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


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

    @property
    def kind(self):
        """The module that serves the traffic mix's requests."""
        return load_kind(self.traffic.get("kind", "sweep"))


def load_kind(name: str):
    """``fabric_bench/kinds/<name>.py``; exits naming the module where
    there is none."""
    module = f"fabric_bench.kinds.{name}"
    if re.fullmatch(r"\w+", name):
        try:
            return importlib.import_module(module)
        except ModuleNotFoundError as e:
            if e.name != module:
                raise
    raise SystemExit(f"fabric_bench: request kind {name!r} has no module "
                     f"{module} (fabric_bench/kinds/{name}.py)")


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
    cell = Cell(workload, config, traffic, limits)
    cell.kind                       # a mix naming no kind module stops here
    return cell, entry


# --- serving ---------------------------------------------------------------

@dataclass
class Served:
    """What the timed path produced for one request, and its host times."""
    request: object                 # the kind's request
    rows: list                      # what the entry point returned
    schedules: dict                 # the program's schedules the
                                    # reference is to serve, by case
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


# --- the cell's request kind ------------------------------------------------

def make_pool(cell: Cell) -> list:
    return cell.kind.make_pool(cell)


def make_request(cell: Cell, pool: list, seed: int, i: int):
    return cell.kind.make_request(cell, pool, seed, i)


def serve(cell: Cell, req) -> Served:
    return cell.kind.serve(cell, req)


def kernel_batches(cell: Cell, req) -> dict:
    return cell.kind.kernel_batches(cell, req)


def fct_pairs(cell: Cell, req, rng: np.random.Generator) -> list:
    return cell.kind.fct_pairs(cell, req, rng)


def program_outputs(cell: Cell, served: Served) -> list:
    return cell.kind.program_outputs(served)


def reference_outputs(cell: Cell, served: Served, pairs: list,
                      rnd=None) -> tuple[list, int]:
    return cell.kind.reference_outputs(cell, served, pairs, rnd)


# --- the check ---------------------------------------------------------------

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
