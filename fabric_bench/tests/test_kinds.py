"""Request kinds (``fabric_bench/kinds/<kind>.py``): the ``sweep`` kind
serves exactly what the harness served before kinds existed, a kind
module the harness has never heard of runs through ``runner`` end to
end, and a traffic file naming a kind with no module is refused."""
import dataclasses
import hashlib
import json
import sys
import time
import types

import numpy as np
import pytest

from fabric_bench import harness, runner
from fabric_bench.tests import tiny

pytest.importorskip("jax")

from repro.core import simulator as sim  # noqa: E402

SEED = 2**31 + 19

# One request of each cell at the tiny sizes (request 1 of the run seeded
# SEED; ws512_twohop at n = 72 on the sparse kernel, as test_ws512.py
# shrinks it), digested on the harness as it stood before the request code
# moved into the ``sweep`` kind.
PINNED = {
    "ws256_singlehop": dict(
        request="4c5c24701c1cff2c00a8",
        schedules="0d88a85e3e2415436be8",
        pairs="0eb81d95984b67161d22",
        program="2300a088d311a60d8d5e",
        reference="2e5637fe64fe64d89d10",
        compare=dict(agg_rel="9.576574704374603e-09", fct_differ="0.0",
                     exact="0.0")),
    "ws256_twohop": dict(
        request="14bed7511c48424693d5",
        schedules="e3b0c44298fc1c149afb",
        pairs="e044a543a899fa5c4e8f",
        program="3d1b80d1be4290bdf393",
        reference="3ad5225661b029408d64",
        compare=dict(agg_rel="1.4986824864240897e-09", fct_differ="nan",
                     exact="0.0")),
    "ws512_twohop": dict(
        request="14bed7511c48424693d5",
        schedules="e3b0c44298fc1c149afb",
        pairs="e044a543a899fa5c4e8f",
        program="8d3ac25e0daebf8010d9",
        reference="3ad5225661b029408d64",
        compare=dict(agg_rel="7.639981652977618e-09", fct_differ="nan",
                     exact="0.0")),
}


def _cell(name: str) -> harness.Cell:
    if name in tiny.SIZES:
        return tiny.cell(name)
    bench = harness.load_json(tiny.ROOT / "BENCHMARK.json")
    cell, _ = harness.load_cell(bench, name, tiny.ROOT)
    cell.config["n"] = 72
    cell.traffic["workload"]["horizon"] = 128
    cell.traffic["trace_requests"] = 1
    return cell


def _hash(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        if isinstance(p, np.ndarray):
            h.update(str(p.dtype).encode() + str(p.shape).encode())
            h.update(np.ascontiguousarray(p).tobytes())
        else:
            h.update(repr(p).encode())
    return h.hexdigest()[:20]


def _outputs(outs: list) -> str:
    return _hash(*[x for o in outs for x in (
        o["label"], repr(float(o["delivered"])), repr(float(o["hops"])),
        "none" if o["fct"] is None else np.asarray(o["fct"], np.float64))])


def digest(cell: harness.Cell, kind) -> dict:
    """One request's traffic, what the program returned for it, the
    reference's outputs and the check's numbers; ``kind`` supplies the
    request functions."""
    pool = kind.make_pool(cell)
    req = kind.make_request(cell, pool, SEED, 1)
    served = kind.serve(cell, req)
    rng = np.random.default_rng([SEED, 1])
    pairs = kind.fct_pairs(cell, req, rng)
    prog = kind.program_outputs(served)
    ref, bad = kind.reference_outputs(cell, served, pairs)
    return dict(
        request=_hash(req.seed, req.slots(cell),
                      repr(kind.kernel_batches(cell, req)),
                      *[a for f in req.flows for a in (
                          f.src, f.dst, f.size, f.arrival, f.n, f.horizon)],
                      *req.demand),
        schedules=_hash(*[x for k in sorted(served.schedules)
                          for x in (k, served.schedules[k])]),
        pairs=_hash(*pairs),
        program=_outputs(prog),
        reference=_outputs(ref),
        compare={k: repr(float(v)) for k, v in
                 harness.compare(prog, ref, bad).items()})


@pytest.mark.parametrize("name", sorted(PINNED))
def test_sweep_kind_serves_as_before(monkeypatch, name):
    from fabric_bench.kinds import sweep
    if name == "ws512_twohop":
        monkeypatch.setattr(sim, "_TWOHOP_DENSE_MAX_N", 64)
    cell = _cell(name)
    assert "kind" not in cell.traffic and cell.kind is sweep
    assert digest(cell, cell.kind) == PINNED[name]


PROBE = "probe_adaptive_loop"
EPOCH = 50


def _probe_serve(cell, req):
    """Each workload of the request as one case of the program's closed
    adaptive loop (``run_adaptive``) on the round robin."""
    cases = [sim.AdaptiveCase(
        wl=wl, epoch_slots=EPOCH, policy="oblivious", d_hat=cell.d_hat,
        recfg_frac=cell.recfg_frac, label=s["label"])
        for wl in req.workloads for s in cell.systems]
    t0 = time.perf_counter()
    with harness.span("fb.request"), harness.span("fb.engine"):
        rows = sim.run_adaptive(cases, cell.bits_per_slot, backend="numpy")
    return harness.Served(req, rows, {}, t0, time.perf_counter(), 0.0)


@pytest.fixture
def probe_cell(monkeypatch):
    """A cell whose mix names a kind no file of the harness names: its
    module exists only here, and takes every function but ``serve`` from
    the ``sweep`` kind."""
    from fabric_bench.kinds import sweep
    mod = types.ModuleType(f"fabric_bench.kinds.{PROBE}")
    for f in ("make_pool", "make_request", "kernel_batches", "fct_pairs",
              "program_outputs", "reference_outputs"):
        setattr(mod, f, getattr(sweep, f))
    mod.serve = _probe_serve
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    cell = tiny.cell("ws256_singlehop")
    cell.traffic["kind"] = PROBE
    cell.traffic["systems"] = [{"label": "adaptive-oblivious",
                                "mode": "single_hop",
                                "schedule": {"kind": "oblivious"}}]
    return cell


def test_a_kind_only_the_test_knows_runs_end_to_end(probe_cell):
    cell = probe_cell
    assert cell.kind.__name__ == f"fabric_bench.kinds.{PROBE}"
    assert not any(PROBE in p.read_text() for p in harness.ROOT.rglob("*")
                   if p.is_file() and p.suffix in (".py", ".json")
                   and "tests" not in p.parts)
    ctx, facts = runner.measure(cell, 2**33 + 5, 0.1, False, "cpu",
                                t_start=0.0, log=lambda *a: None)
    assert facts["requests"] == len(ctx.window) >= 1
    rows = [r for s in ctx.window for r in s.rows]
    assert {type(r) for r in rows} == {sim.AdaptiveRow}
    assert all(np.isfinite(r.result.utilization) for r in rows)
    m = runner.read_metrics(ctx, [{"name": "slot_rate", "unit": "slots/s"},
                                  {"name": "setup_s", "unit": "s"}])
    slots = sum(s.request.slots(cell) for s in ctx.window)
    assert slots == len(ctx.window) * 2 * 200
    assert m["slot_rate"]["value"] == pytest.approx(
        slots / runner.serving_s(ctx.window))
    checked = runner.check(cell, ctx.window, 2**33 + 5)
    assert set(checked) == {"agg_rel", "fct_differ", "exact"}
    assert runner.is_correct(checked), checked
    assert checked["exact"]["value"] == 0


def test_a_broken_kind_comes_out_not_correct(probe_cell, monkeypatch):
    """The check holds the new kind to the reference: a loop that serves
    half the load it was given is not correct."""
    real = sim.run_adaptive

    def half(cases, *args, **kw):
        return real([dataclasses.replace(c, wl=sim.Workload(
            src=c.wl.src[::2], dst=c.wl.dst[::2], size=c.wl.size[::2],
            arrival=c.wl.arrival[::2], n=c.wl.n, horizon=c.wl.horizon))
            for c in cases], *args, **kw)

    monkeypatch.setattr(sim, "run_adaptive", half)
    ctx, _ = runner.measure(probe_cell, 11, 0.0, False, "cpu", t_start=0.0,
                            log=lambda *a: None)
    assert not runner.is_correct(runner.check(probe_cell, ctx.window, 11))


@pytest.mark.parametrize("kind", ["no_such_kind", "sweep/../gen"])
def test_an_unknown_kind_names_the_missing_module(tmp_path, monkeypatch,
                                                  kind):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "limits").mkdir()
    mix = dict(harness.load_json(harness.ROOT / "traffic"
                                 / "singlehop_sweep.json"), kind=kind)
    (tmp_path / "traffic" / "mix.json").write_text(json.dumps(mix))
    (tmp_path / "limits" / "c.json").write_text("{}")
    bench = harness.load_json(tiny.ROOT / "BENCHMARK.json")
    bench["workloads"] = [dict(name="c", config="websearch_n256",
                               traffic="mix", chips=1)]
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    with pytest.raises(SystemExit, match=f"fabric_bench.kinds.{kind}"):
        harness.load_cell(bench, "c", tiny.ROOT)
