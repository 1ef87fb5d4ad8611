"""The harness end to end on the CPU at a tiny size, below the command's
chip gate: request builders, the window, the check and the metrics'
arithmetic."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from fabric_bench import runner, trace
from fabric_bench.tests import tiny

pytest.importorskip("jax")

CELLS = sorted(tiny.SIZES)


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_runs_and_checks(name):
    cell = tiny.cell(name)
    ctx, facts = runner.measure(cell, 2**33 + 1, 0.1, False, "cpu",
                                t_start=0.0, log=lambda *a: None)
    assert facts["compiles_in_window"] == 0
    assert facts["requests"] == len(ctx.window) >= 1
    checked = runner.check(cell, ctx.window, 2**33 + 1)
    assert runner.is_correct(checked), checked
    assert checked["exact"]["value"] == 0
    m = runner.read_metrics(ctx, [{"name": "slot_rate", "unit": "slots/s"}])
    slots = sum(s.request.slots(cell) for s in ctx.window)
    busy = sum(s.t1 - s.t0 for s in ctx.window)
    assert m["slot_rate"]["value"] == pytest.approx(slots / busy)


def test_slots_count_unpadded_case_horizons():
    cell = tiny.cell("ws256_singlehop")
    from fabric_bench import harness
    req = harness.make_request(cell, harness.make_pool(cell), 5, 0)
    # 2 loads x 2 systems, 200 slots each (the kernel pads to 256)
    assert req.slots(cell) == 4 * 200


def test_requests_differ_but_carry_the_pools_work():
    """Two requests on one pool entry offer the same sizes and arrivals,
    on other pairs and with other schedules; (seed, i) fixes each."""
    from fabric_bench import harness
    cell = tiny.cell("ws256_singlehop")
    pool = harness.make_pool(cell)
    seed = 2**31 + 7
    a = harness.make_request(cell, pool, seed, 0)
    b = harness.make_request(cell, pool, seed, len(pool))
    for fa, fb in zip(a.flows, b.flows):
        assert np.array_equal(fa.size, fb.size)
        assert np.array_equal(fa.arrival, fb.arrival)
        assert not np.array_equal(fa.src, fb.src)
        assert np.array_equal(np.sort(fa.demand_matrix(), axis=None),
                              np.sort(fb.demand_matrix(), axis=None))
    assert a.seed != b.seed
    again = harness.make_request(cell, pool, seed, 0)
    assert again.seed == a.seed
    assert np.array_equal(again.flows[0].dst, a.flows[0].dst)


def test_set_up_and_window_never_serve_one_request_twice(monkeypatch):
    from fabric_bench import harness
    cell = tiny.cell("ws256_twohop")
    seen, real = [], harness.serve

    def record(c, req):
        seen.append(req.seed)
        return real(c, req)

    monkeypatch.setattr(harness, "serve", record)
    runner.measure(cell, 9, 0.2, False, "cpu", t_start=0.0,
                   log=lambda *a: None)
    assert len(seen) > len(cell.traffic["pool_seeds"])
    assert len(set(seen)) == len(seen)


def test_metric_arithmetic_on_a_hand_made_trace():
    """Per-layer readers on a trace whose numbers are known."""
    cell = tiny.cell("ws256_singlehop")
    from fabric_bench import harness
    req = harness.make_request(cell, harness.make_pool(cell), 5, 0)
    s = harness.Served(req, [], {}, 0.0, 1.0, 0.25)
    tr = trace.Trace(
        ops=[[("fusion", 0.30, 0.40), ("fusion", 0.35, 0.50),
              ("copy", 0.90, 0.95)]],
        modules=[[("jit_singlehop(7)", 0.30, 0.50)]],
        spans=[("fb.request", 0.0, 1.0), ("fb.construct", 0.0, 0.25),
               ("fb.engine", 0.25, 1.0)])
    ctx = runner.Context(cell, "TPU v5 lite", 3.0, traced=[s],
                         trace=trace.reduce(tr))
    entries = [{"name": n, "unit": "x"} for n in (
        "construct_ms", "engine_host_ms", "singlehop_ms",
        "singlehop_roofline", "twohop_dense_ms", "device_idle", "setup_s")]
    m = {k: v["value"] for k, v in runner.read_metrics(ctx, entries).items()}
    assert m["construct_ms"] == pytest.approx(250.0)
    assert m["engine_host_ms"] == pytest.approx(750.0 - 250.0)
    assert m["singlehop_ms"] == pytest.approx(200.0)
    assert m["device_idle"] == pytest.approx(75.0)
    assert m["setup_s"] == 3.0
    assert "twohop_dense_ms" not in m          # that kernel never ran
    assert 0.0 < m["singlehop_roofline"] < 100.0


def _run(args, cwd, env):
    return subprocess.run([sys.executable, "fabric_bench/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(["--workload", "ws256_singlehop", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tiny.ROOT, env)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_command_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and fabric_bench/ prints no
    result."""
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.ROOT / "fabric_bench", tmp_path / "fabric_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = _run(["--workload", "ws256_singlehop", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path, env)
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
