"""One run of one cell, below the command line: set-up, the measured (or
traced) window, the metrics, and the check that decides ``correct``.

The chip gate lives in ``run.py``; everything here also runs on the CPU at
a tiny size, which is how ``fabric_bench/tests`` drive it.
"""
from __future__ import annotations

import importlib
import itertools
import shutil
import time
from dataclasses import dataclass, field

import numpy as np

from . import harness, trace


@dataclass
class Context:
    """What the metric readers in ``fabric_bench/metrics`` read."""
    cell: harness.Cell
    device_kind: str
    setup_s: float
    window: list = field(default_factory=list)    # Served, untraced window
    traced: list = field(default_factory=list)    # Served, traced window
    trace: trace.Reduced | None = None


def _traces() -> int:
    from repro.core.simulator import compile_cache_stats
    return sum(s["traces"] for s in compile_cache_stats().values())


def measure(cell: harness.Cell, seed: int, seconds: float, traced: bool,
            device_kind: str, t_start: float, trace_dir: str | None = None,
            log=print) -> tuple[Context, dict]:
    """Set up, then serve fresh requests (:func:`harness.make_request`)
    until they have taken ``seconds`` (the last request in flight
    finishes), or, when ``traced``, serve the traffic mix's
    ``trace_requests`` under the profiler.  The window's clock runs only
    while a request is served: making the next request's traffic is the
    benchmark's own work.  Returns the readers' context and facts about
    the run."""
    t_pool = time.perf_counter()
    pool = harness.make_pool(cell)
    requests = (harness.make_request(cell, pool, seed, i)
                for i in itertools.count())
    warm = [next(requests) for _ in pool]
    t_warm = time.perf_counter()
    for req in warm:
        harness.serve(cell, req)
    ctx = Context(cell, device_kind, time.perf_counter() - t_start)
    log(f"setup: start_s={t_pool - t_start!r} traffic_s={t_warm - t_pool!r} "
        f"warm_s={ctx.setup_s - (t_warm - t_start)!r}")
    before = _traces()
    if traced:
        import jax
        batch = [next(requests) for _ in range(cell.traffic["trace_requests"])]
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            ctx.traced = [harness.serve(cell, req) for req in batch]
        finally:
            jax.profiler.stop_trace()
        ctx.trace = trace.reduce(trace.read(trace.newest_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        while not ctx.window or serving_s(ctx.window) < seconds:
            ctx.window.append(harness.serve(cell, next(requests)))
    served = ctx.traced or ctx.window
    facts = dict(requests=len(served), window_s=serving_s(served),
                 compiles_in_window=_traces() - before, pool=len(pool))
    log(f"requests={facts['requests']} window_s={facts['window_s']!r} "
        f"wall_s={served[-1].t1 - served[0].t0!r} "
        f"compiles_in_window={facts['compiles_in_window']} "
        f"pool={facts['pool']} setup_s={ctx.setup_s!r} request_s="
        + ",".join(repr(s.t1 - s.t0) for s in served))
    return ctx, facts


def serving_s(served: list) -> float:
    """Host seconds spent serving ``served``, request by request."""
    return sum(s.t1 - s.t0 for s in served)


def read_metrics(ctx: Context, entries: list) -> dict:
    """Each metric entry's reader, ``fabric_bench/metrics/<name>.py``; a
    reader that finds nothing returns None and its metric is left out."""
    out = {}
    for m in entries:
        reader = importlib.import_module(f"fabric_bench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check(cell: harness.Cell, served: list, seed: int) -> dict:
    """Compare one request of the window, drawn from ``seed``, with the
    reference; returns each compared number with its limit."""
    rng = np.random.default_rng([seed, 1])
    s = served[int(rng.integers(len(served)))]
    pairs = harness.fct_pairs(cell, s.request, rng)
    prog = harness.program_outputs(cell, s)
    ref, bad = harness.reference_outputs(cell, s, pairs)
    numbers = harness.compare(prog, ref, bad)
    return {k: {"value": float(v), "limit": float(cell.limits[k])}
            for k, v in numbers.items() if k in cell.limits}


def is_correct(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
