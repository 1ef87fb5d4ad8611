"""One run of one cell of the fabric simulator's benchmark, on the chip.

    python3 fabric_bench/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout; its
configuration, traffic mix, limits and metric readers are files under
``fabric_bench/`` found by name.  Set-up generates the traffic mix's pool
and serves one request per pool entry (so every shape is compiled, or
loaded from the persistent cache, before the window).  With ``--trace 0``
the window serves fresh requests, each a pool entry with its racks
relabelled and its schedules seeded from ``--seed`` and its index, for
``--seconds`` of serving, and the result carries the cell's end-to-end
metrics; with ``--trace 1`` a few whole requests run under the profiler
and the result carries its per-layer metrics.  Either way one request of
the window is then checked against the plain reference.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any request.  The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` (traced runs) and ``check``, the compared numbers with their
limits, which are also the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _cache_dir() -> str:
    """JAX's persistent compilation cache: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` at the root of
    the checkout; a fixed path, since it is part of every cache key."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import numpy as np

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    from fabric_bench import harness, runner
    cell, entry = harness.load_cell(bench, args.workload, ROOT)

    cache = _cache_dir()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"fabric_bench: no TPU (jax.devices()[0] is "
              f"{devs[0].platform!r}); not falling back", file=sys.stderr)
        return 2
    if len(devs) < entry["chips"]:
        print(f"fabric_bench: {args.workload} needs {entry['chips']} chips, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    print(f"device={devs[0].platform}:{kind!r} x{len(devs)} "
          f"compile_cache={cache}", flush=True)

    ctx, facts = runner.measure(
        cell, args.seed, args.seconds, bool(args.trace), kind, T_START,
        trace_dir=str(ROOT / ".fabric_bench_out" / "trace" / args.workload))
    stats = [d.memory_stats() or {} for d in devs[:entry["chips"]]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    key = "per_layer" if args.trace else "end_to_end"
    entries = [m for m in bench[key]
               if args.workload in m.get("workloads", [args.workload])]
    metrics = runner.read_metrics(ctx, entries)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if args.trace:
        if ctx.trace is not None:
            device.update(busy_s=ctx.trace.busy_s,
                          window_s=ctx.trace.window_s)

    served = ctx.traced or ctx.window
    failed = sum(not all(np.isfinite(r.result.utilization) for r in s.rows)
                 for s in served)
    checked = runner.check(cell, served, args.seed)
    for k, c in checked.items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    result = {"correct": runner.is_correct(checked) and failed == 0,
              "attempted": facts["requests"], "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace and ctx.trace is not None:
        result["breakdown"] = {"device_ops": ctx.trace.device_ops,
                               "idle_gaps": ctx.trace.idle_gaps}
    result["check"] = checked
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
