"""Paper Fig 5 + Fig 6: flow completion times and link utilization for the
websearch workload, 5%..70% load, all systems.

The whole load x system grid goes through :func:`repro.core.simulator.run_sweep`
in one call — single-hop systems advance through the sparse batched engine,
rotorlb/vlb through the dense-relay engine.  ``--backend jax`` runs the same
grid through the jitted lax.scan kernels (``singlehop`` / ``twohop_fct``),
which emit real per-flow FCTs — every column, including the percentiles and
``done``, is populated on both backends.  ``main`` also prints a
before/after timing table against the
pre-vectorization reference engine (``--no-timing`` skips it; ``--timing-n``
sets the node count, default 64).  :func:`twohop_table` times the two-hop
relay engine numpy-vs-jax per (n, mode) with min-of-N wall clocks — the rows
``benchmarks/run.py`` persists to ``results/BENCH_twohop.json``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.schedule import (
    greedy_matching_schedule,
    oblivious_schedule,
    vermilion_schedule,
)
from repro.core.simulator import (
    SweepCase,
    run_sweep,
    simulate_reference,
    websearch_workload,
)

RECFG = 1 / 9
BITS_PER_SLOT = 100e9 * 4.5e-6          # 100G links, 4.5us slots (paper)
SHORT = 100e3 * 8                        # <=100KB flows
LONG = 1e6 * 8                           # >1MB flows
LOADS = (0.05, 0.15, 0.3, 0.45, 0.6, 0.7)


def build_grid(n: int, d_hat: int, horizon: int, loads=LOADS,
               seed: int = 1) -> list[SweepCase]:
    """The benchmark's load x system grid as sweep cases."""
    cases = []
    obl = oblivious_schedule(n, d_hat=d_hat, recfg_frac=RECFG)
    for load in loads:
        wl = websearch_workload(n, load, horizon, BITS_PER_SLOT,
                                d_hat=d_hat, seed=seed)
        m = wl.demand_matrix()
        systems = {
            "vermilion": (vermilion_schedule(
                m, k=3, d_hat=d_hat, recfg_frac=RECFG,
                normalize="saturate"), "single_hop"),
            "greedy": (greedy_matching_schedule(
                m, n_matchings=3 * n, d_hat=d_hat, recfg_frac=RECFG),
                "single_hop"),
            "rotorlb": (obl, "rotorlb"),
            "vlb": (obl, "vlb"),
            "obl-singlehop": (obl, "single_hop"),
        }
        for name, (sched, mode) in systems.items():
            cases.append(SweepCase(
                sched=sched, wl=wl, mode=mode, label=name,
                meta={"load": load}))
    return cases


def run(n: int = 16, d_hat: int = 4, horizon: int = 4000,
        loads=LOADS, seed: int = 1, backend: str = "numpy") -> list[dict]:
    rows = []
    for sr in run_sweep(build_grid(n, d_hat, horizon, loads, seed),
                        BITS_PER_SLOT, backend=backend):
        r = sr.result
        rows.append({
            "system": sr.label, "load": sr.meta["load"],
            "backend": backend,
            "p99_short": r.fct_percentile(99, short_cutoff=SHORT),
            "p99_long": r.fct_percentile(99, long_cutoff=LONG),
            "p50_short": r.fct_percentile(50, short_cutoff=SHORT),
            "util": r.utilization,
            "done": r.completed_frac,
            "hops": r.avg_hops,
        })
    return rows


def twohop_table(ns=(32, 64, 128, 256), d_hat: int = 2, horizon: int = 300,
                 load: float = 0.4, repeats: int = 3,
                 seed: int = 1) -> list[dict]:
    """Two-hop relay engine wall-clock per (n, mode, backend), min-of-N.

    The jax backend is warmed up once per shape before timing so the
    min-of-N excludes compilation; the numpy engine has no compile to
    exclude.  Rows feed ``results/BENCH_twohop.json`` (the cross-PR perf
    trajectory for the relay data plane).  Skips the jax rows (with a
    note) when jax is not installed; otherwise ends with the jit
    compile-cache counters (one trace per shape bucket — a hit count far
    below the call count would mean the kernels are retracing).
    """
    try:
        import jax  # noqa: F401
        have_jax = True
    except ImportError:
        have_jax = False
    rows = []
    print(f"# twohop engine timing: websearch uniform load={load} "
          f"d_hat={d_hat} horizon={horizon} (min of {repeats})")
    print("name,us_per_call,derived")
    for n in ns:
        wl = websearch_workload(n, load, horizon, BITS_PER_SLOT,
                                d_hat=d_hat, seed=seed, pattern="uniform")
        sched = oblivious_schedule(n, d_hat=d_hat, recfg_frac=RECFG)
        for mode in ("rotorlb", "vlb"):
            cases = [SweepCase(sched, wl, mode, mode)]
            base: dict[str, float] = {}
            for backend in ("numpy", "jax"):
                if backend == "jax":
                    if not have_jax:
                        print(f"# twohop[{mode},n={n},jax] skipped: "
                              "jax not installed")
                        continue
                    run_sweep(cases, BITS_PER_SLOT, backend="jax")  # warmup
                best, row = None, None
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    sr = run_sweep(cases, BITS_PER_SLOT, backend=backend)[0]
                    dt = time.perf_counter() - t0
                    if best is None or dt < best:
                        best, row = dt, sr
                base[backend] = best
                speedup = base["numpy"] / best
                rows.append({
                    "n": n, "mode": mode, "backend": backend,
                    "horizon": horizon, "seconds": best,
                    "speedup_vs_numpy": speedup,
                    "util": row.result.utilization,
                    "avg_hops": row.result.avg_hops,
                })
                print(f"twohop[{mode},n={n},{backend}],{best * 1e6:.0f},"
                      f"speedup={speedup:.1f}x;"
                      f"util={row.result.utilization:.3f};"
                      f"hops={row.result.avg_hops:.2f}")
    if have_jax:
        from repro.core.simulator import compile_cache_stats
        for kern, st in compile_cache_stats().items():
            if st["calls"]:
                print(f"# compile_cache[{kern}]: traces={st['traces']} "
                      f"calls={st['calls']} hits={st['hits']} "
                      f"shapes={st['shape_buckets']}")
    return rows


def timing_table(n: int = 64, d_hat: int = 4, horizon: int = 1500,
                 loads=(0.05, 0.3, 0.6), seed: int = 1) -> None:
    """Before/after wall time of the engine rebuild on the websearch grid."""
    cases = build_grid(n, d_hat, horizon, loads, seed)
    # run_sweep partitions into one single-hop and one two-hop batch
    # internally, so the group times sum to the whole-grid time
    t0 = time.perf_counter()
    run_sweep([c for c in cases if c.mode == "single_hop"], BITS_PER_SLOT)
    t_new_sh = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_sweep([c for c in cases if c.mode != "single_hop"], BITS_PER_SLOT)
    t_new_th = time.perf_counter() - t0
    t_new = t_new_sh + t_new_th

    groups = {"single_hop": 0.0, "two_hop": 0.0}
    t_old = 0.0
    for c in cases:
        t0 = time.perf_counter()
        simulate_reference(c.sched, c.wl, BITS_PER_SLOT, mode=c.mode)
        dt = time.perf_counter() - t0
        t_old += dt
        groups["single_hop" if c.mode == "single_hop" else "two_hop"] += dt

    print(f"# engine timing: websearch n={n} d_hat={d_hat} "
          f"horizon={horizon} ({len(cases)} cases)")
    print("# group,old_engine_s,new_engine_s,speedup")
    print(f"timing[single_hop,n={n}],{groups['single_hop']:.2f},"
          f"{t_new_sh:.2f},{groups['single_hop'] / t_new_sh:.1f}x")
    print(f"timing[two_hop,n={n}],{groups['two_hop']:.2f},"
          f"{t_new_th:.2f},{groups['two_hop'] / t_new_th:.1f}x")
    print(f"timing[all,n={n}],{t_old:.2f},{t_new:.2f},"
          f"{t_old / t_new:.1f}x")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--horizon", type=int, default=4000)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy")
    ap.add_argument("--no-timing", action="store_true")
    ap.add_argument("--timing-n", type=int, default=64)
    ap.add_argument("--twohop-timing", action="store_true",
                    help="also run the numpy-vs-jax twohop_table")
    args = ap.parse_args(argv)
    enable_compile_cache()

    rows = run(n=args.n, horizon=args.horizon, backend=args.backend)
    print("name,derived")
    for r in rows:
        print(f"fct_fig5[{r['system']},load={r['load']},{r['backend']}],"
              f"p99short={r['p99_short']:.0f};p99long={r['p99_long']:.0f};"
              f"util={r['util']:.3f};done={r['done']:.3f};hops={r['hops']:.2f}")
    if not args.no_timing:
        timing_table(n=args.timing_n)
    if args.twohop_timing:
        twohop_table()


if __name__ == "__main__":
    main()
