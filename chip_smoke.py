"""Run the fabric simulator's device path on a TPU and check it against the
numpy engine.

    python chip_smoke.py             # one chip: the phases below
    python chip_smoke.py --chips 4   # four chips: schedule-driven collectives

One chip, in order, each through the served entry points
(``run_sweep`` / ``run_adaptive`` with ``backend="jax"``, ``sanitize=True``):

* the paper's single-hop websearch sweep (Fig 5/6) at n = 256 racks, d_hat = 4,
  loads 0.3 and 0.6: vermilion, greedy and oblivious single-hop through the
  ``singlehop`` kernel, rotorlb/vlb through ``twohop_dense``;
* rotorlb/vlb at n = 64 (``twohop_fct``, per-flow FCTs) and at n = 512
  (``twohop_sparse``), the two sides of the two-hop kernel crossovers;
* the adaptive epoch loop on the phase-shifting workload at n = 128,
  d_hat = 4, 150-slot epochs, policies adaptive / oracle / oblivious;
* the golden n = 10 instances of ``tests/test_simulator.py``.

Every phase runs again through the numpy engine: each case's utilization and
delivered bits must agree within 1e-3 relative, and the golden instances'
FCT multisets must be equal.  With ``--chips 4`` only ``run_schedule_demo``
runs: all-gather, all-reduce and permute built from a schedule's matchings,
64 MiB per chip, against ``all_gather`` / ``psum`` / the transpose.

Each phase prints one line; its times are host wall clock with the device
named, for information only.  Any failure raises.  Without a TPU the script
exits non-zero before the first phase.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from benchmarks import adaptive_bench, fct_bench  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.schedule import (  # noqa: E402
    oblivious_schedule,
    vermilion_schedule,
)
from repro.core.simulator import (  # noqa: E402
    SweepCase,
    compile_cache_stats,
    run_adaptive,
    run_sweep,
    websearch_workload,
)

RTOL = 1e-3                    # the jax engine's documented f32 tolerance
BITS = fct_bench.BITS_PER_SLOT
SWEEP = dict(n=256, d_hat=4, horizon=1000, loads=(0.3, 0.6))
TWOHOP = (dict(n=64, d_hat=4, horizon=1000, loads=(0.3, 0.6)),
          dict(n=512, d_hat=4, horizon=384, loads=(0.6,)))
ADAPTIVE = dict(n=128, d_hat=4, load=0.5, horizon=3000, shift_period=1000,
                epoch_slots=150, seed=1)
GOLDEN = dict(n=10, d_hat=2, load=0.45, horizon=400, seeds=(0, 3, 7),
              bits=25e9 * 4.5e-6)
COLLECTIVE_ELEMS = 16 << 20    # float32 per chip and payload: 64 MiB


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _buckets() -> set:
    return {(k, b) for k, st in compile_cache_stats().items()
            for b in st["shape_buckets"]}


def _check(phase: str, labels, jx, ref, fct_exact: bool = False) -> str:
    """Hold each jax result to its numpy reference; return the verdict."""
    worst, fct_diff, fct_n = 0.0, 0, 0
    for label, a, b in zip(labels, jx, ref):
        for attr in ("utilization", "delivered_bits"):
            x, y = getattr(a, attr), getattr(b, attr)
            rel = abs(x - y) / abs(y) if y else abs(x)
            if not rel <= RTOL:
                raise AssertionError(
                    f"{phase}/{label}: {attr} jax {x!r} vs numpy {y!r} "
                    f"(relative {rel!r} > {RTOL})")
            worst = max(worst, rel)
        if np.isfinite(a.fct_slots).any():
            fct_diff += int(np.sum(a.fct_slots != b.fct_slots))
            fct_n += a.fct_slots.size
            if fct_exact and not np.array_equal(np.sort(a.fct_slots),
                                                np.sort(b.fct_slots)):
                raise AssertionError(
                    f"{phase}/{label}: FCT multiset differs from numpy")
    fct = (f"fct_flows_differ={fct_diff}/{fct_n}" if fct_n
           else "fct=aggregate-only")
    return f"max_rel={worst!r} {fct}"


def _phase(phase: str, dims: str, kind: str, labels, run_jax, run_numpy,
           fct_exact: bool = False) -> None:
    before = _buckets()
    jx, first_s = _timed(run_jax)
    jx, warm_s = _timed(run_jax)
    ref, numpy_s = _timed(run_numpy)
    verdict = _check(phase, labels, [r.result for r in jx],
                     [r.result for r in ref], fct_exact)
    kernels = ",".join(f"{k}{b}" for k, b in sorted(_buckets() - before))
    print(f"[{phase}] {dims} device={kind!r} setup_s={first_s - warm_s!r} "
          f"warm_s={warm_s!r} numpy_s={numpy_s!r} kernels={kernels} "
          f"parity=ok {verdict}", flush=True)


def _sweep_phase(phase: str, cases: list[SweepCase], dims: str, kind: str,
                 bits: float = BITS, fct_exact: bool = False) -> None:
    _phase(phase, f"{dims} B={len(cases)}", kind,
           [c.label + (f"@{c.meta['load']}" if "load" in c.meta else "")
            for c in cases],
           lambda: run_sweep(cases, bits, backend="jax", sanitize=True),
           lambda: run_sweep(cases, bits), fct_exact)


def one_chip(kind: str) -> None:
    s = SWEEP
    _sweep_phase(
        "websearch_sweep",
        fct_bench.build_grid(s["n"], s["d_hat"], s["horizon"], s["loads"]),
        f"n={s['n']} d_hat={s['d_hat']} horizon={s['horizon']} "
        f"loads={s['loads']}", kind)

    for t in TWOHOP:
        obl = oblivious_schedule(t["n"], d_hat=t["d_hat"],
                                 recfg_frac=fct_bench.RECFG)
        cases = []
        for load in t["loads"]:
            wl = websearch_workload(t["n"], load, t["horizon"], BITS,
                                    d_hat=t["d_hat"], seed=1)
            cases += [SweepCase(obl, wl, m, m, meta={"load": load})
                      for m in ("rotorlb", "vlb")]
        _sweep_phase(
            f"twohop_n{t['n']}", cases,
            f"n={t['n']} d_hat={t['d_hat']} horizon={t['horizon']} "
            f"loads={t['loads']}", kind)

    a = ADAPTIVE
    cases = [c for c in adaptive_bench.build_cases(
                 a["n"], a["d_hat"], a["load"], a["horizon"],
                 a["shift_period"], a["epoch_slots"], a["seed"],
                 alphas=(0.3,))
             if c.policy in ("adaptive", "oracle", "oblivious")
             and c.gather_steps is None]
    _phase("adaptive", f"n={a['n']} d_hat={a['d_hat']} B={len(cases)} "
           f"horizon={a['horizon']} epochs={a['horizon'] // a['epoch_slots']}"
           f"x{a['epoch_slots']}", kind, [c.label for c in cases],
           lambda: run_adaptive(cases, adaptive_bench.BITS_PER_SLOT,
                                backend="jax", sanitize=True),
           lambda: run_adaptive(cases, adaptive_bench.BITS_PER_SLOT))

    g = GOLDEN
    cases = []
    for seed in g["seeds"]:
        wl = websearch_workload(g["n"], g["load"], g["horizon"], g["bits"],
                                d_hat=g["d_hat"], seed=seed)
        cases.append(SweepCase(
            vermilion_schedule(wl.demand_matrix(), k=3, d_hat=g["d_hat"],
                               recfg_frac=fct_bench.RECFG, seed=seed),
            wl, "single_hop", f"single_hop-s{seed}"))
        cases.append(SweepCase(
            oblivious_schedule(g["n"], d_hat=g["d_hat"],
                               recfg_frac=fct_bench.RECFG),
            wl, "rotorlb", f"rotorlb-s{seed}"))
    _sweep_phase("golden", cases,
                 f"n={g['n']} d_hat={g['d_hat']} horizon={g['horizon']} "
                 f"load={g['load']}", kind, bits=g["bits"], fct_exact=True)


def four_chips(kind: str, count: int) -> None:
    from repro.core.optical import run_schedule_demo
    res, wall_s = _timed(
        lambda: run_schedule_demo(row_elems=COLLECTIVE_ELEMS))
    if not all(res.values()):
        raise AssertionError(f"schedule-driven collectives disagree with "
                             f"XLA's on some device: {res}")
    print(f"[collectives] n={count} bytes_per_chip={COLLECTIVE_ELEMS * 4} "
          f"device={kind!r} wall_s={wall_s!r} (compile included) "
          f"parity=ok {res}", flush=True)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip collective phase")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax.devices()[0] is "
                         f"{devs[0].platform!r}); not falling back")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devs)} device(s)")
    kind = devs[0].device_kind
    print(f"compile cache: {cache_dir}", flush=True)
    if args.chips == 4:
        four_chips(kind, len(devs))
    else:
        one_chip(kind)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
