"""Benchmark harness: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (``name,derived`` for the
simulated sections, which time nothing).  Sections:
  - throughput_fig7     (Fig 7: throughput across demand matrices)
  - bound_fig8a/b       (Fig 8: convergence to (k-1)/k)
  - fct_fig5            (Fig 5/6: FCT + utilization, websearch)
  - adaptive            (closed estimation->schedule loop, phase shifts)
  - schedule_time_fig10 (Fig 10: schedule computation latency)
  - interconnect        (DESIGN.md §7: pod-axis collective pricing)
  - roofline            (per-cell analytic three-term summary)

Persists the perf trajectory for cross-PR tracking:
  - results/BENCH_schedule.json — construction latency per method per n
    (per-stage breakdown + hk/euler end-to-end speedup)
  - results/BENCH_adaptive.json — closed-loop utilization, with and
    without construction charging, the epoch-length x
    reconfiguration-penalty tradeoff grid, the gather-staleness ->
    schedule-disagreement -> utilization sweep, the fault-injection
    recovery sweep (fault type x severity x policy, with per-epoch
    utilization recovery curves), and the ``jax_adaptive`` engine
    comparison (numpy vs jitted jax wall-clock on the disagreement grid,
    with per-flow FCT percentiles from the jax rows)
  - results/BENCH_twohop.json — two-hop relay engine wall-clock per
    (n, mode, backend), numpy vs jax (min-of-N)
"""
from __future__ import annotations

import json
import pathlib
import sys

RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"


def _adaptive_row_json(row) -> dict:
    r = row.result
    return {
        "label": row.label,
        "policy": row.policy,
        "utilization": r.utilization,
        "completed_frac": r.completed_frac,
        "recomputes": row.recomputes,
        "stale_slots": row.stale_slots,
        "dark_slots": row.dark_slots,
        "construction_s": row.construction_s,
        "mean_disagreement": float(row.epoch_disagreement.mean()),
        "mean_collision_loss": float(row.epoch_collision_loss.mean()),
        "collision_lost_bits": row.collision_lost_bits,
        "schedule_groups_max": row.schedule_groups_max,
        "fault_lost_bits": row.fault_lost_bits,
        "fault_refused_bits": row.fault_refused_bits,
        "dark_plane_slots": row.dark_plane_slots,
        "excised_nodes": row.excised_nodes,
        "excised_planes": row.excised_planes,
        "epoch_utilization": [round(float(u), 6)
                              for u in row.epoch_utilization],
        "meta": row.meta,
    }


def main() -> None:
    from . import (
        adaptive_bench,
        bound_convergence,
        fct_bench,
        interconnect_bench,
        schedule_time,
        throughput_bench,
    )

    throughput_bench.main()
    sys.stdout.flush()
    bound_convergence.main()
    sys.stdout.flush()
    fct_bench.main([])
    sys.stdout.flush()

    (adaptive_rows, charged_rows, tradeoff_rows,
     disagreement_rows, fault_rows, jax_speedup) = adaptive_bench.main([])
    sys.stdout.flush()

    twohop_rows = fct_bench.twohop_table()
    sys.stdout.flush()

    sched_rows = schedule_time.main([])
    sys.stdout.flush()
    interconnect_bench.main()
    sys.stdout.flush()

    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "BENCH_schedule.json").write_text(
        json.dumps(sched_rows, indent=2) + "\n")
    (RESULTS / "BENCH_adaptive.json").write_text(json.dumps({
        "sweep": [_adaptive_row_json(r) for r in adaptive_rows],
        "charged": [_adaptive_row_json(r) for r in charged_rows],
        "epoch_tradeoff": [_adaptive_row_json(r) for r in tradeoff_rows],
        "disagreement": [_adaptive_row_json(r) for r in disagreement_rows],
        "faults": [_adaptive_row_json(r) for r in fault_rows],
        "jax_adaptive": jax_speedup,
    }, indent=2) + "\n")
    (RESULTS / "BENCH_twohop.json").write_text(
        json.dumps(twohop_rows, indent=2) + "\n")

    # roofline summary (analytic three terms per assigned cell)
    from .analytic import cell_cost
    from repro.configs import REGISTRY, shape_cells
    for arch in sorted(REGISTRY):
        for shape in shape_cells(arch):
            c = cell_cost(arch, shape)
            print(f"roofline[{arch},{shape}],0,"
                  f"tc={c.t_compute:.3e};tm={c.t_memory:.3e};"
                  f"tx={c.t_collective:.3e};dom={c.dominant};"
                  f"frac={c.roofline_frac:.3f}")


if __name__ == "__main__":
    main()
