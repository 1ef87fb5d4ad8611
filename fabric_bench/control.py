"""Readings that the check's limits are set from.

    python3 fabric_bench/control.py --workload <cell> --seeds 1 2 3 ...

For each seed: one request of the cell, at the cell's own size, is served
by the program (as in a run's window), and the check's numbers are read
twice against the float64 reference: for the program's outputs (the lower
readings), and for the control's, the same reference computed in bfloat16,
the precision below the float32 the configuration serves in (the upper
readings).  One JSON line per seed.  Runs on whatever JAX finds; the
limits come from runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from fabric_bench import harness, reference  # noqa: E402


def readings(cell: harness.Cell, seed: int, control: bool = True) -> dict:
    """The program's numbers for one request, and the control's."""
    req = harness.make_request(cell, harness.make_pool(cell), seed, 0)
    harness.serve(cell, req)
    served = harness.serve(cell, req)
    rng = np.random.default_rng([seed, 1])
    pairs = harness.fct_pairs(cell, req, rng)
    t0 = time.perf_counter()
    ref, bad = harness.reference_outputs(cell, served, pairs)
    ref_s = time.perf_counter() - t0
    out = dict(seed=seed,
               program=harness.compare(harness.program_outputs(cell, served),
                                       ref, bad),
               reference_s=ref_s, serve_s=served.t1 - served.t0)
    if control:
        low, _ = harness.reference_outputs(cell, served, pairs,
                                           rnd=reference.round_bf16)
        out["control"] = harness.compare(low, ref, 0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="read the control on the first this many seeds")
    args = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell, _ = harness.load_cell(bench, args.workload, ROOT)
    import jax
    print(f"device={jax.devices()[0].device_kind!r}", flush=True)
    for i, seed in enumerate(args.seeds):
        print(json.dumps(readings(cell, seed, i < args.controls)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
