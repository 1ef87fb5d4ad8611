"""Cells of ``BENCHMARK.json`` shrunk to a size the CPU tests can hold."""
from fabric_bench import harness

ROOT = harness.ROOT.parent

# the two-hop cell keeps n above the program's 64-rack limit for per-flow
# two-hop FCTs, so it runs the dense aggregate kernel as at full size
SIZES = {"ws256_singlehop": (16, 200), "ws256_twohop": (72, 128)}


def cell(name: str) -> harness.Cell:
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    c, _ = harness.load_cell(bench, name, ROOT)
    n, horizon = SIZES[name]
    c.config["n"] = n
    c.traffic["workload"]["horizon"] = horizon
    c.traffic["trace_requests"] = 1
    return c
