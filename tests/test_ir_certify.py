"""repro.analysis third layer: IR-level kernel budgets + Theorem-3
schedule certificates (and the roofline/jaxpr flop cross-check)."""
import json

import numpy as np
import pytest

from repro.analysis.certify import (
    batch_parity,
    certify_schedule,
    demand_case,
    main as certify_main,
)
from repro.core.schedule import (
    Schedule,
    vermilion_rounded,
    vermilion_scaled_demands,
    vermilion_schedule,
)
from repro.core.throughput import quantized_theorem3_bound, theorem3_bound


# ---------------------------------------------------------------------------
# IR analyzer (requires jax: the kernels cannot be traced without it)
# ---------------------------------------------------------------------------

def test_ir_reports_all_cached_kernels():
    pytest.importorskip("jax")
    from repro.analysis.ir import analyze_all
    from repro.core.simulator import jax_kernels
    reports = {r.kernel: r for r in analyze_all()}
    assert set(reports) == set(jax_kernels())
    for r in reports.values():
        assert r.flops > 0 and r.bytes_moved > 0 and r.peak_bytes > 0
        assert r.carry_bytes > 0 and r.carry_shapes
        # the kernels are dtype-clean: no float64, weak-type, or uint16
        # arithmetic survives into the traced IR
        assert r.dtype_leaks == [], (r.kernel, r.dtype_leaks)


def test_ir_carry_exponents_pin_bucketed_state():
    pytest.importorskip("jax")
    from repro.analysis.ir import analyze_kernel
    # per-(at, dst) bucketed relay state is ~n^2 (PR 4's contract; the
    # O(n^3) dense relay must never come back) ...
    for k in ("twohop_dense", "twohop_sparse", "singlehop"):
        assert abs(analyze_kernel(k).carry_exponent - 2.0) < 0.1, k
    # ... while the per-flow FCT replay alone carries its deliberate
    # (B, n, n, n) buffer (size-gated separately by _twohop_fct_ok)
    assert analyze_kernel("twohop_fct").carry_exponent > 2.5


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested jaxprs (closed or open)
    included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_ir_sparse_step_has_no_per_circuit_gather_or_scatter():
    """The traced ``twohop_sparse`` step holds one scatter-add, the
    arrivals (K updates), and one gather, the spray's B n D source rows;
    drain, direct hop and link shares index nothing per circuit."""
    jax = pytest.importorskip("jax")
    from repro.core.simulator import jax_kernels, kernel_abstract_inputs
    dims = dict(B=2, n=8, K=32, D=3, P=4)
    closed = jax.make_jaxpr(jax_kernels()["twohop_sparse"])(
        *kernel_abstract_inputs("twohop_sparse", **dims))
    (scan,) = [e for e in _eqns(closed.jaxpr)
               if e.primitive.name == "scan"]
    body = list(_eqns(scan.params["jaxpr"].jaxpr))
    scatters = [e for e in body if "scatter" in e.primitive.name]
    gathers = [e for e in body if e.primitive.name == "gather"]
    assert [e.primitive.name for e in scatters] == ["scatter-add"]
    assert scatters[0].invars[2].aval.size == dims["K"]
    assert len(gathers) <= 1
    for g in gathers:
        idx = g.invars[1].aval.shape
        assert np.prod(idx[:-1]) == dims["B"] * dims["n"] * dims["D"]


def test_ir_dot_flops_match_analytic_form():
    pytest.importorskip("jax")
    from repro.analysis.ir import _REF_DIMS, analyze_kernel
    from repro.core.simulator import _PAD_H
    b, n = _REF_DIMS["B"], _REF_DIMS["n"]
    # the dense relay einsum contracts (B, n, n) x (B, n, n) per slot:
    # 2 * B * n^3 flops for each of the H_pad scanned slots
    assert analyze_kernel("twohop_dense").dot_flops == 2 * b * n**3 * _PAD_H


def test_ir_budget_gate_exit_codes(tmp_path):
    pytest.importorskip("jax")
    from repro.analysis.ir import load_budget, main as ir_main
    bp = tmp_path / "budget.json"
    assert ir_main(["--budget", str(bp), "--write-budget"]) == 0
    assert ir_main(["--budget", str(bp)]) == 0
    # a regressed kernel (budget below measurement) must trip the gate
    b = load_budget(str(bp))
    victim = sorted(b["kernels"])[0]
    b["kernels"][victim]["flops"] = 1
    bp.write_text(json.dumps(b))
    assert ir_main(["--budget", str(bp)]) == 1
    # a kernel the budget has never seen must trip it too
    del b["kernels"][victim]
    b["kernels"][sorted(b["kernels"])[0]]["flops"] = 10**12
    bp.write_text(json.dumps(b))
    assert ir_main(["--budget", str(bp)]) == 1
    # missing budget file: distinct exit
    assert ir_main(["--budget", str(tmp_path / "nope.json")]) == 2


def test_ir_checked_in_budget_is_green(tmp_path):
    pytest.importorskip("jax")
    from repro.analysis.ir import (
        DEFAULT_BUDGET,
        analyze_all,
        check_budget,
        load_budget,
        main as ir_main,
    )
    assert check_budget(analyze_all(), load_budget(DEFAULT_BUDGET)) == []
    # and the CLI emits the machine-readable report CI uploads
    out = tmp_path / "ir_report.json"
    assert ir_main(["--json", str(out)]) == 0
    rep = json.loads(out.read_text())
    assert rep["violations"] == [] and len(rep["reports"]) == 4


def test_roofline_hlo_crosscheck_agrees():
    pytest.importorskip("jax")
    from benchmarks.roofline import kernel_crosscheck
    row = kernel_crosscheck("twohop_dense")
    assert row["agree"], row
    assert row["rel_disagreement"] <= 0.05


# ---------------------------------------------------------------------------
# Certificate checker (numpy-only: verifies without jax or simulation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case,n,k,d_hat", [
    ("skewed", 16, 3, 2),
    ("websearch", 12, 3, 4),
    ("uniform", 8, 2, 1),
])
def test_certificate_holds_on_golden_cases(case, n, k, d_hat):
    m = demand_case(case, n)
    sched = vermilion_schedule(m, k=k, d_hat=d_hat)
    res = certify_schedule(m, sched)
    assert res.ok, res.violations
    assert all(v == "pass" for v in res.checks.values())
    assert res.theta >= res.quantized_bound - 1e-9
    # d_hat | k*n in all three cases, so the finite-period bound achieves
    # the paper's asymptotic (k-1)/k exactly
    assert res.quantized_bound == pytest.approx(theorem3_bound(k))


def test_certificate_with_recfg_and_saturate():
    m = demand_case("skewed", 12, seed=3)
    sched = vermilion_schedule(m, k=3, d_hat=2, recfg_frac=1.0 / 9.0,
                               normalize="saturate", spread=False)
    res = certify_schedule(m, sched)
    assert res.ok, res.violations
    assert res.quantized_bound == pytest.approx(
        theorem3_bound(3, 1.0 / 9.0))


def test_certificate_trips_on_corruptions():
    m = demand_case("skewed", 16)
    s = vermilion_schedule(m, k=3, d_hat=2)

    def failed(sched):
        r = certify_schedule(m, sched)
        assert not r.ok
        return {c for c, v in r.checks.items() if v == "fail"}

    # truncated period: capacity (and the period contract) is lost
    short = Schedule(perms=s.perms[:-2], d_hat=2, name=s.name,
                     meta=dict(s.meta))
    assert "C2_period" in failed(short)
    # a matching replaced by the identity: self-loops serve nothing
    p = s.perms.copy()
    p[0] = np.arange(16)
    assert "C4_emulation" in failed(
        Schedule(perms=p, d_hat=2, name=s.name, meta=dict(s.meta)))
    # a duplicated destination: row is no longer a permutation
    p2 = s.perms.copy()
    p2[1, 0] = p2[1, 1]
    bad = failed(Schedule(perms=p2, d_hat=2, name=s.name, meta=dict(s.meta)))
    assert "C1_perms" in bad and "C5_matchings" in bad


def test_quantized_bound_forms():
    # d_hat | k*n: exactly the asymptotic bound
    assert quantized_theorem3_bound(3, 2, 16) == pytest.approx(
        theorem3_bound(3))
    assert quantized_theorem3_bound(3, 4, 12) == pytest.approx(2.0 / 3.0)
    # a non-dividing d_hat pays the ceiling's slack slot
    assert quantized_theorem3_bound(3, 5, 7) < theorem3_bound(3)
    assert quantized_theorem3_bound(3, 5, 7) == pytest.approx(
        2 * 7 / (5 * 5.0))


def test_rounding_hooks_match_construction():
    m = demand_case("skewed", 12)
    scaled = vermilion_scaled_demands([m], k=3)[0]
    r = vermilion_rounded([m], k=3)[0]
    # Bacharach quantization slack + double substochasticity
    assert np.abs(r - scaled).max() < 1.0
    assert r.sum(axis=0).max() <= 2 * 12 and r.sum(axis=1).max() <= 2 * 12
    assert np.diagonal(r).sum() == 0
    # the hooks feed the same rounding the construction consumes: the
    # schedule's edge counts dominate R + 1 off-diagonal
    sched = vermilion_schedule(m, k=3, d_hat=2)
    counts = sched.edge_counts()
    off = ~np.eye(12, dtype=bool)
    assert (counts[off] >= (r + 1)[off]).all()


def test_batch_parity_pins_batched_construction():
    mats = [demand_case("skewed", 10, seed=s) for s in range(3)]
    assert batch_parity(mats, k=3, d_hat=2) == []


def test_certify_main_emits_certificate(tmp_path):
    out = tmp_path / "cert.json"
    rc = certify_main(["--case", "skewed", "--n", "16", "--k", "3",
                       "--d-hat", "2", "--batch-check",
                       "--json", str(out)])
    assert rc == 0
    cert = json.loads(out.read_text())
    assert cert["checks"]["C8_batch"] == "pass"
    assert cert["violations"] == []
    assert cert["bounds"]["theta"] >= \
        cert["bounds"]["quantized_theorem3"] - 1e-9
    assert len(cert["demand"]["sha256"]) == 64
    assert cert["schedule"]["T"] == 48 and cert["schedule"]["n_slots"] == 24
