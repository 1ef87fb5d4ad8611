"""IR-level kernel analyzer: static jaxpr accounting for the scan kernels.

The AST lint (:mod:`repro.analysis.lint`) sees *source*; since the hot path
became four jitted ``lax.scan`` kernels, the structures that matter — a
dense ``(B, n, n)`` intermediate materialized inside a scan body, a float64
promotion surviving tracing, a carry that silently grew a dimension — only
exist post-tracing.  This module traces every cached kernel with the same
shape-bucketed abstract inputs the compile cache uses
(:func:`repro.core.simulator.kernel_abstract_inputs`), walks the resulting
``ClosedJaxpr``, and reports per kernel:

* **flops / dot_flops** — an analytic op count (elementwise = output size,
  reductions = input size, ``dot_general`` = 2·M·N·K, scan bodies scaled by
  trip count).  ``dot_flops`` is the ``dot_general``-only subtotal, the
  quantity :mod:`benchmarks.roofline`'s HLO parser also counts — the two
  front-ends cross-check each other.
* **bytes_moved** — operand + result bytes per equation (scan bodies scaled
  by trip count): the numerator of an arithmetic-intensity estimate.
* **peak_bytes** — peak live-buffer bytes from a liveness walk over the
  equation list (last-use analysis; nested sub-jaxprs contribute their own
  peak on top of the live set at their call site).
* **carry scaling** — the scan-carry footprint, measured at the reference
  fabric size and at doubled ``n``; the fitted exponent
  ``log2(carry(2n)/carry(n))`` is the IR-level R1.  The bucketed relay
  kernels must stay at ~n² (per-(at, dst) state — *not* the O(n³) dense
  relay PR 4 eliminated); ``twohop_fct`` alone is allowed its deliberate
  n³ per-flow replay buffer (separately size-gated by ``_twohop_fct_ok``).
* **dtype leaks** — float64 results, weak-typed results, and uint16
  arithmetic surviving into the IR (the quantizer's 16-bit counters wrap
  silently).

Budgets live in ``ir_budget.json`` next to this module (same freeze
pattern as the lint's ``baseline.json``): any PR that regresses a kernel's
footprint, op count, carry exponent, or dtype hygiene fails CI with a
diff.  ``--write-budget`` regenerates the file.

Usage::

    PYTHONPATH=src python -m repro.analysis.ir                # report + gate
    PYTHONPATH=src python -m repro.analysis.ir --write-budget # refreeze
    PYTHONPATH=src python -m repro.analysis.ir --json out.json

Violations print in the lint's report format (``kernel: RULE[tag] msg``)
and exit 1; a missing budget file exits 2.  Requires jax (the kernels
cannot be traced without it) — the CLI exits 3 with a clear message when
jax is absent, and the library raises ``ImportError``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from dataclasses import dataclass, field

__all__ = [
    "KernelReport",
    "analyze_kernel",
    "analyze_all",
    "check_budget",
    "write_budget",
    "load_budget",
    "main",
    "DEFAULT_BUDGET",
]

DEFAULT_BUDGET = os.path.join(os.path.dirname(__file__), "ir_budget.json")

# Reference bucket the budget is frozen at, and the doubled-n probe used
# to fit the carry exponent.  Matches the compile cache's smallest real
# bucket shape (B=2 cases, n=8 ToRs, H padded to 128).
_REF_DIMS = {"B": 2, "n": 8}
_REF_N2 = 16

# -- flop model -------------------------------------------------------------
# One flop per output element:
_EW = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg", "abs",
    "sign", "floor", "ceil", "round", "exp", "log", "log1p", "expm1",
    "sqrt", "rsqrt", "tanh", "logistic", "erf", "max", "min", "and", "or",
    "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "lt", "le", "gt", "ge", "eq", "ne",
    "select_n", "clamp", "nextafter", "atan2", "is_finite",
})
# One flop per *input* element (tree reductions / prefix ops):
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumprod", "cummax",
    "cummin", "cumlogsumexp", "reduce_precision", "sort",
})
# Pure data movement — bytes, not flops:
_MOVE = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "gather", "rev", "pad", "iota", "copy", "convert_element_type",
    "stop_gradient", "real", "imag", "device_put", "split",
})
# flops = size of the updates operand (third input):
_SCATTER = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter-mul", "scatter-max",
    "scatter-min", "scatter_apply",
})
# Arithmetic primitives that make a uint16 result a wraparound hazard:
_UINT16_ARITH = frozenset({"add", "sub", "mul", "pow", "integer_pow"})


def _nbytes(aval) -> int:
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


@dataclass
class _Cost:
    flops: int = 0
    dot_flops: int = 0
    bytes_moved: int = 0
    peak_bytes: int = 0
    carry_bytes: int = 0
    carry_shapes: list[str] = field(default_factory=list)
    leaks: list[str] = field(default_factory=list)
    unknown: set[str] = field(default_factory=set)

    def add_scaled(self, sub: "_Cost", times: int) -> None:
        """Fold a sub-jaxpr executed ``times`` times (a scan body)."""
        self.flops += sub.flops * times
        self.dot_flops += sub.dot_flops * times
        self.bytes_moved += sub.bytes_moved * times
        self.carry_bytes += sub.carry_bytes
        self.carry_shapes.extend(sub.carry_shapes)
        self.leaks.extend(sub.leaks)
        self.unknown |= sub.unknown


def _closed(obj):
    """Normalize a params entry to (ClosedJaxpr | None) — duck-typed so
    this file never imports a jax internal module."""
    if hasattr(obj, "jaxpr") and hasattr(obj, "consts"):
        return obj
    return None


def _eqn_flops(eqn, cost: _Cost) -> int:
    """Analytic flop count for one non-container equation."""
    p = eqn.primitive.name
    out_size = sum(int(v.aval.size) for v in eqn.outvars
                   if hasattr(v, "aval"))
    in_sizes = [int(v.aval.size) for v in eqn.invars if hasattr(v, "aval")]
    if p in _EW:
        return out_size
    if p in _REDUCE:
        return max(in_sizes, default=0)
    if p in _MOVE:
        return 0
    if p in _SCATTER:
        return in_sizes[2] if len(in_sizes) >= 3 else max(in_sizes, default=0)
    if p == "dot_general":
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        cdim = 1
        for d in lhs_c:
            cdim *= int(lhs.shape[d])
        f = 2 * out_size * cdim
        cost.dot_flops += f
        return f
    cost.unknown.add(p)
    return 0


def _eqn_leaks(eqn, cost: _Cost) -> None:
    p = eqn.primitive.name
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "dtype"):
            continue
        if str(aval.dtype) == "float64":
            cost.leaks.append(f"float64:{p}")
        if getattr(aval, "weak_type", False):
            cost.leaks.append(f"weak:{p}")
        if p in _UINT16_ARITH and str(aval.dtype) == "uint16":
            cost.leaks.append(f"uint16-arith:{p}")


def _analyze(jaxpr) -> _Cost:
    """Walk one ``jax.core.Jaxpr``: flops / bytes / liveness / carries.

    Containers recurse: ``scan`` scales its body by trip count and records
    carry avals; ``jit``/call-like primitives fold their inner jaxpr once;
    ``cond`` takes the max over branches; ``while`` folds cond+body once
    (no static trip count — flagged via ``unknown``).
    """
    cost = _Cost()

    # liveness: last equation index at which each var is read.  Literals
    # are unhashable (and cost nothing); real Vars carry a .count.
    def _is_var(v) -> bool:
        return hasattr(v, "aval") and hasattr(v, "count")

    n_eqns = len(jaxpr.eqns)
    last_use: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if _is_var(v):
            last_use[v] = n_eqns

    live: dict = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        live[v] = _nbytes(v.aval)
    live_bytes = sum(live.values())
    cost.peak_bytes = live_bytes

    for i, eqn in enumerate(jaxpr.eqns):
        p = eqn.primitive.name
        nested_peak = 0
        _eqn_leaks(eqn, cost)

        if p == "scan":
            body = eqn.params["jaxpr"]
            sub = _analyze(body.jaxpr)
            length = int(eqn.params["length"])
            nc = int(eqn.params["num_consts"])
            num_carry = int(eqn.params["num_carry"])
            carry_avals = [v.aval for v in
                           body.jaxpr.invars[nc:nc + num_carry]]
            here = _Cost()
            here.add_scaled(sub, length)
            here.carry_bytes += sum(_nbytes(a) for a in carry_avals)
            here.carry_shapes.extend(
                f"{tuple(a.shape)}:{a.dtype}" for a in carry_avals)
            cost.add_scaled(here, 1)
            nested_peak = sub.peak_bytes
        elif p == "cond":
            subs = [_analyze(b.jaxpr) for b in eqn.params["branches"]]
            cost.flops += max((s.flops for s in subs), default=0)
            cost.dot_flops += max((s.dot_flops for s in subs), default=0)
            cost.bytes_moved += max((s.bytes_moved for s in subs), default=0)
            for s in subs:
                cost.carry_bytes += s.carry_bytes
                cost.carry_shapes.extend(s.carry_shapes)
                cost.leaks.extend(s.leaks)
                cost.unknown |= s.unknown
            nested_peak = max((s.peak_bytes for s in subs), default=0)
        elif p == "while":
            subs = [_analyze(eqn.params["cond_jaxpr"].jaxpr),
                    _analyze(eqn.params["body_jaxpr"].jaxpr)]
            for s in subs:
                cost.add_scaled(s, 1)
            cost.unknown.add("while(unbounded-trips)")
            nested_peak = max(s.peak_bytes for s in subs)
        else:
            inner = None
            for key in ("jaxpr", "call_jaxpr"):
                inner = _closed(eqn.params.get(key)) if eqn.params else None
                if inner is not None:
                    break
            if inner is not None:
                sub = _analyze(inner.jaxpr
                               if hasattr(inner, "jaxpr") else inner)
                cost.add_scaled(sub, 1)
                nested_peak = sub.peak_bytes
            else:
                cost.flops += _eqn_flops(eqn, cost)
                cost.bytes_moved += sum(
                    _nbytes(v.aval) for v in eqn.invars
                    if hasattr(v, "aval"))
                cost.bytes_moved += sum(
                    _nbytes(v.aval) for v in eqn.outvars
                    if hasattr(v, "aval"))

        # liveness update: results become live, then anything last read
        # here (or never read) dies
        for v in eqn.outvars:
            if _is_var(v):
                b = _nbytes(v.aval)
                live[v] = b
                live_bytes += b
        cost.peak_bytes = max(cost.peak_bytes, live_bytes + nested_peak)
        for v in list(eqn.invars) + list(eqn.outvars):
            if _is_var(v) and v in live and last_use.get(v, -1) <= i:
                live_bytes -= live.pop(v)

    return cost


# -- per-kernel reports -----------------------------------------------------

@dataclass
class KernelReport:
    kernel: str
    dims: dict
    flops: int
    dot_flops: int
    bytes_moved: int
    peak_bytes: int
    carry_bytes: int
    carry_shapes: list[str]
    carry_exponent: float
    dtype_leaks: list[str]
    unknown_prims: list[str]

    def to_dict(self) -> dict:
        return {
            "kernel": self.kernel, "dims": dict(self.dims),
            "flops": self.flops, "dot_flops": self.dot_flops,
            "bytes_moved": self.bytes_moved, "peak_bytes": self.peak_bytes,
            "carry_bytes": self.carry_bytes,
            "carry_shapes": list(self.carry_shapes),
            "carry_exponent": self.carry_exponent,
            "dtype_leaks": list(self.dtype_leaks),
            "unknown_prims": sorted(self.unknown_prims),
        }


def _trace_cost(fn, specs) -> _Cost:
    import jax
    closed = jax.make_jaxpr(fn)(*specs)
    inner = closed
    # a jitted fn traces to a single jit equation wrapping the real body
    if len(closed.jaxpr.eqns) == 1 \
            and closed.jaxpr.eqns[0].primitive.name == "jit":
        inner = closed.jaxpr.eqns[0].params["jaxpr"]
    return _analyze(inner.jaxpr)


def analyze_kernel(kernel: str, fn=None, **dims) -> KernelReport:
    """Trace one cached kernel at the reference bucket (override via
    ``dims``) and fit its carry exponent against a doubled-``n`` trace."""
    from repro.core.simulator import jax_kernels, kernel_abstract_inputs
    if fn is None:
        fn = jax_kernels()[kernel]
    use = dict(_REF_DIMS)
    use.update(dims)
    cost = _trace_cost(fn, kernel_abstract_inputs(kernel, **use))
    use2 = dict(use)
    use2["n"] = 2 * use["n"]
    cost2 = _trace_cost(fn, kernel_abstract_inputs(kernel, **use2))
    if cost.carry_bytes > 0 and cost2.carry_bytes > 0:
        exponent = math.log2(cost2.carry_bytes / cost.carry_bytes)
    else:
        exponent = 0.0
    return KernelReport(
        kernel=kernel, dims=use,
        flops=cost.flops, dot_flops=cost.dot_flops,
        bytes_moved=cost.bytes_moved, peak_bytes=cost.peak_bytes,
        carry_bytes=cost.carry_bytes, carry_shapes=cost.carry_shapes,
        carry_exponent=round(exponent, 4),
        dtype_leaks=cost.leaks, unknown_prims=sorted(cost.unknown))


def analyze_all(kernels: list[str] | None = None) -> list[KernelReport]:
    from repro.core.simulator import jax_kernels
    fns = jax_kernels()
    names = kernels if kernels is not None else sorted(fns)
    return [analyze_kernel(k, fns[k]) for k in names]


# -- budget gate ------------------------------------------------------------

def load_budget(path: str = DEFAULT_BUDGET) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_budget(reports: list[KernelReport],
                 path: str = DEFAULT_BUDGET, slack: float = 0.01) -> dict:
    """Freeze the current measurements.  The carry-exponent ceiling gets
    +0.15 headroom over the fitted value (quantization of the pad-to
    buckets makes the fit slightly inexact), everything else relies on the
    shared relative ``slack``."""
    data = {
        "version": 1,
        "reference": {**_REF_DIMS, "n2": _REF_N2},
        "slack": slack,
        "kernels": {
            r.kernel: {
                "flops": r.flops,
                "dot_flops": r.dot_flops,
                "bytes_moved": r.bytes_moved,
                "peak_bytes": r.peak_bytes,
                "carry_bytes": r.carry_bytes,
                "carry_exponent_max": round(r.carry_exponent + 0.15, 2),
                "dtype_leaks": len(r.dtype_leaks),
            } for r in reports
        },
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1)
        f.write("\n")
    return data


def check_budget(reports: list[KernelReport], budget: dict) -> list[str]:
    """Lint-style violation lines; empty means every kernel is within
    budget.  IR1 = footprint/op-count regression, IR2 = carry scaling,
    IR3 = dtype leaks, IR0 = a cached kernel the budget has never seen."""
    slack = float(budget.get("slack", 0.0))
    out: list[str] = []
    for r in reports:
        b = budget.get("kernels", {}).get(r.kernel)
        if b is None:
            out.append(f"{r.kernel}: IR0[budget] kernel has no entry in "
                       "ir_budget.json (run --write-budget to freeze it)")
            continue
        for metric in ("flops", "bytes_moved", "peak_bytes", "carry_bytes"):
            got, ref = getattr(r, metric), int(b[metric])
            if got > ref * (1.0 + slack):
                out.append(
                    f"{r.kernel}: IR1[{metric}] {got} exceeds budget "
                    f"{ref} (+{slack:.0%} slack) — kernel footprint "
                    "regressed; fix it or refreeze with --write-budget")
        if r.carry_exponent > float(b["carry_exponent_max"]):
            out.append(
                f"{r.kernel}: IR2[carry] scan-carry n-exponent "
                f"{r.carry_exponent:.2f} exceeds the budget ceiling "
                f"{b['carry_exponent_max']} — the carry grew a fabric "
                "dimension (the IR-level dense-alloc rule)")
        if len(r.dtype_leaks) > int(b["dtype_leaks"]):
            out.append(
                f"{r.kernel}: IR3[dtype] {len(r.dtype_leaks)} dtype leaks "
                f"(budget {b['dtype_leaks']}): "
                + ", ".join(sorted(set(r.dtype_leaks))))
    return out


def _fmt_bytes(b: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if b < 1024 or unit == "GiB":
            return f"{b:.1f}{unit}" if unit != "B" else f"{b}B"
        b /= 1024
    return f"{b}B"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.ir",
        description="Static jaxpr analysis of the cached scan kernels.")
    ap.add_argument("--kernel", action="append", default=None,
                    help="restrict to this kernel (repeatable)")
    ap.add_argument("--budget", default=DEFAULT_BUDGET,
                    help="budget file (default: the checked-in one)")
    ap.add_argument("--write-budget", action="store_true",
                    help="refreeze the budget from current measurements")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump the full report (+violations) as JSON")
    args = ap.parse_args(argv)

    try:
        import jax  # noqa: F401
    except ImportError:
        print("repro.analysis.ir requires jax (kernels cannot be traced "
              "without it)")
        return 3

    reports = analyze_all(args.kernel)
    for r in reports:
        print(f"{r.kernel}: flops={r.flops} dot={r.dot_flops} "
              f"moved={_fmt_bytes(r.bytes_moved)} "
              f"peak={_fmt_bytes(r.peak_bytes)} "
              f"carry={_fmt_bytes(r.carry_bytes)} "
              f"(~n^{r.carry_exponent:.2f}) "
              f"leaks={len(r.dtype_leaks)}")
        for s in r.carry_shapes:
            print(f"    carry {s}")
        if r.unknown_prims:
            print(f"    unmodeled primitives: {', '.join(r.unknown_prims)}")

    if args.write_budget:
        data = write_budget(reports, args.budget)
        print(f"wrote budgets for {len(data['kernels'])} kernels "
              f"to {args.budget}")
        return 0

    if not os.path.exists(args.budget):
        print(f"\nno budget at {args.budget} — run --write-budget first")
        return 2
    violations = check_budget(reports, load_budget(args.budget))

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump({"reports": [r.to_dict() for r in reports],
                       "violations": violations}, f, indent=1)
            f.write("\n")

    for v in violations:
        print(v)
    if violations:
        print(f"\n{len(violations)} IR budget violation(s)")
        return 1
    print(f"\nall {len(reports)} kernels within ir_budget.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
