"""A run whose timed path is broken underneath comes out not correct.

Each cell drives the rest of a run (set-up, window, check) on the CPU at a
tiny size with one fault planted in the program: a scan step that returns
its state unchanged (nothing served), half of the batch left out (the
other half's rows returned in its place), or the delivered bits altered
where the kernel produces them.  The cells run on one chip, so there is
no exchange between chips to leave out.
"""
import dataclasses

import numpy as np
import pytest

from fabric_bench import runner
from fabric_bench.tests import tiny

sim = pytest.importorskip("repro.core.simulator")

KERNEL = {"ws256_singlehop": "singlehop", "ws256_twohop": "twohop_dense"}


def _outputs_fault(monkeypatch, kernel: str, change):
    """Replace ``kernel``'s per-slot outputs by ``change(outputs)``."""
    fns = sim._jax_fns()
    real = fns[kernel]

    def broken(*args):
        if kernel == "singlehop":
            voq, (tx, drained) = real(*args)
            return voq, change((tx, drained))
        out, carry = real(*args)
        return change(out), carry

    monkeypatch.setitem(fns, kernel, broken)


def state_unchanged(monkeypatch, name):
    _outputs_fault(monkeypatch, KERNEL[name],
                   lambda out: tuple(np.zeros_like(np.asarray(o))
                                     for o in out))


def answer_altered(monkeypatch, name):
    def change(out):
        first = np.asarray(out[0]).copy()
        first[len(first) // 2] *= 1.5         # one slot's delivered bits
        return (first,) + tuple(out[1:])
    _outputs_fault(monkeypatch, KERNEL[name], change)


def half_batch(monkeypatch, name):
    real = sim.run_sweep

    def broken(cases, *args, **kw):
        h = max(len(cases) // 2, 1)
        rows = real(cases[:h], *args, **kw)
        return rows + [dataclasses.replace(rows[i % h], label=c.label)
                       for i, c in enumerate(cases[h:])]

    monkeypatch.setattr(sim, "run_sweep", broken)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   answer_altered])
@pytest.mark.parametrize("name", sorted(KERNEL))
def test_fault_is_not_correct(monkeypatch, name, fault):
    cell = tiny.cell(name)
    fault(monkeypatch, name)
    ctx, _ = runner.measure(cell, 2**32 + 3, 0.0, False, "cpu", 0.0,
                            log=lambda *a: None)
    checked = runner.check(cell, ctx.window, 2**32 + 3)
    assert not runner.is_correct(checked), checked
