"""Least work of the ``singlehop`` kernel: serving one batch of single-hop
cases, counted from the problem's shapes alone.

Per case, each of the ``horizon`` slots serves at most ``n * d_hat``
circuits: a ``min`` and a subtraction per circuit, and the kernel's output
per circuit and slot, the bits sent (4 bytes) and whether the queue
drained (1 byte), which the host's flow replay reads.  Each arriving flow
adds its size to its queue: one addition, and its pair and size read
(8 bytes).  Queue state that stays on the chip, the padding of the
horizon, of arrivals per slot and of the circuit plan, and the
capacities of a periodic plan are not counted: they are the
implementation's, not the problem's.
"""
from __future__ import annotations

BATCH = "singlehop"


def count(cases: list) -> tuple[float, float]:
    """(flops, bytes) for a batch; each case a dict with ``n``, ``d_hat``,
    ``horizon`` and ``flows`` (flows arriving inside the horizon)."""
    flops = nbytes = 0.0
    for c in cases:
        circuit_slots = c["n"] * c["d_hat"] * c["horizon"]
        flops += 2 * circuit_slots + c["flows"]
        nbytes += 5 * circuit_slots + 8 * c["flows"]
    return flops, nbytes
