"""Least work of two-hop aggregate serving (rotorlb, vlb), the problem the
``twohop_dense`` kernel solves, counted from the problem's shapes alone.

Per case and slot, each of the at most ``n * d_hat`` circuits (u, v)
drains relay bucket (u, v), serves the direct queue (u, v) and computes
its link share of u's leftover capacity (6 operations), and then sprays
that share of each of u's ``n`` destination queues into v's relay
buckets: a multiply and an add per destination, ``2 n`` operations.  Per
slot the kernel writes two numbers per case (bits delivered, bits on a
second hop); each arriving flow is read once (pair and size, 8 bytes) and
added once.  That is the work over the circuit support: a formulation
that touches every (u, v, d) triple, as the dense einsum does with
``n^3``, does more than this count and reads as a lower share.
"""
from __future__ import annotations

BATCH = "twohop"


def count(cases: list) -> tuple[float, float]:
    """(flops, bytes) for a batch; each case a dict with ``n``, ``d_hat``,
    ``horizon`` and ``flows`` (flows arriving inside the horizon)."""
    flops = nbytes = 0.0
    for c in cases:
        circuit_slots = c["n"] * c["d_hat"] * c["horizon"]
        flops += (2 * c["n"] + 6) * circuit_slots + c["flows"]
        nbytes += 8 * c["horizon"] + 8 * c["flows"]
    return flops, nbytes
