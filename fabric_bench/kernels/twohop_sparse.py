"""Least work of the ``twohop_sparse`` kernel: two-hop aggregate serving
(rotorlb, vlb) on the padded circuit-support formulation the program picks
above ``_TWOHOP_DENSE_MAX_N`` racks.

It solves the same problem as ``twohop_dense``, so its least work is the
same count, imported from there: the roofline reads the work the problem
needs whatever implements it.  The support lookup table, its padding to
``_PAD_J`` entries and a power-of-two plan count, and the dense capacity
table the kernel gathers from are the implementation's, not the
problem's.
"""
from __future__ import annotations

from .twohop_dense import count

BATCH = "twohop"

__all__ = ["BATCH", "count"]
