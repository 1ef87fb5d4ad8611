"""Least work of the ``twohop_sparse`` kernel: two-hop aggregate serving
(rotorlb, vlb) on the padded circuit-support formulation the program picks
above ``_TWOHOP_DENSE_MAX_N`` racks.

It solves the same problem as ``twohop_dense``, so its least work is the
same count, imported from there: the roofline reads the work the problem
needs whatever implements it.  The fixed-degree plan tables (per plan,
each row's D out-circuits with their destinations and capacities and its
D in-circuits), their padding to D slots a row and to a power-of-two
plan count are the implementation's, not the problem's.
"""
from __future__ import annotations

from .twohop_dense import count

BATCH = "twohop"

__all__ = ["BATCH", "count"]
