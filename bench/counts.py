"""The multiplication model of a GGR row-append sweep.

A copy of ``repro.core.counts.ggr_append_mults`` (and ``mults_to_flops``),
kept here so that the yardstick cannot move with the program.
``tests/bench/test_bench_counts.py`` holds the copy equal to the original.
Each client kind (``bench/clients/<kind>.py``) states its requests' work
with it in ``work(cfg)``.
"""
from __future__ import annotations


def ggr_append_mults(n: int, p: int, w: int) -> int:
    """Multiplications of one compact row-append GGR sweep: an (n, n)
    upper-triangular R with p appended rows and total width w >= n.  Column
    step c rotates the pivot row against the p rows over ``w - c`` columns."""
    steps = max(0, min(n, w))
    return sum(3 * ((p + 1) * (w - c) - 1) for c in range(steps))


def mults_to_flops(mults: int) -> int:
    """Each counted multiplication pairs with one add (FMA-shaped grids)."""
    return 2 * int(mults)
