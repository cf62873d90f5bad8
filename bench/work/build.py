"""Build layer: bytes the algorithm moves per forest build, from shapes.

Weights read (4 B each); CDF, left and right children written (4 B per
interval, the CDF one longer); guide table and ``cell_first`` written (4 B
per cell, ``cell_first`` one longer) and the ``fallback`` flags (1 B per
cell)."""
from __future__ import annotations


def bytes_per_build(n: int, m: int) -> int:
    weights = 4 * n
    cdf, left, right = 4 * (n + 1), 4 * n, 4 * n
    table, cell_first, fallback = 4 * m, 4 * (m + 1), m
    return weights + cdf + left + right + table + cell_first + fallback
