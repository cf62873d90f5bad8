"""Update layer of a 2-D map: bytes the algorithm moves per ``update_map``
of a band of rows, from shapes.

The band's rows are rebuilt as forests of their width (``build``), the
marginal over the map's rows is rebuilt once, and each built row is read
and written into the class stack: its CDF twice (the stack's skip key and
the forest's own), its children, guide table, ``cell_first`` and flags."""
from __future__ import annotations

from bench.work import build


def scatter_bytes_per_row(width: int, m: int) -> int:
    cdfs, left, right = 2 * 4 * (width + 1), 4 * width, 4 * width
    table, cell_first, fallback = 4 * m, 4 * (m + 1), m
    return 2 * (cdfs + left + right + table + cell_first + fallback)


def bytes_per_update(rows: int, width: int, height: int) -> int:
    """``rows`` rows of ``width`` texels rebuilt (one guide cell per texel)
    in a map of ``height`` rows (one marginal cell per row)."""
    return (rows * build.bytes_per_build(width, width)
            + build.bytes_per_build(height, height)
            + rows * scatter_bytes_per_row(width, width))
