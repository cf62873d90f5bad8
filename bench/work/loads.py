"""The paper's Table-1 load model for Algorithm 2 (a copy of the counting
walk in ``repro.core.counting``, kept with the benchmark as its yardstick).

One guide-table load per draw, then one node load per radix-tree node
visited (split value and both children, interleaved); a tagged cell (a
single overlapping interval) costs no node load. Node ``j`` splits at
``split[j]``, the lower bound of interval ``j``; references ``< 0`` are
leaves ``~i``.
"""
from __future__ import annotations

import numpy as np


def node_loads(split, table, left, right, xi, cell_base=0, m=None):
    """Per-draw node loads and leaves of Algorithm 2.

    ``table`` is the guide table (flat; draw ``q`` reads entry
    ``cell_base[q] + floor(xi[q] * m)``), ``split``/``left``/``right`` the
    node arrays indexed by the references the table and children hold.
    Returns ``(leaf, loads)``, both int64 arrays over the draws."""
    xi = np.asarray(xi, np.float32)
    m = len(table) if m is None else m
    g = np.clip(np.floor(xi * np.float32(m)).astype(np.int64), 0, m - 1)
    j = np.asarray(table)[np.asarray(cell_base, np.int64) + g].astype(np.int64)
    split = np.asarray(split)
    left = np.asarray(left)
    right = np.asarray(right)
    loads = np.zeros(len(xi), np.int64)
    for _ in range(4096):
        live = j >= 0
        if not live.any():
            return ~j, loads
        jj = np.where(live, j, 0)
        nxt = np.where(xi < split[jj], left[jj], right[jj])
        loads += live
        j = np.where(live, nxt, j)
    raise RuntimeError("radix-tree walk did not terminate")
