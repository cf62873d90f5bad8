"""Drain layer: bytes the algorithm moves per draw (Algorithm 2).

Per dimension: the uniform read (4 B), one guide-table load (4 B), 12 B
per node load (split value and two children, interleaved as the paper's
model counts them) and the index written (4 B)."""
from __future__ import annotations

UNIFORM, GUIDE, NODE, INDEX = 4, 4, 12, 4


def bytes_per_draw(*mean_node_loads: float) -> float:
    """Bytes of one draw that descends one forest per given mean node-load
    count (one argument for a 1-D draw, two for a 2-D draw)."""
    return sum(UNIFORM + GUIDE + NODE * loads + INDEX
               for loads in mean_node_loads)
