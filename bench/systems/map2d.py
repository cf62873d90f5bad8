"""A 2-D map: ``repro.spatial.Map2DSampler`` built over the map, and
``sample_map`` called as a renderer calls it, with host points in and
host (row, col) out. Every step is one frame of points."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import repro.core
from repro.core.cdf import build_cdf
from repro.core.forest2d import build_forest_rows
from repro.spatial import Map2DSampler

from bench import traffic as T
from bench.reference import map2d as ref
from bench.systems import span
from bench.work import drain, loads

LOAD_SAMPLE = 1 << 10   # draws whose conditional forests are built to count


class System:
    def __init__(self, cfg, traffic, seed, data, control=False):
        if traffic["points"] != "qmc2d":
            raise ValueError(f"this system takes qmc2d points, not "
                             f"{traffic['points']!r}")
        self.ring = int(traffic["ring"])
        self.draws = int(traffic["draws_per_step"])
        self.control = control
        img = data.make(cfg, seed)
        self.img = np.asarray(img, np.float64)
        self.points = T.qmc2d_ring(seed, self.ring, self.draws)
        if control:
            self.state = jax.block_until_ready(ref.control_tables(img))
        else:
            self.state = Map2DSampler(self.img)
        del img
        self._sample(self.points[0])  # warm up the one shape a frame uses
        self.work = None if control else self._work()
        self.last = None

    def _sample(self, pts):
        if self.control:
            row, col = ref.control_sample(self.state, jnp.asarray(pts[:, 0]),
                                          jnp.asarray(pts[:, 1]))
            return np.asarray(row), np.asarray(col)
        row, col, _, _ = self.state.sample_map(pts)
        return row, col

    def step(self, s: int):
        k = s % self.ring
        with span("bench.drain"):
            row, col = self._sample(self.points[k])
        self.last = (k, row, col)
        return None

    def kept(self):
        return self.last

    def _work(self) -> dict:
        """Bytes per frame from the load model: the marginal forest, and the
        conditional forests of the rows a seeded sample of draws lands in."""
        H, W = self.img.shape
        pts = self.points[0][:LOAD_SAMPLE]
        rows = self.img.sum(axis=1)
        marg = repro.core.build_forest(jnp.asarray(rows / rows.sum(),
                                                   jnp.float32), H)
        cdf = np.asarray(marg.cdf)
        row, marg_loads = loads.node_loads(
            cdf[:-1], np.asarray(marg.table), np.asarray(marg.left),
            np.asarray(marg.right), pts[:, 0])
        picked = self.img[row]
        cdf_rows = jax.vmap(build_cdf)(
            jnp.asarray(picked / picked.sum(axis=1, keepdims=True), jnp.float32))
        rf = build_forest_rows(cdf_rows, m=W)
        _, cond_loads = loads.node_loads(
            np.asarray(rf.data), np.asarray(rf.table), np.asarray(rf.left),
            np.asarray(rf.right), pts[:, 1],
            cell_base=np.arange(len(pts)) * W, m=W)
        self.mean_node_loads = (float(marg_loads.mean()),
                                float(cond_loads.mean()))
        return {"drain": self.draws * drain.bytes_per_draw(
            *self.mean_node_loads), "build": 0}

    def check(self, kept: list) -> dict:
        """Widest marginal and conditional gaps against the float64
        reference over the kept frames."""
        tables = ref.Tables(self.img)
        row_gap = col_gap = 0.0
        for k, row, col in kept:
            pts = self.points[k]
            r, c = tables.gaps(pts[:, 0], pts[:, 1], row, col)
            row_gap, col_gap = max(row_gap, r), max(col_gap, c)
        return {"row_gap": row_gap, "col_gap": col_gap}

    def close(self):
        self.state = self.last = None
