"""A 2-D map whose sun moves every frame. Each step hands the sun's band of
rows, at the sun's next ring position, to ``Map2DSampler.update_map``
(``bench.update``), then draws one frame through ``sample_map`` with host
points in and host (row, col) out (``bench.drain``), as ``map2d`` draws it.

The band's rows at every ring position are made at set-up, and set-up runs
the whole ring once, so that every shape and degenerate flag the window
meets has compiled before it opens."""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import numpy as np

from bench.reference import map2d as ref
from bench.systems import map2d, span
from bench.work import update2d


class System(map2d.System):
    def __init__(self, cfg, traffic, seed, data, control=False):
        ring, width = int(traffic["ring"]), int(cfg["width"])
        cols = [(int(cfg["moving_sun_col0"]) + int(traffic["sun_step_px"]) * k)
                % width for k in range(ring)]
        self.lo, self.rows = data.band_start(cfg), int(cfg["band_rows"])
        fixed = data.make(cfg, seed)
        bands = data.bands(cfg, fixed, cols)
        img = fixed.at[self.lo:self.lo + self.rows].set(bands[0])
        del fixed
        self.bands = np.asarray(bands, np.float64)
        self.img_dev, self.bands_dev = (img, bands) if control else (None, None)
        # map2d's set-up, over the map with the sun at ring position 0
        super().__init__(cfg, traffic, seed,
                         SimpleNamespace(make=lambda *_: img), control)
        del img, bands
        if not control:
            self.work["update"] = update2d.bytes_per_update(
                self.rows, width, int(cfg["height"]))
        for s in range(self.ring):   # set-up: every state the window meets
            self.step(s)
        self.last = None

    def _update(self, pos: int) -> None:
        lo, hi = self.lo, self.lo + self.rows
        if self.control:
            self.img_dev = self.img_dev.at[lo:hi].set(self.bands_dev[pos])
            self.state = jax.block_until_ready(
                ref.control_tables(self.img_dev))
            return
        self.state.update_map({lo + i: self.bands[pos, i]
                               for i in range(self.rows)})
        jax.block_until_ready([(c.forest, c.cdf_rows)
                               for c in self.state.classes.values()])

    def step(self, s: int):
        k, pos = s % self.ring, (s + 1) % self.ring
        with span("bench.update"):
            t0 = time.perf_counter()
            self._update(pos)
            update_s = time.perf_counter() - t0
        with span("bench.drain"):
            row, col = self._sample(self.points[k])
        self.last = (k, pos, row, col)
        return update_s

    def check(self, kept: list) -> dict:
        """Widest marginal and conditional gaps against the float64
        reference of the map as it stood at each kept frame's position."""
        row_gap = col_gap = 0.0
        for pos in sorted({item[1] for item in kept}):
            img = self.img.copy()
            img[self.lo:self.lo + self.rows] = self.bands[pos]
            tables = ref.Tables(img)
            for k, p, row, col in kept:
                if p == pos:
                    pts = self.points[k]
                    r, c = tables.gaps(pts[:, 0], pts[:, 1], row, col)
                    row_gap, col_gap = max(row_gap, r), max(col_gap, c)
        return {"row_gap": row_gap, "col_gap": col_gap}

    def close(self):
        super().close()
        self.img_dev = self.bands_dev = None
