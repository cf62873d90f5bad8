"""One giant distribution: ``repro.core.build_forest`` builds the forest,
``repro.kernels.ops.forest_sample`` drains it.

Without ``reweight_sigma`` in the traffic the forest is built once at
set-up and every step drains one batch of uniforms. With it, every step
first hands in new weights (an update: build, plus the host read of
``fallback.any()`` that tells the drain whether degenerate cells exist),
then drains one batch from the new forest."""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

import repro.core
from repro.kernels import ops

from bench import traffic as T
from bench.reference import forest1d as ref
from bench.systems import span
from bench.work import build, drain, loads

LOAD_SAMPLE = 1 << 16


@functools.partial(jax.jit, static_argnames=("degenerate",))
def bench_drain(forest, xi, degenerate):
    return ops.forest_sample(forest, xi, degenerate=degenerate)


class System:
    def __init__(self, cfg, traffic, seed, data, control=False):
        if traffic["points"] != "prng":
            raise ValueError(f"this system takes prng points, not "
                             f"{traffic['points']!r}")
        self.n, self.m = int(cfg["vocab_size"]), int(cfg["guide_cells"])
        self.ring = int(traffic["ring"])
        self.draws = int(traffic["draws_per_step"])
        self.control = control
        self.reweight = "reweight_sigma" in traffic
        self.weights = T.drift_ring(
            data.make(cfg, seed), seed, self.ring if self.reweight else 1,
            traffic.get("reweight_sigma", 0.0), cfg["power"])
        self.xi = T.uniform_ring(seed, self.ring, self.draws)
        self.state = self._update(self.weights[0])
        # warm up: both drain programs where the degenerate flag can change
        for degenerate in ({False, True} if self.reweight else {self.state[1]}):
            self._drain(self.state[0], self.xi[0], degenerate).block_until_ready()
        self.work = None if control else self._work()
        self.last = None

    # -- the system under test (or the control in its place) ---------------
    def _update(self, weights):
        if self.control:
            return jax.block_until_ready(ref.control_cdf(weights)), False
        forest = repro.core.build_forest(weights, self.m)
        degenerate = bool(forest.fallback.any())
        return jax.block_until_ready(forest), degenerate

    def _drain(self, state, xi, degenerate):
        if self.control:
            return ref.control_sample(state, xi)
        return bench_drain(state, xi, degenerate)

    def step(self, s: int):
        k = s % self.ring
        update_s = None
        if self.reweight:
            with span("bench.build"):
                t0 = time.perf_counter()
                self.state = self._update(self.weights[k])
                update_s = time.perf_counter() - t0
        state, degenerate = self.state
        with span("bench.drain"):
            idx = self._drain(state, self.xi[k], degenerate)
            idx.block_until_ready()
        self.last = (k, idx, state if self.control else state.cdf)
        return update_s

    def kept(self):
        return self.last

    # -- yardstick ---------------------------------------------------------
    def _work(self) -> dict:
        """Bytes per call from the load model on the set-up forest."""
        f = self.state[0]
        cdf = np.asarray(f.cdf)
        _, node = loads.node_loads(cdf[:-1], np.asarray(f.table),
                                   np.asarray(f.left), np.asarray(f.right),
                                   np.asarray(self.xi[0][:LOAD_SAMPLE]))
        self.mean_node_loads = mean = float(node.mean())
        return {"drain": self.draws * drain.bytes_per_draw(mean),
                "build": build.bytes_per_build(self.n, self.m)
                if self.reweight else 0}

    def check(self, kept: list) -> dict:
        """Widest gaps against the float64 reference over the kept steps."""
        cdf_gap = draw_gap = 0.0
        cdfs = {}
        for k, idx, cdf_prog in kept:
            w = k if self.reweight else 0
            if w not in cdfs:
                cdfs[w] = ref.cdf(np.asarray(self.weights[w]))
            cdf64 = cdfs[w]
            cdf_gap = max(cdf_gap, ref.cdf_gap(np.asarray(cdf_prog), cdf64))
            draw_gap = max(draw_gap, float(ref.interval_gap(
                cdf64, np.asarray(self.xi[k]), np.asarray(idx)).max()))
        return {"cdf_gap": cdf_gap, "draw_gap": draw_gap}

    def close(self):
        self.state = self.last = None
