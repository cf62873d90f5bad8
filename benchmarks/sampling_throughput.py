"""Sampling throughput (us/call over batches): forest traversal vs binary
search vs cutpoint+binary vs alias, in both pure-XLA and Pallas-interpret
forms. The paper's Table-1 'average_32' models exactly the vector-lane
lock-step this batch timing measures on real hardware.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    build_alias,
    build_forest,
    sample_alias,
    sample_binary,
    sample_cutpoint_binary,
    sample_forest,
)
from repro.core.cdf import normalize_weights
from repro.kernels import ops


def _time(fn, reps: int = 10) -> float:
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(n: int = 1 << 16, m: int | None = None, batch: int = 1 << 16):
    m = m or n
    rng = np.random.default_rng(0)
    w = normalize_weights(rng.random(n) ** 12 + 1e-12)
    f = build_forest(jnp.asarray(w), m)
    at = build_alias(w)
    xi = jnp.asarray(rng.random(batch), jnp.float32)

    sb = jax.jit(lambda u: sample_binary(f.cdf, u))
    scb = jax.jit(lambda u: sample_cutpoint_binary(f.cdf, f.cell_first, u))
    sf = jax.jit(lambda u: sample_forest(f, u))
    sa = jax.jit(lambda u: sample_alias(at, u))

    rows = [
        ("binary_search", _time(lambda: sb(xi))),
        ("cutpoint_binary", _time(lambda: scb(xi))),
        ("forest_alg2", _time(lambda: sf(xi))),
        ("alias", _time(lambda: sa(xi))),
        ("forest_pallas_interpret",
         _time(lambda: ops.forest_sample(f, xi, use_pallas=True), reps=3)),
    ]
    return [(name, us, batch / us) for name, us in rows]


def run_sharded(n: int = 1 << 16, batch: int = 1 << 16):
    """Sampling over the cell-partitioned *windowed* forest across
    fake-device counts (repro.dist.forest.sample_sharded), both paths:

      * ``forest_sharded_d{D}``        — replicated masked-psum oracle
        (every shard descends the full batch; kept as the reference).
      * ``forest_sharded_routed_d{D}`` — owner-routed all-to-all bulk
        drain; each shard descends only its capacity-padded ~B/D bucket.

    Each row reports the static per-device leaf window the descent runs
    over; routed rows additionally report the per-(src,dst) bucket
    capacity — the descent lane count is D*bucket per shard, vs the full
    padded batch on the oracle. Full sweep needs
    XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    from jax.sharding import Mesh

    from repro.dist import forest as DF

    rng = np.random.default_rng(0)
    w = normalize_weights(rng.random(n) ** 12 + 1e-12)
    xi = jnp.asarray(rng.random(batch), jnp.float32)
    devices = jax.devices()
    rows = []
    for D in (c for c in (1, 2, 4, 8) if c <= len(devices)):
        mesh = Mesh(np.asarray(devices[:D]), ("data",))
        sf = DF.build_forest_sharded(jnp.asarray(w), n, mesh=mesh)
        us = _time(
            lambda: DF.sample_sharded(sf, xi, mesh=mesh, routed=False), reps=5
        )
        rows.append(
            {
                "name": f"forest_sharded_d{D}", "us": us, "mps": batch / us,
                "window": sf.capacity,
            }
        )
        plan = DF.drain_plan(sf, xi, mesh=mesh)
        us = _time(
            lambda: DF.sample_sharded(sf, xi, mesh=mesh, routed=True), reps=5
        )
        rows.append(
            {
                "name": f"forest_sharded_routed_d{D}", "us": us,
                "mps": batch / us, "window": sf.capacity,
                "bucket": plan["bucket_capacity"],
            }
        )
    return rows


def main() -> list[str]:
    lines = [
        f"throughput,{name},us_per_call={us:.0f},Msamples_s={mps:.2f}"
        for name, us, mps in run()
    ]
    for r in run_sharded():
        line = (
            f"throughput,{r['name']},us_per_call={r['us']:.0f},"
            f"Msamples_s={r['mps']:.2f},window={r['window']}"
        )
        if "bucket" in r:
            line += f",bucket={r['bucket']}"
        lines.append(line)
    return lines


if __name__ == "__main__":
    print("\n".join(main()))
