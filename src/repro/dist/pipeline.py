"""GPipe microbatch pipeline over ``lax.ppermute`` (paper-era classic).

``gpipe(block, mesh, axis)`` turns a per-layer ``block(W, h) -> h`` into a
pipelined ``f(Ws, xs)`` where ``Ws`` stacks the L layer params on axis 0 and
``xs`` stacks M microbatches on axis 0. The mesh axis ``axis`` (size S)
carries the pipeline: each stage owns L/S consecutive layers (``shard_map``
splits ``Ws``), microbatches stream through the stages, and stage boundaries
are a single ring ``ppermute`` per tick.

Schedule: T = M + S - 1 ticks; at tick ``t`` stage ``s`` runs microbatch
``t - s`` through its local layers (bubble fraction (S-1)/T, the GPipe
figure). Stage 0 ingests ``xs[t]``; the last stage accumulates its output
into slot ``t - (S-1)``; a final ``psum`` over the pipeline axis replicates
the result (only the last stage contributes non-zeros, so the sum is exact).

Guarantees (asserted by ``test_gpipe_matches_sequential``):

* **Matches sequential execution exactly** — every microbatch sees the same
  per-layer op sequence as a plain loop; no re-ordering, no rescaling.
* **Differentiable** — ``ppermute``/``psum``/``where`` all have transposes,
  so ``jax.grad`` flows through the schedule (backward runs the reverse
  permutes — the classic GPipe backward bubble).

Mesh axes not named ``axis`` are left unmentioned in the ``shard_map`` specs
(replicated), so a (pod, data) mesh pipelines over pods while data
parallelism proceeds untouched inside each stage.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def gpipe(block, mesh, axis: str):
    """Build the pipelined callable. ``block(W, h) -> h`` must be shape
    preserving; ``Ws.shape[0]`` must be divisible by ``mesh.shape[axis]``."""
    S = int(mesh.shape[axis])
    # Outside a mesh context, trace over Auto axes: an Explicit-axes mesh
    # types every aval by it, and a caller's jit would then need the context.
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    ring = [(i, (i + 1) % S) for i in range(S)]

    def stage(ws, xs):
        # ws: (L/S, ...) this stage's layers; xs: (M, mb, D) full stream.
        M = xs.shape[0]
        idx = jax.lax.axis_index(axis)

        def local(h):
            return jax.lax.scan(lambda c, W: (block(W, c), None), h, ws)[0]

        out = jnp.zeros_like(xs)
        carry = jnp.zeros(xs.shape[1:], xs.dtype)
        for t in range(M + S - 1):
            inp = jnp.where(idx == 0, xs[min(t, M - 1)], carry)
            y = local(inp)
            carry = jax.lax.ppermute(y, axis, ring)
            j = t - (S - 1)
            if 0 <= j < M:  # last stage finished microbatch j this tick
                out = out.at[j].add(jnp.where(idx == S - 1, y, jnp.zeros_like(y)))
        # Only stage S-1 wrote non-zeros -> psum replicates exactly.
        return jax.lax.psum(out, axis)

    def pipelined(Ws, xs):
        L = Ws.shape[0]
        if L % S != 0:
            raise ValueError(f"layers ({L}) must divide over pipeline axis ({S})")
        in_context = not jax.sharding.get_abstract_mesh().empty
        return shard_map(
            stage,
            mesh=mesh if in_context else auto,
            in_specs=(P(axis), P()),
            out_specs=P(),
            check_vma=False,
        )(Ws, xs)

    return pipelined
