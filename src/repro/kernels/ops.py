"""Public kernel entry points: the one place that picks an implementation.

Every op resolves to one of three implementations (:func:`implementation`):

* ``"pallas"`` — the Pallas kernel, compiled by Mosaic (TPU only);
* ``"interpret"`` — the same kernel body run by the Pallas interpreter;
* ``"xla"`` — the pure-jnp formulation in :mod:`repro.kernels.ref`.

The policy: on a TPU every op whose kernel Mosaic compiles runs as
``"pallas"``; everywhere else ops run as ``"xla"`` (the references give the
same results at a fraction of the interpreter's dispatch cost). A caller may
pass ``use_pallas=True``/``False`` to ask for the kernel or the reference —
the differential tests do, to compare the two — and ``None`` (the default
everywhere) takes the policy. ``True`` off a TPU means the interpreter.

:data:`XLA_ONLY` names the ops whose kernels Mosaic refuses; they run as
``"xla"`` on every backend, whatever the caller asks for on a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.forest import RadixForest

from . import ref
from .alias_build import alias_build_batched as _alias_build_batched
from .alias_sample import alias_sample_batched as _alias_sample_batched
from .cdf_scan import cdf_scan as _cdf_scan
from .flash_attention import flash_attention as _flash_attention
from .forest_delta import forest_delta as _forest_delta
from .forest_delta import forest_delta_update as _forest_delta_update
from .forest_sample import forest_sample as _forest_sample
from .forest_sample import forest_sample_batched as _forest_sample_batched
from .forest_sample import (
    forest_sample_batched_streams as _forest_sample_batched_streams,
)
from .sample_tiled import sample_rows as _sample_rows

OPS = (
    "fused_cdf", "row_cumsum", "sample_rows", "forest_sample",
    "forest_sample_batched", "forest_sample_batched_streams",
    "alias_build_batched", "alias_sample_batched", "forest_delta",
    "forest_delta_update", "flash_attention",
)

# The descent and alias-drain kernels gather per lane from tables held whole
# in VMEM. Mosaic refuses that ("Only 2D gather is supported"; a 2-D gather
# must stay inside one vreg), and at n = 2^22 the tables would not fit in
# VMEM anyway. Until they are rewritten to DMA the rows a tile touches, these
# ops run their XLA formulation on every backend.
XLA_ONLY = frozenset({
    "forest_sample", "forest_sample_batched", "forest_sample_batched_streams",
    "alias_sample_batched",
})


def implementation(op: str, use_pallas: bool | None = None) -> str:
    """``"pallas"``, ``"interpret"`` or ``"xla"``: what ``op`` runs as on the
    current default backend, given the caller's ``use_pallas``."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = tpu
    if not use_pallas or (tpu and op in XLA_ONLY):
        return "xla"
    return "pallas" if tpu else "interpret"


def implementations() -> dict[str, str]:
    """The policy's choice for every op on the current backend."""
    return {op: implementation(op) for op in OPS}


def _kernel(op: str, use_pallas: bool | None):
    """None for the XLA formulation, else the kernel's ``interpret`` flag."""
    impl = implementation(op, use_pallas)
    return None if impl == "xla" else impl == "interpret"


def _degenerate_tables(forest, degenerate: bool | None):
    """The side tables for degenerate (tied-weight) cells, or ``None`` when
    no cell is flagged. ``degenerate=None`` asks the device (one blocking
    reduction; callers that track it host-side pass it and stay jit-safe)."""
    if degenerate is None:
        degenerate = bool(jax.device_get(forest.fallback.any()))
    if not degenerate:
        return None, None
    return forest.cell_first, forest.fallback


def fused_cdf(x: jax.Array, softmax: bool = True,
              use_pallas: bool | None = None) -> jax.Array:
    """(B, V) logits/weights -> (B, V) inclusive CDF rows."""
    interpret = _kernel("fused_cdf", use_pallas)
    if interpret is None:
        return ref.ref_cdf_scan(x, softmax=softmax)
    return _cdf_scan(x, softmax=softmax, interpret=interpret)


def row_cumsum(rows: jax.Array, use_pallas: bool | None = None) -> jax.Array:
    """(R, L) -> raw inclusive row prefix sums (no normalisation): the local
    scan of the distributed CDF build."""
    interpret = _kernel("row_cumsum", use_pallas)
    if interpret is None:
        return jnp.cumsum(rows, axis=-1)
    return _cdf_scan(rows, softmax=False, normalize=False, interpret=interpret)


def sample_rows(cdf_rows: jax.Array, xi: jax.Array,
                use_pallas: bool | None = None) -> jax.Array:
    """Per-row inverse CDF: (B, V) x (B, k) -> (B, k) int32 indices."""
    interpret = _kernel("sample_rows", use_pallas)
    if interpret is None:
        return ref.ref_sample_rows(cdf_rows, xi)
    return _sample_rows(cdf_rows, xi, interpret=interpret)


def forest_sample(forest: RadixForest, xi: jax.Array,
                  use_pallas: bool | None = None,
                  degenerate: bool | None = None) -> jax.Array:
    """Shared-distribution Algorithm 2 over a batch of uniforms.

    When the build flagged degenerate (tied-weight) cells, both paths get the
    forest's ``cell_first``/``fallback`` side tables so those lanes
    pre-resolve by bisection instead of running past the fixed trip count.
    Well-conditioned forests (no flagged cell — the common case) skip the
    side tables and the 32-trip pre-resolution entirely."""
    cf, fb = _degenerate_tables(forest, degenerate)
    interpret = _kernel("forest_sample", use_pallas)
    if interpret is None:
        return ref.ref_forest_sample(
            forest.cdf, forest.table, forest.left, forest.right, xi, cf, fb
        )
    return _forest_sample(
        forest.cdf, forest.table, forest.left, forest.right, xi, cf, fb,
        interpret=interpret,
    )


def forest_sample_batched(
    forest, dist_id: jax.Array, xi: jax.Array,
    use_pallas: bool | None = None, degenerate: bool | None = None,
    coalesce: bool = True,
) -> jax.Array:
    """Mixed-batch Algorithm 2 over B stacked forests (one launch).

    ``forest`` is any object with the stacked ``BatchedForest`` fields
    (``repro.pool.batched.BatchedForest``; duck-typed here so the kernel
    layer never imports the pool layer). Same degenerate-cell policy as
    :func:`forest_sample`; ``ForestPool`` tracks flagged rows host-side and
    passes ``degenerate``, sparing the serving hot path a blocking device
    round-trip per drain. Lanes with ``dist_id < 0`` are sentinels
    (padding): resolved to 0 without walking any tree. ``coalesce`` toggles
    the kernel's bucketing pre-pass (stable sort by owning tree; elementwise
    identical either way — the jnp reference is order-invariant and ignores
    it)."""
    cf, fb = _degenerate_tables(forest, degenerate)
    interpret = _kernel("forest_sample_batched", use_pallas)
    if interpret is None:
        return ref.ref_forest_sample_batched(
            forest.cdf, forest.table, forest.left, forest.right,
            dist_id, xi, cf, fb,
        )
    return _forest_sample_batched(
        forest.cdf, forest.table, forest.left, forest.right, dist_id, xi,
        cf, fb, interpret=interpret, coalesce=coalesce,
    )


def forest_sample_batched_streams(
    forest, dist_id: jax.Array, counter: jax.Array, offset_bits: jax.Array,
    use_pallas: bool | None = None, degenerate: bool | None = None,
    coalesce: bool = True,
):
    """Stream-aware mixed-batch drain: QMC state in, ``(idx, xi)`` out.

    ``counter`` (Q,) uint32 carries each lane's rank-adjusted stream counter
    and ``offset_bits`` (Q,) uint32 its slot's 24-bit Cranley-Patterson
    rotation; the base-2 radical inverse + rotation run device-side (both
    paths use the exact integer pipeline of ``core.lds``), so a full pool
    drain needs no host-side uniform generation or counter bookkeeping.
    Same degenerate/sentinel/coalesce policy as
    :func:`forest_sample_batched`."""
    cf, fb = _degenerate_tables(forest, degenerate)
    interpret = _kernel("forest_sample_batched_streams", use_pallas)
    if interpret is None:
        return ref.ref_forest_sample_batched_streams(
            forest.cdf, forest.table, forest.left, forest.right,
            dist_id, counter, offset_bits, cf, fb,
        )
    return _forest_sample_batched_streams(
        forest.cdf, forest.table, forest.left, forest.right, dist_id,
        counter, offset_bits, cf, fb, interpret=interpret, coalesce=coalesce,
    )


def alias_build_batched(
    weights: jax.Array, use_pallas: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Batched split-and-pack alias construction: (B, n) stacked weights ->
    packed ``(q, alias)`` (B, n) stacks, one fused program. Rows with mixed
    lights/heavies pack via the positional prefix formulation; exactly
    uniform rows come back as identity tables. Both paths run the same row
    core, so they are bit-identical by construction."""
    interpret = _kernel("alias_build_batched", use_pallas)
    if interpret is None:
        return ref.ref_alias_build_batched(weights)
    return _alias_build_batched(weights, interpret=interpret)


def alias_sample_batched(
    table, dist_id: jax.Array, xi: jax.Array,
    use_pallas: bool | None = None, coalesce: bool = True,
) -> jax.Array:
    """Mixed-batch alias drain over B stacked tables (one launch).

    ``table`` is any object with stacked ``q`` (B, n) f32 / ``alias``
    (B, n) i32 fields (``repro.pool.batched.BatchedAlias``; duck-typed so
    the kernel layer never imports the pool layer). O(1) per lane — two
    gathers and a comparison — which is why PRNG tenants route here; the
    mapping is non-monotone, so QMC tenants must not. Lanes with
    ``dist_id < 0`` are sentinels (padding) resolved to 0; ``coalesce``
    toggles the stable sort-by-row bucketing pre-pass (elementwise
    identical either way)."""
    interpret = _kernel("alias_sample_batched", use_pallas)
    if interpret is None:
        return ref.ref_alias_sample_batched(table.q, table.alias, dist_id, xi)
    return _alias_sample_batched(
        table.q, table.alias, dist_id, xi, interpret=interpret,
        coalesce=coalesce,
    )


def forest_delta(data: jax.Array, m: int,
                 use_pallas: bool | None = None) -> jax.Array:
    """Separator distances for forest construction."""
    interpret = _kernel("forest_delta", use_pallas)
    if interpret is None:
        return ref.ref_forest_delta(data, m)
    return _forest_delta(data, m, interpret=interpret)


def forest_delta_update(data_old: jax.Array, data_new: jax.Array, m: int,
                        use_pallas: bool | None = None):
    """New separator distances + changed-leaf-bits mask for a weight update."""
    interpret = _kernel("forest_delta_update", use_pallas)
    if interpret is None:
        return ref.ref_forest_delta_update(data_old, data_new, m)
    return _forest_delta_update(data_old, data_new, m, interpret=interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    use_pallas: bool | None = None) -> jax.Array:
    """Blocked attention, (B, S, H, hd) x (B, S, KV, hd) -> (B, S, H, hd)."""
    interpret = _kernel("flash_attention", use_pallas)
    if interpret is None:
        return ref.ref_flash_attention(q, k, v, causal=causal)
    return _flash_attention(q, k, v, causal=causal, interpret=interpret)
