"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

The descent oracles are also the XLA formulation that ``kernels.ops`` runs
for the ops in ``ops.XLA_ONLY``: the guide lookups of the two table layouts
in front of ``core.sample``'s one descent and bisection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bits import DIST_SENTINEL
from repro.core.sample import bisect, descend


def ref_cdf_scan(x: jax.Array, softmax: bool = True) -> jax.Array:
    """Oracle for kernels.cdf_scan.cdf_scan (float32 accumulation)."""
    x = x.astype(jnp.float32)
    if softmax:
        x = x - jnp.max(x, axis=-1, keepdims=True)
        e = jnp.exp(x)
    else:
        e = x
    c = jnp.cumsum(e, axis=-1)
    return c / c[..., -1:]


def ref_sample_rows(cdf_rows: jax.Array, xi: jax.Array) -> jax.Array:
    """Oracle for kernels.sample_tiled.sample_rows."""
    V = cdf_rows.shape[-1]

    def one(row, u):
        return jnp.clip(
            jnp.searchsorted(row, u, side="right").astype(jnp.int32), 0, V - 1
        )

    return jax.vmap(one)(cdf_rows, xi)


def ref_forest_descent(
    cdf, table, left, right, xi, cell_first=None, fallback=None, dist_id=None
):
    """Algorithm 2 as XLA ops, for the descent ops of both table layouts:
    ``(idx, trips, bisect_trips)``.

    1-D tables (``dist_id=None``) hold one forest; stacked (B, .) tables hold
    B, and lane q descends row ``dist_id[q]`` with 2-D gathers (sentinel
    lanes, ``dist_id < 0``, resolve to 0 without descending). With the
    ``cell_first``/``fallback`` side tables, lanes in flagged cells first
    resolve by bisection. The loops are ``core.sample.bisect`` and
    ``core.sample.descend``; both return their trip counts, which the ops
    drop."""
    m = table.shape[-1]
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    if dist_id is None:
        def at(a, i):
            return a[i]
        j = table[g]
    else:
        raw = dist_id.astype(jnp.int32)
        did = jnp.clip(raw, 0, table.shape[0] - 1)

        def at(a, i):
            return a[did, i]
        j = jnp.where(raw >= 0, at(table, g), -1)  # sentinels sit at leaf ~0

    bisect_trips = jnp.int32(0)
    if cell_first is not None and fallback is not None:
        flagged = at(fallback, g) & (j >= 0)
        lo = at(cell_first, g)
        hi = jnp.where(flagged, at(cell_first, g + 1), lo)
        lo, bisect_trips = bisect(cdf, xi, lo, hi, at=at)
        j = jnp.where(flagged, ~lo, j)
    idx, trips = descend(cdf, left, right, xi, j, at=at)
    return idx, trips, bisect_trips


def ref_forest_sample(
    cdf, table, left, right, xi, cell_first=None, fallback=None
) -> jax.Array:
    """Oracle for kernels.forest_sample.forest_sample (same optional
    degenerate-cell pre-resolution as the kernel), stopping at the deepest
    lane (:func:`ref_forest_descent`)."""
    return ref_forest_descent(
        cdf, table, left, right, xi, cell_first, fallback
    )[0]


def ref_forest_sample_batched(
    cdf, table, left, right, dist_id, xi, cell_first=None, fallback=None
) -> jax.Array:
    """Oracle for kernels.forest_sample.forest_sample_batched: lane q
    descends distribution dist_id[q]'s row with 2-D gathers (same optional
    degenerate-cell pre-resolution as the kernel), stopping at the deepest
    lane (:func:`ref_forest_descent`). Sentinel lanes (``dist_id < 0``)
    resolve to 0 without descending — same contract as the kernel, so padded
    drains stay elementwise comparable."""
    return ref_forest_descent(
        cdf, table, left, right, xi, cell_first, fallback, dist_id
    )[0]


def ref_forest_sample_batched_streams(
    cdf, table, left, right, dist_id, counter, offset_bits,
    cell_first=None, fallback=None,
):
    """Oracle for kernels.forest_sample.forest_sample_batched_streams: the
    same exact 24-bit fixed-point radical-inverse + rotation pipeline
    (``core.lds.qmc_point``), then the batched descent. Returns
    ``(idx, xi)`` exactly like the kernel."""
    from repro.core.lds import qmc_point

    xi = qmc_point(counter, offset_bits)
    idx = ref_forest_sample_batched(
        cdf, table, left, right, dist_id, xi, cell_first, fallback
    )
    return idx, xi


def ref_alias_build_batched(weights: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Oracle for kernels.alias_build.alias_build_batched: literally the
    same positional split-and-pack row core (rows are independent, so the
    kernel's row blocking cannot change bits — agreement is structural)."""
    from repro.kernels.alias_build import alias_split_pack_rows

    return alias_split_pack_rows(jnp.asarray(weights, jnp.float32))


def ref_alias_sample_batched(
    q: jax.Array, alias: jax.Array, dist_id: jax.Array, xi: jax.Array
) -> jax.Array:
    """Oracle for kernels.alias_sample.alias_sample_batched: same float32
    arithmetic (scale, truncate, clamp into [0, 1), one comparison) with
    2-D gathers. Sentinel lanes (``dist_id < 0``) resolve to 0 without
    touching any row — same contract as the kernel."""
    from repro.core.alias import ALIAS_FRAC_MAX

    B, n = q.shape
    raw = dist_id.astype(jnp.int32)
    valid = raw >= 0
    did = jnp.clip(raw, 0, B - 1)
    scaled = xi * jnp.float32(n)
    cell = jnp.clip(scaled.astype(jnp.int32), 0, n - 1)
    frac = jnp.clip(
        scaled - cell.astype(jnp.float32), 0.0, jnp.float32(ALIAS_FRAC_MAX)
    )
    out = jnp.where(frac < q[did, cell], cell, alias[did, cell])
    return jnp.where(valid, out, 0).astype(jnp.int32)


def ref_forest_delta(data: jax.Array, m: int) -> jax.Array:
    """Oracle for kernels.forest_delta.forest_delta. Cells are clipped to
    [0, m-1] exactly like core.forest._cells, so the crossing mask is the
    tree builder's by construction, not by a rounding argument."""
    bits = jax.lax.bitcast_convert_type(data.astype(jnp.float32), jnp.uint32)
    raw = bits[:-1] ^ bits[1:]
    cells = jnp.clip(
        jnp.floor(data * jnp.float32(m)).astype(jnp.int32), 0, m - 1
    )
    return jnp.where(cells[:-1] != cells[1:], jnp.uint32(DIST_SENTINEL), raw)


def ref_forest_delta_update(data_old, data_new, m: int):
    """Oracle for kernels.forest_delta.forest_delta_update."""
    bits_old = jax.lax.bitcast_convert_type(data_old.astype(jnp.float32), jnp.uint32)
    bits_new = jax.lax.bitcast_convert_type(data_new.astype(jnp.float32), jnp.uint32)
    return ref_forest_delta(data_new, m), bits_old != bits_new


def ref_flash_attention(q, k, v, causal: bool = True) -> jax.Array:
    """Oracle for kernels.flash_attention (materialized scores)."""
    import numpy as np

    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqhgk,bthk->bhgqt", qg, k.astype(jnp.float32))
    s = s / np.sqrt(hd)
    if causal:
        mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqt,bthk->bqhgk", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)
