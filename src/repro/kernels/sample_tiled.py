"""Per-row inverse-CDF sampling by streaming counts — decode-time sampling.

Per-row decode sampling: each of B rows has its *own* CDF (from that row's
logits) and k uniforms. A GPU thread would binary-search with scattered
loads; a TPU lane cannot gather across vregs. The TPU-native equivalent is a
dense count: the sampled index of uniform ``xi`` is the number of CDF
entries ``<= xi``. The kernel streams each row block through VMEM in
``(8, tile)`` tiles along the vocabulary and accumulates the per-uniform
counts in the resident output block, so every lane does the same compares
and the whole batch finishes together — the kernel-level form of the
paper's "all lanes finish together" goal. The row is read once from HBM,
which a search that loads the row into VMEM pays as well.

CDF convention: row[i] = P_{i+1} (leading zero omitted, row[V-1] ~= 1), i.e.
the output of :mod:`repro.kernels.cdf_scan`. Returned index i satisfies
P_i <= xi < P_{i+1}; on a monotone row the count is exactly
``searchsorted(row, xi, side="right")``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROWS = 8  # f32 sublanes per row block


def _count_kernel(cdf_ref, xi_ref, o_ref, *, k: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = cdf_ref[...]                      # (R, tile)
    xis = xi_ref[...]                       # (R, k)
    for kk in range(k):                     # k is small & static (usually 1)
        hits = (seg <= xis[:, kk:kk + 1]).astype(jnp.int32)
        o_ref[:, kk:kk + 1] += jnp.sum(hits, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sample_rows(
    cdf_rows: jax.Array,
    xi: jax.Array,
    tile: int = 512,
    *,
    interpret: bool,
) -> jax.Array:
    """cdf_rows (B, V) inclusive CDFs, xi (B, k) uniforms -> (B, k) int32."""
    B, V = cdf_rows.shape
    k = xi.shape[1]
    Bp = (B + _ROWS - 1) // _ROWS * _ROWS
    Vp = (V + tile - 1) // tile * tile
    # pad with +inf-like sentinel: padded entries never counted as <= xi
    cp = jnp.pad(
        cdf_rows.astype(jnp.float32), ((0, Bp - B), (0, Vp - V)),
        constant_values=2.0,
    )
    xp = jnp.pad(xi.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_count_kernel, k=k),
        grid=(Bp // _ROWS, Vp // tile),
        in_specs=[
            pl.BlockSpec((_ROWS, tile), lambda i, j: (i, j)),
            pl.BlockSpec((_ROWS, k), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, k), jnp.int32),
        interpret=interpret,
    )(cp, xp)
    return jnp.minimum(out[:B], V - 1)
