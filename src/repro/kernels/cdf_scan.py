"""Fused softmax -> CDF Pallas kernel (the inversion-method setup hot path).

For LM decode we must turn a row of logits (vocab up to ~202k) into a
normalized CDF every step. Doing softmax and cumsum as separate XLA ops costs
three HBM round-trips of the (B, V) tensor; this kernel fuses exponentiation,
normalization and the prefix scan into one pass over VMEM-resident tiles with
a per-row running carry (TPU grids iterate the trailing axis sequentially, so
the carry lives in VMEM scratch).

Two phases (two `pallas_call`s):
  1. row stats: running max/sum-of-exp (online-softmax style rescaling), or a
     plain sum for the weights->CDF case (the paper's construction input);
  2. scan: normalized exp + running prefix, emitting the inclusive CDF.

Tiling: rows x vocab blocks of (R, T); T a multiple of 128 (lane width), R a
multiple of 8 (sublanes, f32). VMEM working set = 2*R*T*4B + carries.

Mosaic has no lowering for ``cumsum`` or ``cummax``, so the in-tile prefix
ops are log-step shifted combines (:func:`lane_cumsum`, :func:`lane_cummax`):
rolls, selects and adds in a fixed order, which give the same bits under
Mosaic, XLA and the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _lane_scan(x: jax.Array, combine, fill) -> jax.Array:
    """Inclusive scan along the last axis by log-step shifted combines
    (Hillis-Steele): step ``s`` combines each lane with the lane ``s`` to its
    left, lanes ``< s`` with ``fill``."""
    T = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    s = 1
    while s < T:
        x = combine(x, jnp.where(lane >= s, jnp.roll(x, s, axis=-1), fill))
        s *= 2
    return x


def lane_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis, in the same association on
    every backend (the kernels and their jnp references share it)."""
    return _lane_scan(x, jnp.add, jnp.zeros((), x.dtype))


def lane_cummax(x: jax.Array, fill) -> jax.Array:
    """Inclusive prefix max along the last axis (exact in any order)."""
    return _lane_scan(x, jnp.maximum, jnp.asarray(fill, x.dtype))


def _stats_kernel(x_ref, m_ref, s_ref, mc_ref, sc_ref, *, softmax: bool):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        mc_ref[...] = jnp.full_like(mc_ref, NEG_INF if softmax else 0.0)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    x = x_ref[...].astype(jnp.float32)
    if softmax:
        m_prev = mc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
        s_new = sc_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
            jnp.exp(x - m_new), axis=-1, keepdims=True
        )
        mc_ref[...] = m_new
        sc_ref[...] = s_new
    else:
        sc_ref[...] = sc_ref[...] + jnp.sum(x, axis=-1, keepdims=True)

    @pl.when(j == nj - 1)
    def _():
        m_ref[...] = mc_ref[...]
        s_ref[...] = sc_ref[...]


def _scan_kernel(x_ref, m_ref, s_ref, o_ref, c_ref, *, softmax: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        c_ref[...] = jnp.zeros_like(c_ref)

    x = x_ref[...].astype(jnp.float32)
    if softmax:
        e = jnp.exp(x - m_ref[...]) / s_ref[...]
    else:
        e = x / s_ref[...]
    c = lane_cumsum(e) + c_ref[...]
    o_ref[...] = c
    c_ref[...] = c[:, -1:]


@functools.partial(
    jax.jit,
    static_argnames=("softmax", "normalize", "block_rows", "block_cols", "interpret"),
)
def cdf_scan(
    x: jax.Array,
    softmax: bool = True,
    normalize: bool = True,
    block_rows: int = 8,
    block_cols: int = 512,
    *,
    interpret: bool,
) -> jax.Array:
    """(B, V) logits (softmax=True) or non-negative weights (False) ->
    (B, V) inclusive CDF rows, last element ~1.0 (leading 0 omitted).

    ``normalize=False`` (weights mode only) skips the stats pass and emits the
    raw inclusive row cumsum — the local scan of the distributed CDF build
    (``repro.dist.forest``): row totals are exchanged across devices and the
    carry is applied there, so the kernel must not divide."""
    if softmax and not normalize:
        raise ValueError("normalize=False requires softmax=False (raw cumsum)")
    B, V = x.shape
    R, T = block_rows, block_cols
    Bp = (B + R - 1) // R * R
    Vp = (V + T - 1) // T * T
    pad_val = NEG_INF if softmax else 0.0
    xp = jnp.pad(x, ((0, Bp - B), (0, Vp - V)), constant_values=pad_val)
    grid = (Bp // R, Vp // T)

    if normalize:
        m, s = pl.pallas_call(
            functools.partial(_stats_kernel, softmax=softmax),
            grid=grid,
            in_specs=[pl.BlockSpec((R, T), lambda i, j: (i, j))],
            out_specs=[
                pl.BlockSpec((R, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((R, 1), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
                jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
            ],
            interpret=interpret,
        )(xp)
    else:
        # raw mode: s == 1 makes the scan kernel's division exact identity
        m = jnp.zeros((Bp, 1), jnp.float32)
        s = jnp.ones((Bp, 1), jnp.float32)

    out = pl.pallas_call(
        functools.partial(_scan_kernel, softmax=softmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, T), lambda i, j: (i, j)),
            pl.BlockSpec((R, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((R, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((R, T), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Vp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32)],
        interpret=interpret,
    )(xp, m, s)
    return out[:B, :V]
