"""Forest-construction distance kernel (Algorithm 1's per-element work).

Computes the separator distance array that fully determines the radix forest:
``delta(k) = bits(data[k]) XOR bits(data[k+1])``, clamped to the sentinel
where the two lower bounds fall into different guide cells (the paper's
"setting the distance to the maximum"). Pure elementwise VPU work (bitcasts,
XOR, floor) — the O(n) hot loop of construction; the nearest-greater descent
that consumes it stays in XLA (see core.forest).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bits import DIST_SENTINEL


def _delta_kernel(a_ref, b_ref, o_ref, *, m: int):
    a = a_ref[...]
    b = b_ref[...]
    bits_a = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits_b = jax.lax.bitcast_convert_type(b, jnp.uint32)
    raw = bits_a ^ bits_b
    # Clip exactly like core.forest._cells. A crossing flagged here that the
    # tree builder does not see would diverge the forests bitwise; the
    # bit-identity contract must not rest on a rounding argument about
    # whether floor(data * m) can ever reach m.
    ca = jnp.clip(jnp.floor(a * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    cb = jnp.clip(jnp.floor(b * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    o_ref[...] = jnp.where(ca != cb, jnp.uint32(DIST_SENTINEL), raw)


@functools.partial(jax.jit, static_argnames=("m", "block", "interpret"))
def forest_delta(
    data: jax.Array, m: int, block: int = 1024, *, interpret: bool
) -> jax.Array:
    """data (n,) f32 increasing lower bounds -> (n-1,) uint32 distances."""
    n = data.shape[0]
    s = n - 1
    sp = max((s + block - 1) // block * block, block)
    a = jnp.pad(data[:-1], (0, sp - s))
    b = jnp.pad(data[1:], (0, sp - s))
    out = pl.pallas_call(
        functools.partial(_delta_kernel, m=m),
        grid=(sp // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((sp,), jnp.uint32),
        interpret=interpret,
    )(a, b)
    return out[:s]


def _changed_kernel(a_ref, b_ref, o_ref):
    bits_a = jax.lax.bitcast_convert_type(a_ref[...], jnp.uint32)
    bits_b = jax.lax.bitcast_convert_type(b_ref[...], jnp.uint32)
    o_ref[...] = (bits_a != bits_b).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("m", "block", "interpret"))
def forest_delta_update(
    data_old: jax.Array,
    data_new: jax.Array,
    m: int,
    block: int = 1024,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Algorithm-1 re-work for a weight update, in one elementwise pass.

    Returns ``(delta_new, leaf_changed)``: the (n-1,) separator distances of
    the *new* lower bounds (identical bits to :func:`forest_delta` on
    ``data_new``) and the (n,) mask of leaves whose float32 *bit pattern*
    moved. A cell (and hence the shard owning it) only needs its trees
    rebuilt when one of its leaves' bits moved — tree topology is a pure
    function of the bit patterns — so this mask is exactly the dirtiness
    signal the sharded delta path needs.
    """
    n = data_old.shape[0]
    np_ = max((n + block - 1) // block * block, block)
    a = jnp.pad(data_old, (0, np_ - n))
    b = jnp.pad(data_new, (0, np_ - n))
    changed = pl.pallas_call(
        _changed_kernel,
        grid=(np_ // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((np_,), jnp.int32),
        interpret=interpret,
    )(a, b)
    return (
        forest_delta(data_new, m, block=block, interpret=interpret),
        changed[:n].astype(jnp.bool_),
    )
