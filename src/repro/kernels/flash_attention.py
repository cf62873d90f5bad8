"""Flash attention (online-softmax tiling) for the prefill hot path.

prefill_32k cells spend most of their compute term in S^2 attention; the
XLA default materializes (B, H, S, S) score tiles through HBM. This kernel
keeps the running (max, sum, acc) in VMEM scratch and streams K/V tiles, the
standard memory-hierarchy adaptation for TPU (HBM -> VMEM -> MXU):

  grid (B, H, Sq/Tq, Sk/Tk), innermost kv axis sequential; per (q-tile):
    m_new = max(m, rowmax(S_ij));  l = l*exp(m-m_new) + rowsum(P);
    acc = acc*exp(m-m_new) + P @ V_j;  out = acc / l at the last kv step.

Causal masking is per-element within the tile (iota comparison); GQA maps
query head h to kv head h // (H/KV) in the BlockSpec index map, so no
replication of K/V in memory. Validated against the pure-jnp oracle over
shape/dtype/causal/GQA sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  kv_len: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (Tq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (Tk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = q @ k.T                                          # (Tq, Tk)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < kv_len                                # mask padded keys
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        valid = valid & (kpos <= qpos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / float(np.sqrt(hd))
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    Sqp = (Sq + bq - 1) // bq * bq
    Skp = (Sk + bk - 1) // bk * bk
    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)),
                 constant_values=0)
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
    grid = (B, H, Sqp // bq, Skp // bk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, kv_len=Sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
