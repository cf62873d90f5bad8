"""Core transformer layers: RMSNorm, RoPE, GQA attention, SwiGLU MLP.

Pure-function style: ``init_*`` builds param pytrees, ``apply`` functions are
stateless. Decode variants operate on a KV cache slice-in-place. Everything
is einsum-based so GSPMD can shard heads/ff/vocab from the PartitionSpec
rules in ``repro.dist.sharding``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Params = dict[str, Any]


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------- norm


def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * p["scale"]).astype(dt)


# ---------------------------------------------------------------------- rope


def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, hd), positions (B, S) -> rotated x."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 8)
    p = {
        "wq": _init(ks[0], (D, H, hd)),
        "wk": _init(ks[1], (D, KV, hd)),
        "wv": _init(ks[2], (D, KV, hd)),
        "wo": _init(ks[3], (H, hd, D)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), jnp.float32)
        p["bk"] = jnp.zeros((KV, hd), jnp.float32)
        p["bv"] = jnp.zeros((KV, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def _qkv(p: Params, cfg: ModelConfig, x: jax.Array, positions, rope: bool):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, cfg: ModelConfig, causal: bool, q_off: int | jax.Array = 0):
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd); GQA by head-group reshape."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    logits = jnp.einsum("bqhgk,bthk->bhgqt", qg, k).astype(jnp.float32) / np.sqrt(hd)
    if causal:
        qpos = jnp.arange(Sq)[:, None] + q_off
        kpos = jnp.arange(Sk)[None, :]
        mask = kpos <= qpos
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqt,bthk->bqhgk", w, v)
    return out.reshape(B, Sq, H, hd)


def attention(p: Params, cfg: ModelConfig, x, positions, causal=True) -> jax.Array:
    q, k, v = _qkv(p, cfg, x, positions, rope=True)
    if cfg.attn_impl == "flash":
        from repro.kernels import ops

        out = ops.flash_attention(q, k, v, causal=causal)
    else:
        out = _sdpa(q, k, v, cfg, causal)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def attention_decode(p: Params, cfg: ModelConfig, x, cache: dict, pos) -> tuple:
    """One-token decode with per-row positions (continuous batching: slots
    sit at different sequence offsets). x (B,1,D); pos (B,) int32;
    cache {k,v: (B,S,KV,hd), len scalar (bookkeeping only)}."""
    q, k, v = _qkv(p, cfg, x, pos[:, None], rope=True)
    B = x.shape[0]
    rows = jnp.arange(B)
    ck = cache["k"].at[rows, pos].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[rows, pos].set(v[:, 0].astype(cache["v"].dtype))
    S = ck.shape[1]
    H, hd = q.shape[2], q.shape[3]
    KV = ck.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    logits = jnp.einsum("bqhgk,bthk->bhgqt", qg, ck).astype(jnp.float32) / np.sqrt(hd)
    mask = jnp.arange(S)[None] <= pos[:, None]             # (B, S)
    logits = jnp.where(mask[:, None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhgqt,bthk->bqhgk", w, cv).reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv, "len": cache["len"] + 1}


def init_cross_attention(key, cfg: ModelConfig) -> Params:
    return init_attention(key, cfg)


def cross_attention(p: Params, cfg: ModelConfig, x, enc_kv) -> jax.Array:
    """enc_kv: precomputed (k, v) from encoder output."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
    k, v = enc_kv
    out = _sdpa(q, k.astype(x.dtype), v.astype(x.dtype), cfg, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def encoder_kv(p: Params, cfg: ModelConfig, enc_out) -> tuple:
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(enc_out.dtype))
    if cfg.qk_norm:
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return k, v


# ----------------------------------------------------------------------- mlp


def init_mlp(key, d: int, ff: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": _init(k1, (d, ff)),
        "wg": _init(k2, (d, ff)),
        "wo": _init(k3, (ff, d)),
    }


def mlp(p: Params, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h, p["wo"].astype(x.dtype))
