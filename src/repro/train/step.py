"""Jittable train/serve steps — the units the dry-run lowers and compiles.

``make_train_step``: loss -> grad -> AdamW, with optional microbatch
gradient accumulation (``lax.scan`` over microbatches; overlaps the implicit
DP gradient reduction of microbatch i with the compute of i+1 under XLA's
latency-hiding scheduler) and optional bf16 gradient compression of the
accumulator (halves accumulation memory traffic + the cross-pod all-reduce
payload; error feedback not needed at bf16 — documented in DESIGN.md §6).

``make_serve_step``: one decode token through the cached stack, then the
paper's sampler: fused softmax->CDF + tiled inverse (kernels), or the
pure-jnp path for dry-runs.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import decode_step as model_decode
from repro.models import loss_fn
from repro.models.config import ModelConfig

from .optimizer import AdamWConfig, OptState, apply_updates


def make_train_step(
    cfg: ModelConfig,
    oc: AdamWConfig,
    remat: str = "dots",
    microbatches: int = 1,
    grad_dtype: str = "float32",
):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    batch tensors are (B, ...); with microbatches=k they are reshaped to
    (k, B/k, ...) and accumulated.
    """

    gdt = jnp.bfloat16 if grad_dtype == "bfloat16" else jnp.float32

    def loss_wrapped(params, batch):
        return loss_fn(params, cfg, batch, remat=remat)

    def train_step(params, opt_state: OptState, batch: dict):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_wrapped, has_aux=True)(
                params, batch
            )
        else:
            mb = {
                k: v.reshape((microbatches, v.shape[0] // microbatches) + v.shape[1:])
                for k, v in batch.items()
            }

            def body(acc, micro):
                (l, m), g = jax.value_and_grad(loss_wrapped, has_aux=True)(
                    params, micro
                )
                g = jax.tree.map(lambda a, b: a + b.astype(gdt), acc[0], g)
                return (g, acc[1] + l), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, gdt), params)
            (gsum, lsum), _ = jax.lax.scan(body, (zeros, 0.0), mb)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
            metrics = {}

        params, opt_state, om = apply_updates(oc, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **om}

    return train_step


def make_serve_step(cfg: ModelConfig, use_pallas: bool | None = None,
                    temperature: float = 1.0):
    """Returns serve_step(params, cache, token, pos, xi[, enc_out])
    -> (next_token (B,), cache). xi: per-slot uniforms (B,) — QMC streams
    from the serving scheduler keep the monotone warp stratified."""

    def serve_step(params, cache, token, pos, xi, enc_out=None):
        logits, cache = model_decode(params, cfg, cache, token, pos, enc_out)
        cdf = ops.fused_cdf(logits / temperature, softmax=True, use_pallas=use_pallas)
        nxt = ops.sample_rows(cdf, xi[:, None], use_pallas=use_pallas)[:, 0]
        return nxt.astype(jnp.int32), cache

    return serve_step


def make_prefill_step(cfg: ModelConfig, max_seq: int):
    from repro.models import prefill as model_prefill

    def prefill_step(params, batch):
        logits, cache, enc_out = model_prefill(params, cfg, batch, max_seq=max_seq)
        return logits, cache, enc_out

    return prefill_step
