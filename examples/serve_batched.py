"""End-to-end serving driver (the paper's kind: sampling in the serving hot
path). Loads a small LM, runs continuous-batched decode over a stream of
requests, sampling every token through fused-CDF + guide-table inversion
with per-slot QMC streams.

  PYTHONPATH=src python examples/serve_batched.py [--requests 16] [--alias]
"""
import argparse
import dataclasses
import time

import numpy as np
import jax

import repro.configs as C
from repro.models import init_params
from repro.serve import Request, ServeEngine, TokenSampler


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--mode", default="inverse_qmc",
                    choices=["inverse_qmc", "inverse_rng", "alias"])
    args = ap.parse_args()

    cfg = dataclasses.replace(
        C.get_reduced("qwen3_4b"), dtype="float32",
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab=1024,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    sampler = TokenSampler(mode=args.mode, n_slots=args.slots,
                           temperature=0.8)
    eng = ServeEngine(params, cfg, n_slots=args.slots, max_seq=128,
                      sampler=sampler)

    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=rng.integers(4, 12)),
                max_new=args.max_new)
        for i in range(args.requests)
    ]
    t0 = time.time()
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=2000)
    dt = time.time() - t0
    toks = sum(len(r.out) for r in reqs)
    print(f"mode={args.mode}: {len(reqs)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s, {eng.steps} batched decode steps)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt={list(r.prompt)[:6]}... -> {r.out[:12]}...")
    assert all(r.done for r in reqs)


if __name__ == "__main__":
    main()
