"""Serving launcher: continuous batching + radix-CDF QMC sampler.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --requests 8

Serves the named architecture at its published width and in its own dtype,
with random weights from ``--seed``. ``--reduced`` serves the registry's
reduced config instead (CPU tests and rehearsals).
"""
from __future__ import annotations

import argparse


def serve(arch: str, *, reduced: bool = False, requests: int = 8,
          slots: int = 4, prompt_len: int = 8, max_new: int = 16,
          max_seq: int = 256, mode: str = "inverse_qmc", seed: int = 0,
          sampler=None):
    """Serve ``requests`` random prompts through ``ServeEngine``; returns
    ``(engine, requests)`` once every request is done (or the step budget
    ran out). ``sampler`` replaces the default ``TokenSampler(mode=mode)``."""
    import numpy as np
    import jax

    import repro.configs as C
    from repro.models import init_params
    from repro.serve import Request, ServeEngine, TokenSampler

    cfg = C.get_reduced(arch) if reduced else C.get(arch)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    eng = ServeEngine(
        params, cfg, n_slots=slots, max_seq=max_seq,
        sampler=sampler or TokenSampler(mode=mode, n_slots=slots, seed=seed),
    )
    rng = np.random.default_rng(seed)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=prompt_len),
                max_new=max_new)
        for i in range(requests)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced config (CPU scale)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", default="inverse_qmc",
                    choices=["inverse_qmc", "inverse_rng", "alias"])
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()
    eng, reqs = serve(
        args.arch, reduced=args.reduced, requests=args.requests,
        slots=args.slots, prompt_len=args.prompt_len, max_new=args.max_new,
        mode=args.mode, seed=args.seed,
    )
    done = sum(r.done for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    print(f"served {done}/{len(reqs)} requests, {toks} tokens, "
          f"{eng.steps} batched decode steps, sampler={args.mode}")


if __name__ == "__main__":
    main()
