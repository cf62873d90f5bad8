"""Chip smoke test: the sampler's main path, once, on a TPU, with checks.

  python chip_smoke.py [--seed S]      # one chip: forest, pool, serve phases
  python chip_smoke.py --chips 4       # four chips: the sharded forest only

Every phase prints one line: its sizes, the implementation each kernel op
resolved to (``repro.kernels.ops``), cold and warm wall seconds (each timed
to ``block_until_ready``; ``compile_s`` is cold minus warm), the persistent
compile-cache hits and misses, and the result of its check. A failed check
raises. The last line of standard output is the JSON result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase and prints no
result. Weights and data come from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}
_cache = {"hits": 0, "misses": 0}


def _on_event(event: str, **_) -> None:
    if event in CACHE_EVENTS:
        _cache[CACHE_EVENTS[event]] += 1


def _timed(fn):
    """(result, seconds) with the result on the host side of the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _impls(*ops_used: str) -> str:
    from repro.kernels import ops

    return ",".join(f"{op}:{ops.implementation(op)}" for op in ops_used)


def _line(phase: str, ok: bool, cache0: dict, **fields) -> None:
    cache = {k: _cache[k] - cache0[k] for k in _cache}
    parts = [f"{k}={v}" for k, v in fields.items()]
    parts += [f"cache_hits={cache['hits']}", f"cache_misses={cache['misses']}",
              f"check={'pass' if ok else 'FAIL'}"]
    print(f"phase={phase} " + " ".join(parts), flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: phase {phase} failed its check")


def zipf_weights(n: int, power: float = 0.75):
    """word2vec's unigram^power over a Zipf vocabulary, made on device."""
    import jax.numpy as jnp

    return jnp.arange(1, n + 1, dtype=jnp.float32) ** jnp.float32(-power)


def qmc_uniforms(n: int, seed: int):
    """n base-2 QMC points with one seeded 24-bit rotation, made on device."""
    import numpy as np
    import jax.numpy as jnp

    from repro.core.lds import qmc_offset_bits_np, qmc_point

    off = qmc_offset_bits_np(np.random.default_rng(seed).random(1))
    return qmc_point(jnp.arange(n, dtype=jnp.uint32), jnp.asarray(off))


def searchsorted_ref(cdf, xi, n: int):
    """Interval ids by ``np.searchsorted`` over a CDF with leading 0."""
    import numpy as np

    return np.clip(np.searchsorted(cdf, xi, side="right") - 1, 0, n - 1)


def phase_forest(n: int, m: int, seed: int) -> None:
    """build_forest over Zipf^0.75, then drain n QMC uniforms through
    ops.forest_sample; every index against searchsorted on the host."""
    import functools
    import numpy as np
    import jax

    from repro.core import build_forest
    from repro.kernels import ops

    c0 = dict(_cache)
    w = zipf_weights(n)
    build = jax.jit(build_forest, static_argnums=1)
    forest, build_cold = _timed(lambda: build(w, m))
    forest, build_warm = _timed(lambda: build(w, m))
    xi = qmc_uniforms(n, seed)
    degenerate = bool(forest.fallback.any())
    drain = jax.jit(functools.partial(ops.forest_sample, degenerate=degenerate))
    idx, drain_cold = _timed(lambda: drain(forest, xi))
    idx, drain_warm = _timed(lambda: drain(forest, xi))
    want = searchsorted_ref(np.asarray(forest.cdf), np.asarray(xi), n)
    bad = int(np.count_nonzero(np.asarray(idx) != want))
    _line("forest", bad == 0, c0, n=n, m=m, draws=n,
          impl=_impls("forest_sample"), degenerate_cells=degenerate,
          build_cold_s=build_cold, build_warm_s=build_warm,
          build_compile_s=build_cold - build_warm,
          drain_cold_s=drain_cold, drain_warm_s=drain_warm,
          drain_compile_s=drain_cold - drain_warm, mismatches=bad)


def phase_pool(tenants: int, lo: int, hi: int, draws: int,
               update_every: int, seed: int) -> None:
    """ForestPool tenants of sizes lo..hi over every size class: insert_many,
    one update_weights round, then drain through device QMC streams; each
    draw against a per-tenant searchsorted at the returned stream points."""
    import numpy as np
    import jax

    from repro.serve import PooledForestSampler

    c0 = dict(_cache)
    rng = np.random.default_rng(seed)
    sizes = np.exp(rng.uniform(np.log(lo), np.log(hi), tenants)).astype(int)
    classes = [lo << k for k in range(int(np.log2(hi // lo)) + 1)]
    sizes[: len(classes)] = classes  # every size class is touched
    weights = [rng.random(s) ** 3 + 1e-3 for s in sizes]
    ps = PooledForestSampler(n_slots=tenants, seed=seed)
    pool = ps.pool
    arrays = lambda: [sc.forest for sc in pool.classes.values()]
    t0 = time.perf_counter()
    hs = ps.add_many(weights, method="forest")
    jax.block_until_ready(arrays())
    insert_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    updated = 0
    for t in range(0, tenants, update_every):
        ps.update(hs[t], rng.random(sizes[t]) ** 3 + 1e-3)
        updated += 1
    jax.block_until_ready(arrays())
    update_s = time.perf_counter() - t0
    per = draws // tenants
    slots = np.repeat(np.arange(tenants), per)
    lanes = [hs[t] for t in slots]
    drain = lambda: pool.sample_streams(lanes, slots, ps.streams,
                                        return_xi=True)
    _, drain_cold = _timed(drain)
    (out, xi), drain_warm = _timed(drain)
    cdfs = {size: np.asarray(sc.forest.cdf) for size, sc in pool.classes.items()}
    bad = 0
    for t, h in enumerate(hs):
        sl = slice(t * per, (t + 1) * per)
        want = searchsorted_ref(cdfs[h.size_class][h.row], xi[sl], h.n)
        bad += int(np.count_nonzero(out[sl] != want))
    _line("pool", bad == 0, c0, tenants=tenants, sizes=f"{lo}-{hi}",
          size_classes=len(pool.classes), draws=len(slots), updated=updated,
          impl=_impls("forest_sample_batched_streams", "forest_delta_update"),
          insert_cold_s=insert_s, update_cold_s=update_s,
          drain_cold_s=drain_cold, drain_warm_s=drain_warm,
          drain_compile_s=drain_cold - drain_warm, mismatches=bad)


def phase_serve(arch: str, reduced: bool, slots: int, requests: int,
                prompt_len: int, max_new: int, seed: int) -> None:
    """launch.serve's path at the arch's width; one full-batch decode step
    checked against a float64 host CDF over the same logits and uniforms."""
    import numpy as np
    import jax.numpy as jnp

    import repro.configs as C
    from repro.kernels import ops
    from repro.launch.serve import serve
    from repro.serve import TokenSampler

    class RecordingSampler(TokenSampler):
        """Keeps the first full-batch step's logits, uniforms and tokens."""
        step = None

        def uniforms(self, lanes):
            self._xi = super().uniforms(lanes)
            return self._xi

        def sample(self, logits, lanes):
            toks = super().sample(logits, lanes)
            if self.step is None and len(lanes) == slots:
                self.step = (logits, self._xi, toks)
            return toks

    cfg = C.get_reduced(arch) if reduced else C.get(arch)
    c0 = dict(_cache)
    runs = []
    for _ in range(2):  # cold (compiles), then warm
        sampler = RecordingSampler(n_slots=slots, seed=seed)
        t0 = time.perf_counter()
        eng, reqs = serve(arch, reduced=reduced, requests=requests,
                          slots=slots, prompt_len=prompt_len, max_new=max_new,
                          seed=seed, sampler=sampler)
        runs.append((time.perf_counter() - t0, eng, reqs, sampler))
    (cold, _, _, _), (warm, eng, reqs, sampler) = runs
    finished = all(r.done and len(r.out) == max_new for r in reqs)
    in_range = all(0 <= t < cfg.vocab for r in reqs for t in r.out)
    logits, xi, toks = sampler.step
    # the device's own f32 CDF of the same logits: the tokens must be its
    # exact inverse (same kernels, same bits)
    cdf32 = np.asarray(ops.fused_cdf(logits / sampler.temperature))
    own = np.minimum((cdf32 <= xi[:, None]).sum(axis=1), cfg.vocab - 1)
    exact = bool(np.array_equal(own, toks))
    # float64 host CDF: each token's interval must hold its uniform, or sit
    # within the f32 CDF's measured error of a boundary
    z = np.asarray(logits.astype(jnp.float32), np.float64) / sampler.temperature
    p = np.exp(z - z.max(axis=1, keepdims=True))
    cdf64 = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)
    err = float(np.abs(cdf32 - cdf64).max())
    rows = np.arange(len(toks))
    hi = cdf64[rows, toks]
    lo = np.where(toks > 0, cdf64[rows, np.maximum(toks - 1, 0)], 0.0)
    inside = (lo <= xi) & (xi < hi)
    near = ~inside & (xi >= lo - err) & (xi < hi + err)
    outside = int(np.count_nonzero(~inside & ~near))
    ok = finished and in_range and exact and outside == 0
    _line("serve", ok, c0, arch=cfg.name, layers=cfg.n_layers,
          d_model=cfg.d_model, vocab=cfg.vocab, dtype=cfg.dtype, slots=slots,
          requests=requests, prompt_len=prompt_len, max_new=max_new,
          decode_steps=eng.steps, impl=_impls("fused_cdf", "sample_rows"),
          cold_s=cold, warm_s=warm, compile_s=cold - warm,
          all_finished=finished, tokens_in_range=in_range,
          tokens_match_device_cdf=exact, cdf_f32_max_err=err,
          checked_tokens=len(toks), near_boundary=int(near.sum()),
          outside_interval=outside)


def phase_sharded(n: int, m: int, seed: int) -> None:
    """build_forest_sharded(rebalance=True) over every device, the routed
    sample_sharded drain against build_forest + sample_forest on one
    device, and a sparse update_forest_sharded against a from-scratch
    build over the same partition, bit for bit."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import build_forest, sample_forest
    from repro.dist import forest as DF

    c0 = dict(_cache)
    mesh = DF.default_mesh()
    D = int(mesh.devices.size)
    w = zipf_weights(n)
    build = lambda: DF.build_forest_sharded(w, m, mesh=mesh, rebalance=True)
    sf, build_cold = _timed(build)
    sf, build_warm = _timed(build)
    xi = qmc_uniforms(n, seed)
    drain = lambda: DF.sample_sharded(sf, xi, mesh=mesh)
    got, drain_cold = _timed(drain)
    got, drain_warm = _timed(drain)
    spread = min(len(a.sharding.device_set) for a in (sf.left, sf.right))
    want = sample_forest(build_forest(w, m), xi)
    bad = int(np.count_nonzero(np.asarray(got) != np.asarray(want)))

    rng = np.random.default_rng(seed)
    hit = jnp.asarray(rng.choice(n, 64, replace=False))
    w2 = w.at[hit].multiply(jnp.asarray(1.0 + rng.random(64), jnp.float32))
    (upd, stats), update_s = _timed(
        lambda: DF.update_forest_sharded(sf, w2, mesh=mesh, with_stats=True))
    fresh = DF.build_forest_sharded(
        w2, m, mesh=mesh, partition=np.asarray(upd.cell_bounds),
        capacity=upd.capacity)
    same = all(
        np.array_equal(np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8))
        for a, b in zip(upd, fresh)
    )
    ok = bad == 0 and same and spread == D
    _line("sharded", ok, c0, n=n, m=m, devices=D, draws=n,
          impl=_impls("forest_delta", "forest_delta_update"),
          capacity=sf.capacity, shards_holding_nodes=spread,
          build_cold_s=build_cold, build_warm_s=build_warm,
          build_compile_s=build_cold - build_warm,
          drain_cold_s=drain_cold, drain_warm_s=drain_warm,
          drain_compile_s=drain_cold - drain_warm, mismatches=bad,
          update_cold_s=update_s, dirty_shards=stats["dirty_shards"],
          update_bit_identical=same)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU here (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro import compile_cache

    print(f"compile_cache={compile_cache.enable()}", flush=True)
    jax.monitoring.register_event_listener(_on_event)
    if args.chips == 4:
        phase_sharded(n=1 << 22, m=1 << 20, seed=args.seed)
    else:
        phase_forest(n=1 << 22, m=1 << 20, seed=args.seed)
        phase_pool(tenants=4096, lo=16, hi=4096, draws=1 << 20,
                   update_every=8, seed=args.seed)
        phase_serve("qwen1.5-0.5b", reduced=False, slots=8, requests=16,
                    prompt_len=32, max_new=32, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
