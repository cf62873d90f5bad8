"""One run of one cell: set-up, the measured closed loop, the check against
the plain reference, and the result line."""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import trace as tracing
from bench import traffic as T
from bench.manifest import Bench
from bench.systems import span

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 5.0   # longest traced window: traces are large
READING_S = 0.25      # shortest span of one host-clock reading


class NoAccelerator(RuntimeError):
    pass


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU here (JAX platform "
                            f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devices)}")
    return devices


def measure(bench: Bench, workload: str, seed: int, seconds: float,
            trace: bool, *, t_start: float, chip: bool = True,
            sizes: dict | None = None, control: bool = False,
            log=print) -> dict:
    """Run the cell once and return its result (the last line's object).

    ``chip=False`` lets the tests run the same path on the CPU, without the
    persistent compilation cache; ``sizes`` overrides keys of the
    configuration and the traffic (tests run at small sizes); ``control``
    puts the bfloat16 reference in the program's place."""
    import jax

    cell = bench.workload(workload)
    devices = _devices(int(cell["chips"]), chip)
    from repro import compile_cache
    from repro.kernels import ops

    if chip:
        log(f"compile_cache={compile_cache.enable()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.__setitem__(
            0, compiles[0] + (event == COMPILE_EVENT)))

    sizes = sizes or {}
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    cfg.update({k: v for k, v in sizes.items() if k in cfg})
    mix.update({k: v for k, v in sizes.items() if k in mix})
    system = bench.system(cfg["system"]).System(
        cfg, mix, seed, bench.config_data(cell["config"]), control=control)
    log("implementations=" + json.dumps(ops.implementations()))
    log("sizes=" + json.dumps({k: v for k, v in {**cfg, **mix}.items()
                               if isinstance(v, (int, float))}))
    log(f"mean_node_loads={getattr(system, 'mean_node_loads', None)}")

    window = min(seconds, TRACE_SECONDS) if trace else seconds
    kept = T.Reservoir(int(mix["kept_steps"]), seed)
    readings, updates = [], []
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    compiles_setup = compiles[0]
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_start
    steps = 0
    try:
        with span(tracing.WINDOW):
            t0 = g0 = time.perf_counter()
            n, upd = 0, []
            while True:
                with span("bench.step"):
                    u = system.step(steps)
                kept.offer(system.kept())
                steps, n = steps + 1, n + 1
                if u is not None:
                    upd.append(u)
                t = time.perf_counter()
                if t - g0 >= READING_S:   # one reading: consecutive steps
                    readings.append((t - g0) / n)
                    if upd:
                        updates.append(sum(upd) / len(upd))
                    g0, n, upd = t, 0, []
                    if t - t0 >= window:
                        break
            window_s = t - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles_window = compiles[0] - compiles_setup

    used = devices[: int(cell["chips"])]
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    work = system.work
    system.close()
    checks = system.check(kept.items)
    limits = bench.limits(workload)["checks"]
    correct = (set(checks) == set(limits)
               and all(checks[k] <= limits[k]["limit"] for k in checks))

    log(f"steps={steps} window_s={window_s!r} compiles_in_window="
        f"{compiles_window} memory_peak_bytes={memory_peak}")
    log("readings_ms=" + json.dumps([round(r * 1e3, 3) for r in readings]))
    if updates:
        log("updates_ms=" + json.dumps([round(u * 1e3, 3) for u in updates]))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    draws = steps * int(mix["draws_per_step"])
    result = {"correct": bool(correct), "attempted": draws, "failed": 0}
    if trace:
        red = tracing.reduce(tracing.load(trace_dir, devices[0].platform))
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = bench.peaks(devices[0].device_kind)
        ctx = SimpleNamespace(**red, work=work,
                              hbm_bytes_per_s=peaks["hbm_bytes_per_s"])
        metrics = {}
        for m in bench.per_layer(workload):
            value = bench.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown=red["breakdown"])
    else:
        e2e = {"setup_s": setup_s,
               "samples_per_s": draws / window_s,
               "step_p95_ms": p95(readings) * 1e3}
        if updates:
            e2e["update_p95_ms"] = p95(updates) * 1e3
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench.end_to_end(workload) if m["name"] in e2e}
        result.update(metrics=metrics, device=device)
    result["checks"] = {k: {"value": v, "limit": limits.get(k, {}).get("limit")}
                        for k, v in checks.items()}
    return result


def main(argv=None, *, t_start: float, root: Path) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = measure(Bench(root), args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=t_start,
                         log=lambda s: print(s, flush=True))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
