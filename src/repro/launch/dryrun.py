"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds ShapeDtypeStruct inputs (zero allocation), attaches
the sharding policy, runs ``jit(step).lower(...).compile()`` against the
production mesh, prints ``memory_analysis()`` / ``cost_analysis()``, derives
the three roofline terms, and writes a JSON record consumed by
EXPERIMENTS.md. Any sharding mismatch / unsupported collective here is a
real bug in the distribution config — that is the point of the exercise.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --arch kimi-k2-1t-a32b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --list
"""
import argparse
import contextlib
import dataclasses
import json
import os
import time
import traceback
from pathlib import Path

# Import hygiene: everything heavyweight (jax, repro.models, repro.dist, the
# step builders) is imported inside function bodies. Importing this module
# must stay cheap and dependency-free so `--list`, the report tooling, and
# `tests/test_imports.py` cannot be taken down by a broken subsystem.


def force_host_devices() -> None:
    """512 fake host devices for the production meshes. Called from
    ``main`` only, before jax's backend starts (it locks the device count on
    first use) — never at import, where it would leak into the importer."""
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS_EXTRA", "")
    )


def _opt_struct(params_sds, opt_dtype: str):
    import jax

    from repro.train.optimizer import AdamWConfig, init_opt

    oc = AdamWConfig(opt_dtype=opt_dtype)
    return jax.eval_shape(lambda p: init_opt(oc, p), params_sds), oc


def _dp(pol):
    return pol.dp if len(pol.dp) > 1 else (pol.dp[0] if pol.dp else None)


def run_cell(
    arch: str,
    shape: str,
    multi_pod: bool = False,
    opt_dtype: str = "float32",
    remat: str = "dots",
    microbatches: int = 1,
    policy_overrides: dict | None = None,
    donate: bool = True,
    gather_weights: bool = False,
    seq_shard: bool = False,
    params_dtype: str = "float32",
) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.configs as configs
    from repro.dist.sharding import (
        Policy,
        batch_specs,
        cache_spec_tree,
        param_shardings,
    )
    from repro.launch import roofline as R
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import (
        SHAPES,
        batch_specs_struct,
        decode_inputs_struct,
        params_struct,
    )
    from repro.train.step import make_serve_step, make_train_step

    cfg = configs.get(arch)
    sh = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    if policy_overrides and policy_overrides.get("auto"):
        pol = Policy.recommended(cfg, mesh, sh.kind)
        # measured: gather-on-use pays for train only (refuted for prefill
        # at 70B and for small-model decode, see EXPERIMENTS §Perf)
        gather_weights = sh.kind == "train"
        seq_shard = pol.shard_seq
        policy_overrides = {k: v for k, v in policy_overrides.items() if k != "auto"}
        if policy_overrides:
            pol = dataclasses.replace(pol, **policy_overrides)
    else:
        pol = Policy.for_mesh(mesh, sh.kind)
        if policy_overrides:
            pol = dataclasses.replace(pol, **policy_overrides)

    rec: dict = {
        "arch": arch,
        "shape": shape,
        "mesh": dict(mesh.shape),
        "chips": chips,
        "kind": sh.kind,
        "policy": dataclasses.asdict(pol),
        "opt_dtype": opt_dtype,
        "remat": remat,
        "microbatches": microbatches,
        "hints": {"gather_weights": gather_weights, "seq_shard": seq_shard},
    }
    from repro.dist.hints import Hints, sharding_hints

    hint_ctx = (
        sharding_hints(Hints(pol, gather_weights=gather_weights, seq_shard=seq_shard))
        if (gather_weights or seq_shard)
        else contextlib.nullcontext()
    )

    p_sds = params_struct(cfg)
    if params_dtype == "bfloat16":
        # pure-bf16 parameter variant (halves every gradient reduction and
        # the FSDP weight gathers; m/v stay in opt_dtype) — §Perf lever.
        import jax.numpy as jnp

        p_sds = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
            if l.dtype == jnp.float32 else l,
            p_sds,
        )
    rec["params_dtype"] = params_dtype
    p_shard = param_shardings(mesh, p_sds, pol)

    t0 = time.time()
    with mesh, hint_ctx:
        if sh.kind == "train":
            o_sds, oc = _opt_struct(p_sds, opt_dtype)
            # opt state shards like params; step counter replicated
            o_shard = type(o_sds)(
                step=NamedSharding(mesh, P()),
                m=param_shardings(mesh, o_sds.m, pol),
                v=param_shardings(mesh, o_sds.v, pol),
            )
            b_sds = batch_specs_struct(cfg, sh)
            b_shard = {
                k: NamedSharding(mesh, spec)
                for k, spec in batch_specs(cfg, pol, b_sds).items()
            }
            step = make_train_step(cfg, oc, remat=remat, microbatches=microbatches)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = jitted.lower(p_sds, o_sds, b_sds)
        elif sh.kind == "prefill":
            from repro.train.step import make_prefill_step

            b_sds = batch_specs_struct(cfg, sh)
            b_shard = {
                k: NamedSharding(mesh, spec)
                for k, spec in batch_specs(cfg, pol, b_sds).items()
            }
            step = make_prefill_step(cfg, max_seq=sh.seq_len)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(p_sds, b_sds)
        else:  # decode / long
            d = decode_inputs_struct(cfg, sh)
            c_shard = cache_spec_tree(cfg, d["cache"], pol, mesh)
            dp = None if pol.shard_seq else _dp(pol)
            tok_spec = (
                P(dp, None, None) if cfg.frontend == "embed" else P(dp)
            )
            in_sh = [
                p_shard,
                c_shard,
                NamedSharding(mesh, tok_spec),
                NamedSharding(mesh, P(dp)),
                NamedSharding(mesh, P(dp)),
            ]
            args = [p_sds, d["cache"], d["token"], d["pos"], d["xi"]]
            if cfg.encoder_layers:
                in_sh.append(
                    NamedSharding(
                        mesh, P(dp, pol.sp if pol.shard_seq else None, None)
                    )
                )
                args.append(d["enc_out"])
            step = make_serve_step(cfg, use_pallas=False)
            jitted = jax.jit(
                step,
                in_shardings=tuple(in_sh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(*args)
        rec["lower_s"] = time.time() - t0

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        mem = compiled.memory_analysis()
        print("memory_analysis:", mem)
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            v = getattr(mem, k, None)
            if v is not None:
                rec[k] = int(v)
        ca = compiled.cost_analysis()
        ca0 = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        print("cost_analysis: flops=%.3e bytes=%.3e" % (
            float(ca0.get("flops", 0)), float(ca0.get("bytes accessed", 0))))

        # lax.scan lowers to while; HloCostAnalysis counts bodies once, so
        # supply analytic flops/bytes + trip hints (see launch/analytic.py).
        from repro.launch import analytic as A

        af = A.step_flops(cfg, sh.kind, sh.seq_len, sh.global_batch, remat)
        ab = A.step_bytes(
            cfg, sh.kind, sh.seq_len, sh.global_batch,
            opt_bytes_per_param=12 if opt_dtype == "float32" else 8,
        )
        hints = (
            (microbatches, cfg.n_periods) if microbatches > 1 else (cfg.n_periods,)
        )
        roof = R.analyze(
            compiled, mesh, chips,
            trip_hints=hints,
            analytic_flops=af["step_flops"],
            analytic_bytes=ab["step_bytes"],
        )
        rec["roofline"] = roof.to_dict()
        rec["analytic"] = {**af, **ab}
        tokens = sh.global_batch * (sh.seq_len if sh.kind in ("train", "prefill") else 1)
        mf = R.model_flops(cfg, tokens)
        rec.update(mf)
        useful = mf["model_flops_6NactiveD" if cfg.n_experts else "model_flops_6ND"]
        if sh.kind != "train":
            useful /= 3.0  # 6ND assumes fwd+bwd; fwd-only is 2ND
        rec["useful_flops"] = useful
        rec["useful_over_hlo"] = useful / max(roof.flops_global, 1.0)
        bound = max(roof.t_compute, roof.t_mem, roof.t_coll, roof.t_coll_wire)
        rec["roofline_fraction"] = (
            useful / (R.PEAK_FLOPS * chips * bound) if bound > 0 else 0.0
        )
    return rec


def main() -> None:
    force_host_devices()
    import repro.configs as configs
    from repro.launch.shapes import SHAPES, cell_matrix

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--opt-dtype", default="float32")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-fsdp", action="store_true", help="hillclimb knob")
    ap.add_argument("--gather-weights", action="store_true", help="ZeRO-3 gather-on-use")
    ap.add_argument("--dp-only", action="store_true",
                    help="fold the model axis into DP/FSDP (no TP)")
    ap.add_argument("--params-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--decode-2d", action="store_true",
                    help="decode: 2D weight-stationary TP over (data,model), "
                         "seq-sharded KV, replicated per-token activations")
    ap.add_argument("--auto-policy", action="store_true",
                    help="use Policy.recommended (the hillclimbed presets)")
    ap.add_argument("--seq-shard", action="store_true", help="Megatron-SP residual")
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for arch, shape, status in cell_matrix():
            print(f"{arch:28s} {shape:12s} {status}")
        return

    overrides = {}
    if args.no_fsdp:
        overrides["fsdp"] = ()
    if args.dp_only:
        axes = ("pod", "data", "model") if args.multi_pod else ("data", "model")
        overrides.update(dp=axes, fsdp=axes, tp=None)
    if args.decode_2d:
        overrides.update(dp=(), fsdp=(), tp=("data", "model"), shard_seq=True)
    if args.auto_policy:
        overrides["auto"] = True
    try:
        rec = run_cell(
            configs.canonical(args.arch),
            args.shape,
            multi_pod=args.multi_pod,
            opt_dtype=args.opt_dtype,
            remat=args.remat,
            microbatches=args.microbatches,
            policy_overrides=overrides or None,
            gather_weights=args.gather_weights,
            seq_shard=args.seq_shard,
            params_dtype=args.params_dtype,
        )
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure for the report
        rec = {
            "arch": args.arch,
            "shape": args.shape,
            "multi_pod": args.multi_pod,
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(rec["traceback"])
    out = args.out or (
        f"experiments/dryrun/{configs.canonical(args.arch)}__{args.shape}"
        f"__{'pod2' if args.multi_pod else 'pod1'}.json"
    )
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    Path(out).write_text(json.dumps(rec, indent=2, default=str))
    print(f"wrote {out}: status={rec['status']}")
    if rec["status"] != "ok":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
