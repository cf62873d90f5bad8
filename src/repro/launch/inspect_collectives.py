"""Per-op collective breakdown for one cell: the §Perf microscope.

  PYTHONPATH=src python -m repro.launch.inspect_collectives --arch granite-3-8b --shape train_4k
"""
import argparse
import re

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--gather-weights", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--decode-2d", action="store_true")
    ap.add_argument("--remat", default="dots")
    args = ap.parse_args()

    from repro.launch.dryrun import force_host_devices

    force_host_devices()
    import repro.configs as configs
    from repro.launch import roofline as R
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES

    # Re-lower directly to keep the compiled object.
    import dataclasses
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.sharding import Policy, batch_specs, cache_spec_tree, param_shardings
    from repro.launch.shapes import batch_specs_struct, decode_inputs_struct, params_struct
    from repro.train.optimizer import AdamWConfig, init_opt
    from repro.train.step import make_serve_step, make_train_step

    arch = configs.canonical(args.arch)
    cfg = configs.get(arch)
    sh = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    pol = Policy.for_mesh(mesh, sh.kind)
    if args.no_fsdp:
        pol = dataclasses.replace(pol, fsdp=())
    if args.decode_2d:
        pol = dataclasses.replace(pol, dp=(), fsdp=(), tp=("data", "model"), shard_seq=True)
    import contextlib
    from repro.dist.hints import Hints, sharding_hints
    hint_ctx = (sharding_hints(Hints(pol, gather_weights=args.gather_weights,
                                     seq_shard=args.seq_shard))
                if (args.gather_weights or args.seq_shard) else contextlib.nullcontext())
    p_sds = params_struct(cfg)
    p_shard = param_shardings(mesh, p_sds, pol)
    with mesh, hint_ctx:
        if sh.kind == "train":
            oc = AdamWConfig()
            o_sds = jax.eval_shape(lambda p: init_opt(oc, p), p_sds)
            o_shard = type(o_sds)(
                step=NamedSharding(mesh, P()),
                m=param_shardings(mesh, o_sds.m, pol),
                v=param_shardings(mesh, o_sds.v, pol),
            )
            b_sds = batch_specs_struct(cfg, sh)
            b_shard = {k: NamedSharding(mesh, s) for k, s in batch_specs(cfg, pol).items()}
            step = make_train_step(cfg, oc, remat=args.remat)
            compiled = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                               donate_argnums=(0, 1)).lower(p_sds, o_sds, b_sds).compile()
            hints = (cfg.n_periods,)
        else:
            d = decode_inputs_struct(cfg, sh)
            c_shard = cache_spec_tree(cfg, d["cache"], pol, mesh)
            dp = None if pol.shard_seq else (pol.dp if len(pol.dp) > 1 else pol.dp[0])
            tok_spec = P(dp, None, None) if cfg.frontend == "embed" else P(dp)
            in_sh = [p_shard, c_shard, NamedSharding(mesh, tok_spec),
                     NamedSharding(mesh, P(dp)), NamedSharding(mesh, P(dp))]
            argsl = [p_sds, d["cache"], d["token"], d["pos"], d["xi"]]
            if cfg.encoder_layers:
                in_sh.append(NamedSharding(mesh, P(dp, None, None)))
                argsl.append(d["enc_out"])
            step = make_serve_step(cfg, use_pallas=False)
            compiled = jax.jit(step, in_shardings=tuple(in_sh),
                               donate_argnums=(1,)).lower(*argsl).compile()
            hints = (cfg.n_periods,)

        txt = compiled.as_text()
        rows = []
        for line in txt.splitlines():
            ls = line.strip()
            m = re.search(r"=\s+((?:\([^)]*\))|(?:\w+\[[\d,]*\]\S*))\s+([\w-]+?)(-start)?\(", ls)
            if not m or m.group(2) not in R._COLL_KINDS:
                continue
            shapes = R._SHAPE_RE.findall(m.group(1))
            rbytes = sum(R._shape_bytes(f"{dt}[{dims}]") for dt, dims in shapes)
            if m.group(3) and len(shapes) >= 2:
                rbytes //= 2
            mo = re.search(r'op_name="([^"]*)"', ls)
            name = mo.group(1) if mo else "?"
            depth = name.count("/while/")
            mult = int(np.prod([hints[d] if d < len(hints) else 1 for d in range(depth)])) if depth else 1
            rows.append((rbytes * mult, rbytes, mult, m.group(2), m.group(1)[:40], name[-90:]))
        rows.sort(reverse=True)
        total = sum(r[0] for r in rows)
        print(f"total effective per-device collective result bytes: {total/1e9:.1f} GB")
        for eff, raw, mult, kind, shape, name in rows[: args.top]:
            print(f"{eff/1e9:9.2f}GB x{mult:3d} {kind:18s} {shape:40s} {name}")


if __name__ == "__main__":
    main()
