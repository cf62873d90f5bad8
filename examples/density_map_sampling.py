"""Figure-8 reproduction: sample a 2-D HDR environment map with the radix
forest (monotone, row-then-column) vs the Alias Method, on a low-discrepancy
point set. Writes PGM images of the sampled histograms + prints errors.

The forest branch runs through :class:`repro.spatial.Map2DSampler`: ONE
multi-row builder launch replaces the old per-row Python build loop, and the
whole point set resolves in one bulk ``sample_map`` drain (marginal descent
+ one batched conditional launch per size class). The alias branch gets the
same treatment via the fused batched alias build + one bulk drain. A
differential gate asserts the bulk path reproduces the per-row
row-then-column reference elementwise — with exact zero-mass-row semantics
(no ``+ 1e-18`` fudge: an empty row's marginal interval has zero width, so
no uniform can select it).

  PYTHONPATH=src python examples/density_map_sampling.py [--n 16384]
"""
import argparse
from pathlib import Path

import numpy as np
import jax.numpy as jnp

from repro.configs.paper_workloads import env_map_2d
from repro.core import build_alias, build_forest, np_sample_alias, quadratic_error, sample_forest
from repro.core.cdf import normalize_weights
from repro.core.lds import sobol
from repro.pool import build_alias_batched, sample_alias_batched
from repro.spatial import Map2DSampler


def write_pgm(path: str, img: np.ndarray) -> None:
    a = img / max(img.max(), 1e-30)
    a = (np.sqrt(a) * 255).astype(np.uint8)  # gamma for visibility
    with open(path, "wb") as fh:
        fh.write(f"P5\n{a.shape[1]} {a.shape[0]}\n255\n".encode())
        fh.write(a.tobytes())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 16)
    ap.add_argument("--h", type=int, default=96)
    ap.add_argument("--w", type=int, default=192)
    ap.add_argument("--out", default="experiments/density_map")
    args = ap.parse_args()

    h, w, n = args.h, args.w, args.n
    img = env_map_2d(h, w)
    p_flat = (img / img.sum()).ravel()
    pts = sobol(n, dims=2).astype(np.float32)

    # ---- forest branch: the bulk 2-D pipeline (no per-row build loop)
    sampler = Map2DSampler(img)
    ri, ci, _, _ = sampler.sample_map(pts)
    inv_counts = np.bincount(
        sampler.flat_index(ri, ci), minlength=h * w
    ).reshape(h, w)

    # Differential gate: the old row-then-column per-row loop, minus the
    # 1e-18 epsilon (zero-mass rows are exactly unselectable now). Class
    # rows behave exactly like build_forest over the pow2-padded row, so
    # the oracle builds at the class width; the bulk path must match
    # ELEMENTWISE — same rows, same columns, hence the same histogram.
    wc = int(sampler._class_of[0])
    f_rows = build_forest(jnp.asarray(normalize_weights(img.sum(axis=1))), h)
    rr = np.asarray(sample_forest(f_rows, jnp.asarray(pts[:, 0])))
    assert np.array_equal(rr, ri), "bulk marginal diverged from reference"
    cr = np.empty(n, np.int64)
    for r in np.unique(rr):
        mask = rr == r
        wpad = np.pad(normalize_weights(img[r]), (0, wc - w))
        f_col = build_forest(jnp.asarray(wpad), wc)
        cr[mask] = np.minimum(
            np.asarray(sample_forest(f_col, jnp.asarray(pts[mask, 1]))), w - 1
        )
    assert np.array_equal(cr, ci), "bulk conditional diverged from reference"

    # ---- alias branch: fused batched build + one bulk drain (loop killed)
    a_rows = build_alias(normalize_weights(img.sum(axis=1)))
    ra = np_sample_alias(
        np.asarray(a_rows.q, np.float64), np.asarray(a_rows.alias), pts[:, 0]
    )
    cond = np.stack([normalize_weights(img[r]) for r in range(h)])
    tbl = build_alias_batched(jnp.asarray(cond))
    ca = np.asarray(sample_alias_batched(
        tbl, jnp.asarray(ra, jnp.int32), jnp.asarray(pts[:, 1])
    ))
    ali_counts = np.bincount(ra * w + ca, minlength=h * w).reshape(h, w)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    write_pgm(out / "target.pgm", img)
    write_pgm(out / "inverse.pgm", inv_counts.astype(np.float64))
    write_pgm(out / "alias.pgm", ali_counts.astype(np.float64))
    e_inv = quadratic_error(inv_counts.ravel(), p_flat)
    e_ali = quadratic_error(ali_counts.ravel(), p_flat)
    print(f"n={n}: quadratic error inverse={e_inv:.3e} alias={e_ali:.3e} "
          f"(alias/inverse = {e_ali / max(e_inv, 1e-30):.2f}x)")
    print(f"forest drain: {sampler.last_drain}")
    print(f"wrote {out}/target.pgm, inverse.pgm, alias.pgm")


if __name__ == "__main__":
    main()
