"""envmap-4k's 4K HDR environment map, with a sun that moves every frame.

The fixed part of the map is ``bench/configs/envmap-4k.py``'s, loaded from
that file: a smooth sky and six fixed suns, rows weighted by ``sin(theta)``.
The moving sun is one more Gaussian sun on row ``moving_sun_row``; it is
truncated to the ``band_rows`` rows centred on that row, so a move of the sun
changes those rows alone, and is weighted by ``sin(theta)`` like the rest."""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp

from bench.manifest import _load_module

_fixed = _load_module(Path(__file__).with_name("envmap-4k.py"),
                      "bench_config_envmap-4k")


def make(cfg: dict, seed: int) -> jax.Array:
    """The fixed part of the map, envmap-4k's; ``seed`` is not used."""
    return _fixed.make(cfg, seed)


def band_start(cfg: dict) -> int:
    """The first row of the moving sun's band."""
    return int(cfg["moving_sun_row"]) - int(cfg["band_rows"]) // 2


def bands(cfg: dict, fixed: jax.Array, cols) -> jax.Array:
    """The band's rows of the map with the moving sun at each azimuth of
    ``cols`` (texels, wrapped at the seam): ``(len(cols), band_rows, width)``
    float32 on the device."""
    h, w = int(cfg["height"]), int(cfg["width"])
    lo, rows = band_start(cfg), int(cfg["band_rows"])
    amp = jnp.float32(cfg["moving_sun_amplitude"])
    sig = jnp.float32(cfg["moving_sun_sigma_px"])
    y0 = jnp.float32(cfg["moving_sun_row"])

    @jax.jit
    def one(band, cx):
        yy = jnp.arange(lo, lo + rows, dtype=jnp.float32)[:, None]
        xx = jnp.arange(w, dtype=jnp.float32)[None, :]
        dx = jnp.mod(xx - cx + w / 2, w) - w / 2
        sun = amp * jnp.exp(-((yy - y0) ** 2 + dx ** 2) / (2 * sig * sig))
        theta = (yy + 0.5) / h * jnp.pi
        return band + sun * jnp.sin(theta)

    band = jax.lax.dynamic_slice_in_dim(fixed, lo, rows, axis=0)
    return jax.vmap(one, in_axes=(None, 0))(
        band, jnp.asarray(cols, jnp.float32))
