"""A 4K HDR environment map, made on the device.

The luminance is ``repro.configs.paper_workloads.env_map_2d``'s (a smooth
sky plus bright Gaussian suns), copied here at full size; each row is then
weighted by ``sin(theta)`` of its centre, as PBRT's image infinite light
weights its 2-D distribution. The map is fixed by the configuration's
``map_seed``: a renderer samples one map frame after frame, and the run's
seed rotates the frames instead, so that every seed does the same work."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic


def make(cfg: dict, seed: int) -> jax.Array:
    """The map; ``seed`` is not used (see the module's docstring)."""
    h, w = int(cfg["height"]), int(cfg["width"])
    rng = traffic.rng(int(cfg["map_seed"]), 5)
    k = int(cfg["suns"])
    cy = rng.integers(0, h, k)
    cx = rng.integers(0, w, k)
    amp = 10.0 ** rng.uniform(*cfg["sun_log10_amplitude"], k)
    sig = rng.uniform(*cfg["sun_sigma_px"], k)
    suns = jnp.asarray(np.stack([cy, cx, amp, sig], axis=1), jnp.float32)

    @jax.jit
    def image(suns):
        yy = jnp.arange(h, dtype=jnp.float32)[:, None]
        xx = jnp.arange(w, dtype=jnp.float32)[None, :]
        img = 0.3 + 0.2 * jnp.sin(xx / w * 2 * jnp.pi) * jnp.cos(yy / h * jnp.pi)

        def add(img, s):
            y, x, a, g = s
            return img + a * jnp.exp(-((yy - y) ** 2 + (xx - x) ** 2)
                                     / (2 * g * g)), None

        img, _ = jax.lax.scan(add, img, suns)
        theta = (yy + 0.5) / h * jnp.pi
        return img * jnp.sin(theta)

    return image(suns)
