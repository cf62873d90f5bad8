"""The one traffic generator: every mix is a data file of parameters in
``bench/traffic/<name>.json``, read here. Inputs depend on the seed and the
ring slot alone, never on timing, so every seed gives the same amount of
work in another order.

Keys of a mix:

* ``draws_per_step`` -- draws resolved by one closed-loop step;
* ``points`` -- ``prng`` (1-D uniforms made on the device by threefry) or
  ``qmc2d`` (2-D base-2 QMC points on the host, as a renderer hands them
  in); a system refuses a kind it cannot take;
* ``ring`` -- how many distinct input batches a run cycles through; step
  ``s`` uses slot ``s % ring``;
* ``reweight_sigma`` (optional) -- every step hands in new weights: each
  count times ``exp(sigma * z)``, ``z`` standard normal per row and slot;
* ``kept_steps`` -- steps whose answers are kept, by seeded reservoir
  sampling, for the comparison with the reference after the window.
"""
from __future__ import annotations

import numpy as np

QMC_BITS = 24
_MASK = np.uint32((1 << QMC_BITS) - 1)


def seed_words(seed: int, *tags: int) -> np.ndarray:
    """Two uint32 words from any whole seed (also beyond 32 bits) and tags."""
    return np.random.SeedSequence([int(seed) & (2**64 - 1), *tags]).generate_state(
        2, np.uint32)


def key(seed: int, *tags: int):
    """A threefry key for (seed, *tags)."""
    import jax

    return jax.random.wrap_key_data(seed_words(seed, *tags), impl="threefry2x32")


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) & (2**64 - 1), *tags]))


def uniform_ring(seed: int, ring: int, n: int) -> list:
    """``ring`` device batches of ``n`` float32 uniforms in [0, 1), one
    jitted call (slot ``k`` from key (seed, 1, k))."""
    import jax
    import jax.numpy as jnp

    keys = jnp.stack([key(seed, 1, k) for k in range(ring)])
    batch = jax.jit(jax.vmap(
        lambda k: jax.random.uniform(k, (n,), jnp.float32)))(keys)
    return [batch[k] for k in range(ring)]


def drift_ring(base, seed: int, ring: int, sigma: float, power: float) -> list:
    """``ring`` reweighted vectors ``(base * exp(sigma * z_k)) ** power`` on
    the device, one jitted call (``z_k`` from key (seed, 2, k))."""
    import jax
    import jax.numpy as jnp

    keys = jnp.stack([key(seed, 2, k) for k in range(ring)])

    def one(k):
        z = jax.random.normal(k, base.shape, jnp.float32)
        return (base * jnp.exp(jnp.float32(sigma) * z)) ** jnp.float32(power)

    batch = jax.jit(jax.vmap(one))(keys)
    return [batch[k] for k in range(ring)]


def reverse_bits32(i: np.ndarray) -> np.ndarray:
    b = np.asarray(i, np.uint32).copy()
    for mask, s in ((0x55555555, 1), (0x33333333, 2), (0x0F0F0F0F, 4),
                    (0x00FF00FF, 8)):
        m, s = np.uint32(mask), np.uint32(s)
        b = ((b & m) << s) | ((b >> s) & m)
    return (b << np.uint32(16)) | (b >> np.uint32(16))


def _sobol2_v24() -> np.ndarray:
    """Sobol' dimension-1 direction numbers (x + 1, m = 1) on 24 bits."""
    v = np.zeros(32, np.uint64)
    v[0] = np.uint64(1 << 31)
    for k in range(1, 32):
        v[k] = v[k - 1] ^ (v[k - 1] >> np.uint64(1))
    return (v >> np.uint64(32 - QMC_BITS)).astype(np.uint32)


def qmc2d(n: int, offset_u: int, offset_v: int) -> np.ndarray:
    """``n`` 2-D points (van der Corput, Sobol' dim 1) with a 24-bit
    Cranley-Patterson rotation: exact float32 pairs in [0, 1)^2, (n, 2)."""
    c = np.arange(n, dtype=np.uint32)
    u = ((reverse_bits32(c) >> np.uint32(8)) + np.uint32(offset_u)) & _MASK
    v = np.zeros(n, np.uint32)
    for k, d in enumerate(_sobol2_v24()):
        v ^= ((c >> np.uint32(k)) & np.uint32(1)) * d
    v = (v + np.uint32(offset_v)) & _MASK
    scale = np.float32(2.0 ** -QMC_BITS)
    return np.stack([u.astype(np.float32) * scale,
                     v.astype(np.float32) * scale], axis=1)


def qmc2d_ring(seed: int, ring: int, n: int) -> list[np.ndarray]:
    """``ring`` host frames of ``n`` rotated 2-D QMC points; frame ``k``
    rotates by offsets drawn from (seed, 3, k)."""
    out = []
    for k in range(ring):
        off = rng(seed, 3, k).integers(0, 1 << QMC_BITS, 2)
        out.append(qmc2d(n, int(off[0]), int(off[1])))
    return out


class Reservoir:
    """Seeded reservoir sample of ``size`` steps' answers out of a window of
    unknown length: every step is kept with the same chance."""

    def __init__(self, size: int, seed: int):
        self.size, self.items, self.seen = size, [], 0
        self._rng = rng(seed, 4)

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self._rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1
