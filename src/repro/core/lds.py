"""Low-discrepancy sequence generators (Hammersley, Halton, Sobol', van der Corput).

Used to reproduce the paper's QMC experiments (Figs. 1, 7, 8, 9): warping a
low-discrepancy sequence through the *monotone* inverse CDF preserves
uniformity properties in warped space; warping through the Alias Method does
not. Also used by the serving layer for per-slot QMC token-sampling streams.

The serving streams run in 24-bit fixed point (:func:`qmc_bits24_np` /
:func:`qmc_bits24`): counter -> bit-reversed 24-bit radical inverse ->
Cranley-Patterson rotation as an *integer* add mod 2^24 -> exact float32.
Every step is exact integer arithmetic plus one exact int->float conversion,
so the host oracle (numpy), the jnp device twin, and the Pallas drain kernel
produce bit-identical points by construction — no float-rounding argument
required.
"""
from __future__ import annotations

import numpy as np

_PRIMES = np.array(
    [2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47, 53], np.int64
)

# Sobol' direction numbers (Joe & Kuo, new-joe-kuo-6) for dimensions 1..16.
# Dim 0 is van der Corput in base 2. Entries: (s, a, m_i ...). Indexing is
# strict: asking for a dimension past the table raises instead of silently
# recycling a polynomial (recycling makes the recycled pair's columns
# *identical*, degenerating every 2D projection that spans them).
_SOBOL_POLY = [
    (1, 0, [1]),
    (2, 1, [1, 3]),
    (3, 1, [1, 3, 1]),
    (3, 2, [1, 1, 1]),
    (4, 1, [1, 1, 3, 3]),
    (4, 4, [1, 3, 5, 13]),
    (5, 2, [1, 1, 5, 5, 17]),
    (5, 4, [1, 1, 5, 5, 5]),
    (5, 7, [1, 1, 7, 11, 19]),
    (5, 11, [1, 1, 5, 1, 1]),
    (5, 13, [1, 1, 1, 3, 11]),
    (5, 14, [1, 3, 5, 5, 31]),
    (6, 1, [1, 3, 3, 9, 7, 49]),
    (6, 13, [1, 1, 1, 15, 21, 21]),
    (6, 16, [1, 3, 1, 13, 27, 49]),
    (6, 19, [1, 1, 1, 15, 7, 5]),
]

SOBOL_MAX_DIMS = len(_SOBOL_POLY) + 1  # + dim 0 (van der Corput)

QMC_BITS = 24                  # fixed-point resolution of the stream points
QMC_SCALE = np.float32(2.0 ** -QMC_BITS)
_QMC_MASK = np.uint32((1 << QMC_BITS) - 1)


def reverse_bits32_np(i: np.ndarray) -> np.ndarray:
    """Bit-reverse uint32 values (numpy)."""
    b = np.asarray(i, np.uint32).copy()
    b = ((b & np.uint32(0x55555555)) << np.uint32(1)) | ((b & np.uint32(0xAAAAAAAA)) >> np.uint32(1))
    b = ((b & np.uint32(0x33333333)) << np.uint32(2)) | ((b & np.uint32(0xCCCCCCCC)) >> np.uint32(2))
    b = ((b & np.uint32(0x0F0F0F0F)) << np.uint32(4)) | ((b & np.uint32(0xF0F0F0F0)) >> np.uint32(4))
    b = ((b & np.uint32(0x00FF00FF)) << np.uint32(8)) | ((b & np.uint32(0xFF00FF00)) >> np.uint32(8))
    return (b << np.uint32(16)) | (b >> np.uint32(16))


def qmc_bits24_np(counter: np.ndarray, offset_bits: np.ndarray) -> np.ndarray:
    """Counter -> rotated 24-bit stream point (integer form, numpy host side).

    ``reverse_bits32 >> 8`` is the base-2 radical inverse in units of 2^-24;
    the Cranley-Patterson rotation is an integer add mod 2^24, so the whole
    pipeline is exact and bit-identical to the jnp/Pallas twins."""
    rev = reverse_bits32_np(counter) >> np.uint32(32 - QMC_BITS)
    return (rev + np.asarray(offset_bits, np.uint32)) & _QMC_MASK


def qmc_point_np(counter: np.ndarray, offset_bits: np.ndarray) -> np.ndarray:
    """Rotated stream point as exact float32 in [0, 1)."""
    return qmc_bits24_np(counter, offset_bits).astype(np.float32) * QMC_SCALE


def qmc_offset_bits_np(offsets01) -> np.ndarray:
    """Quantize [0,1) rotation offsets to the stream's 24-bit grid."""
    bits = (np.asarray(offsets01, np.float64) * (1 << QMC_BITS)).astype(np.uint32)
    return np.minimum(bits, _QMC_MASK)


def reverse_bits32(i):
    """Bit-reverse uint32 values (jnp twin of :func:`reverse_bits32_np`;
    also safe inside Pallas kernel bodies — shifts/masks only)."""
    import jax.numpy as jnp  # local: keep numpy-only callers jax-free

    b = jnp.asarray(i, jnp.uint32)
    b = ((b & jnp.uint32(0x55555555)) << 1) | ((b & jnp.uint32(0xAAAAAAAA)) >> 1)
    b = ((b & jnp.uint32(0x33333333)) << 2) | ((b & jnp.uint32(0xCCCCCCCC)) >> 2)
    b = ((b & jnp.uint32(0x0F0F0F0F)) << 4) | ((b & jnp.uint32(0xF0F0F0F0)) >> 4)
    b = ((b & jnp.uint32(0x00FF00FF)) << 8) | ((b & jnp.uint32(0xFF00FF00)) >> 8)
    return (b << 16) | (b >> 16)


def qmc_bits24(counter, offset_bits):
    """jnp twin of :func:`qmc_bits24_np` (identical integer pipeline)."""
    import jax.numpy as jnp

    rev = reverse_bits32(counter) >> (32 - QMC_BITS)
    return (rev + jnp.asarray(offset_bits, jnp.uint32)) & jnp.uint32(_QMC_MASK)


def qmc_point(counter, offset_bits):
    """jnp twin of :func:`qmc_point_np` (exact float32 in [0, 1)). The
    24-bit values convert exactly through int32, which Mosaic can cast to
    float32 (it has no uint32 -> float32 cast)."""
    import jax.numpy as jnp

    bits = qmc_bits24(counter, offset_bits).astype(jnp.int32)
    return bits.astype(jnp.float32) * QMC_SCALE


def _sobol2_v24() -> np.ndarray:
    """Sobol' dimension-1 direction numbers on the 24-bit stream grid."""
    return (_sobol_directions(1) >> np.uint64(32 - QMC_BITS)).astype(np.uint32)


def sobol2_bits24_np(counter: np.ndarray) -> np.ndarray:
    """Counter -> unrotated Sobol' dim-1 point in units of 2^-24 (numpy).

    Direct binary indexing (XOR of direction numbers for set counter bits);
    the pipeline is pure integer XOR/shift so the jnp twin
    (:func:`sobol2_bits24`) is bit-identical by construction. Together with
    the van der Corput u-dimension of :func:`qmc_bits24_np` (= Sobol' dim 0)
    this forms the exact 2-D Sobol' pair used by the spatial serving
    streams."""
    c = np.asarray(counter, np.uint32)
    v = _sobol2_v24()
    x = np.zeros(c.shape, np.uint32)
    for k in range(32):
        bit = (c >> np.uint32(k)) & np.uint32(1)
        x ^= bit * v[k]
    return x & _QMC_MASK


def qmc2_bits24_np(
    counter: np.ndarray, offset_u: np.ndarray, offset_v: np.ndarray
):
    """Counter -> rotated 2-D stream point (integer form, numpy host side).

    u is the base-2 radical inverse, v is Sobol' dim 1; each carries its own
    Cranley-Patterson rotation as an integer add mod 2^24, so host, jnp and
    kernel twins agree bit-for-bit."""
    u = qmc_bits24_np(counter, offset_u)
    v = (sobol2_bits24_np(counter) + np.asarray(offset_v, np.uint32)) & _QMC_MASK
    return u, v


def qmc2_point_np(
    counter: np.ndarray, offset_u: np.ndarray, offset_v: np.ndarray
):
    """Rotated 2-D stream point as exact float32 pairs in [0, 1)^2."""
    u, v = qmc2_bits24_np(counter, offset_u, offset_v)
    return u.astype(np.float32) * QMC_SCALE, v.astype(np.float32) * QMC_SCALE


def sobol2_bits24(counter):
    """jnp twin of :func:`sobol2_bits24_np` (identical integer pipeline)."""
    import jax.numpy as jnp

    c = jnp.asarray(counter, jnp.uint32)
    v = _sobol2_v24()
    x = jnp.zeros_like(c)
    for k in range(32):
        bit = (c >> jnp.uint32(k)) & jnp.uint32(1)
        x = x ^ bit * jnp.uint32(int(v[k]))
    return x & jnp.uint32(_QMC_MASK)


def qmc2_bits24(counter, offset_u, offset_v):
    """jnp twin of :func:`qmc2_bits24_np`."""
    import jax.numpy as jnp

    u = qmc_bits24(counter, offset_u)
    v = (sobol2_bits24(counter) + jnp.asarray(offset_v, jnp.uint32)) & jnp.uint32(
        _QMC_MASK
    )
    return u, v


def qmc2_point(counter, offset_u, offset_v):
    """jnp twin of :func:`qmc2_point_np` (exact float32 in [0, 1)^2)."""
    import jax.numpy as jnp

    u, v = qmc2_bits24(counter, offset_u, offset_v)
    return u.astype(jnp.float32) * QMC_SCALE, v.astype(jnp.float32) * QMC_SCALE


def radical_inverse_base2(i: np.ndarray) -> np.ndarray:
    """Van der Corput sequence in base 2 via 32-bit reversal (float32 exact)."""
    b = reverse_bits32_np(np.asarray(i, np.uint32))
    return (b >> np.uint32(8)).astype(np.float64) * (1.0 / (1 << 24))


def radical_inverse(i: np.ndarray, base: int) -> np.ndarray:
    """Van der Corput sequence in arbitrary integer base."""
    if base == 2:
        return radical_inverse_base2(i)
    i = np.asarray(i, np.int64).copy()
    inv = np.zeros(i.shape, np.float64)
    f = 1.0 / base
    while np.any(i > 0):
        inv += f * (i % base)
        i //= base
        f /= base
    return inv


def hammersley(n: int, dims: int = 2) -> np.ndarray:
    """The n-point Hammersley set in [0,1)^dims (first component = i/n)."""
    idx = np.arange(n, dtype=np.int64)
    cols = [idx.astype(np.float64) / n]
    for d in range(dims - 1):
        cols.append(radical_inverse(idx, int(_PRIMES[d])))
    return np.stack(cols, axis=-1)


def halton(n: int, dims: int = 2, start: int = 0) -> np.ndarray:
    idx = np.arange(start, start + n, dtype=np.int64)
    cols = [radical_inverse(idx, int(_PRIMES[d])) for d in range(dims)]
    return np.stack(cols, axis=-1)


def _sobol_directions(dim: int, bits: int = 32) -> np.ndarray:
    """Direction numbers v_k (as uint32 scaled by 2^32) for one dimension."""
    if dim == 0:
        return np.array([1 << (31 - k) for k in range(bits)], np.uint64)
    if dim - 1 >= len(_SOBOL_POLY):
        raise ValueError(
            f"sobol direction-number table covers dims <= {SOBOL_MAX_DIMS} "
            f"(got dimension index {dim}); recycling polynomials would make "
            f"dimensions {dim} and {((dim - 1) % len(_SOBOL_POLY)) + 1} "
            "identical — extend _SOBOL_POLY (Joe & Kuo) instead"
        )
    s, a, m = _SOBOL_POLY[dim - 1]
    m = list(m)
    v = np.zeros(bits, np.uint64)
    for k in range(s):
        v[k] = np.uint64(m[k]) << np.uint64(31 - k)
    for k in range(s, bits):
        vk = v[k - s] ^ (v[k - s] >> np.uint64(s))
        for j in range(1, s):
            if (a >> (s - 1 - j)) & 1:
                vk ^= v[k - j]
        v[k] = vk
    return v


def sobol(n: int, dims: int = 2, scramble_seed: int | None = None) -> np.ndarray:
    """First n points of the Sobol' sequence (graycode order), optional
    Owen-style digital shift (XOR scramble) per dimension. Supports up to
    ``SOBOL_MAX_DIMS`` dimensions; beyond that the direction-number table
    raises (recycled polynomials would duplicate columns)."""
    out = np.zeros((n, dims), np.float64)
    rng = np.random.default_rng(scramble_seed) if scramble_seed is not None else None
    idx = np.arange(n, dtype=np.uint64)
    gray = idx ^ (idx >> np.uint64(1))
    for d in range(dims):
        v = _sobol_directions(d)
        x = np.zeros(n, np.uint64)
        g = gray.copy()
        for k in range(32):
            bit = (g >> np.uint64(k)) & np.uint64(1)
            x ^= bit * v[k]
        if rng is not None:
            x ^= np.uint64(rng.integers(0, 1 << 32, dtype=np.uint64))
        out[:, d] = (x >> np.uint64(8)).astype(np.float64) * (1.0 / (1 << 24))
    return out


def uniform(n: int, dims: int = 2, seed: int = 0) -> np.ndarray:
    """Plain pseudo-random points — the MC baseline for QMC comparisons."""
    return np.random.default_rng(seed).random((n, dims))
