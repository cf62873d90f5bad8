"""Compile every Pallas kernel that ``kernels.ops`` runs on a TPU, for a
described v5e chip, at the widths ``chip_smoke.py`` serves.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip that
is described and not attached, and refuses what the chip would refuse (a
lowering Mosaic lacks, a block that breaks the tiling, more VMEM than a
kernel may use). Each test asserts the kernel reached the program as a
``tpu_custom_call``. The topology is described inside a fixture, never at
import: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.alias_build import alias_build_batched
from repro.kernels.cdf_scan import cdf_scan
from repro.kernels.forest_delta import forest_delta, forest_delta_update
from repro.kernels.sample_tiled import sample_rows

VOCAB = 151936   # qwen1.5-0.5b, the smoke's served vocabulary
N = 1 << 22      # the smoke's forest size
M = 1 << 20      # its guide cells


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache, topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_cdf_scan_softmax_compiles(one_chip, rows, dtype):
    text = _compiled_text(
        one_chip, lambda x: cdf_scan(x, softmax=True, interpret=False),
        ((rows, VOCAB), dtype),
    )
    assert "tpu_custom_call" in text


def test_cdf_scan_raw_rows_compile(one_chip):
    """The raw row scan of the distributed CDF build: 64 chunk rows of n."""
    text = _compiled_text(
        one_chip,
        lambda x: cdf_scan(x, softmax=False, normalize=False, interpret=False),
        ((64, N // 64), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [1, 8])
def test_sample_rows_compiles(one_chip, rows):
    text = _compiled_text(
        one_chip, lambda c, u: sample_rows(c, u, interpret=False),
        ((rows, VOCAB), jnp.float32), ((rows, 1), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [16, 1024, 4096])
def test_alias_build_batched_compiles(one_chip, n):
    text = _compiled_text(
        one_chip, lambda w: alias_build_batched(w, interpret=False),
        ((64, n), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_forest_delta_compiles(one_chip):
    text = _compiled_text(
        one_chip, lambda d: forest_delta(d, M, interpret=False),
        ((N,), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [16, 4096, N])
def test_forest_delta_update_compiles(one_chip, n):
    text = _compiled_text(
        one_chip,
        lambda a, b: forest_delta_update(a, b, max(n // 4, 1), interpret=False),
        ((n,), jnp.float32), ((n,), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_every_tpu_kernel_is_covered():
    """Each op the policy compiles on a TPU has a compile test above."""
    covered = {"fused_cdf", "row_cumsum", "sample_rows", "alias_build_batched",
               "forest_delta", "forest_delta_update", "flash_attention"}
    assert set(ops.OPS) - ops.XLA_ONLY == covered


@pytest.mark.parametrize("S,H,KV,hd", [(128, 16, 16, 64), (512, 8, 2, 128)])
def test_flash_attention_compiles(one_chip, S, H, KV, hd):
    from repro.kernels.flash_attention import flash_attention

    text = _compiled_text(
        one_chip,
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        ((1, S, H, hd), jnp.bfloat16), ((1, S, KV, hd), jnp.bfloat16),
        ((1, S, KV, hd), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text
