"""The serving kernels compile for a TPU v5e chip at yi-6b widths.

Each test lowers one Pallas kernel with ``interpret=False`` for a v5e chip
that is described, not attached, and compiles it with the TPU compiler:
what Mosaic refuses (block shapes off the tiling, too much VMEM) fails here
at no chip time.  Nothing runs, so these say nothing about results.

The topology is described inside a fixture, never while a module is being
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (flash_attention_kernel,
                                           flash_decode_kernel)
from repro.kernels.quant_matmul import choose_blocks, quant_matmul_kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_yi6b_mlp(chip, x_dtype):
    """Decode-sized rows against yi-6b's (4096, 11008) int8 MLP weight."""
    M, K, N = 16, 4096, 11008            # N is a multiple of the 256 block
    blocks = choose_blocks(M, K, N, x_dtype)
    _compile(lambda x, c, s: quant_matmul_kernel(x, c, s, blocks=blocks),
             chip((M, K), x_dtype), chip((K, N), jnp.int8),
             chip((1, 1), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_yi6b_prefill(chip, dtype):
    """32 heads x 2048 tokens x head_dim 128, causal."""
    x = chip((32, 2048, 128), dtype)
    _compile(lambda q, k, v: flash_attention_kernel(q, k, v, causal=True),
             x, x, x)


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_yi6b_paged(chip, pool_dtype):
    """4 slots x 4 KV heads x 8 queries per KV head, 16-token pages."""
    B, KV, G, hd, page, n_pmax = 4, 4, 8, 128, 16, 64
    pool = chip((B * n_pmax, page, KV, hd), pool_dtype)
    compiled = _compile(
        flash_decode_kernel, chip((B, KV, G, hd), jnp.float32), pool, pool,
        chip((B, n_pmax), jnp.int32), chip((B,), jnp.int32))
    # the K/V pools are read through VMEM blocks, never copied whole
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
