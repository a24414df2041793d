"""Compile the paged-attention kernels for a described TPU v5e.

Interpret mode evaluates a kernel's body in Python and accepts block shapes
the TPU compiler refuses (the (8, 128) tiling rule, unprovable alignment of
a dynamic offset). These tests hand the kernels to the real Mosaic compiler
for a ``v5e:2x2`` topology that is described, not attached, so a kernel the
chip would refuse fails here on the CPU. Nothing runs: only shapes go in.

The topology is described inside a module fixture (never at import), since
only one process at a time may load the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_multi)

# (H, Hkv, Dh, L): smollm-360m's attention widths (15/5 heads, Dh 64, 32
# layers), and a Dh=128 GQA shape (32/8 heads) of the llama-3 family
SHAPES = {"smollm-360m": (15, 5, 64, 32), "gqa-dh128": (32, 8, 128, 4)}
B, BLOCK_SIZE, N_PAGES, NUM_BLOCKS = 8, 16, 64, 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _operands(sharding, shape, q_len, b=B, n_pages=N_PAGES,
              num_blocks=NUM_BLOCKS):
    H, Hkv, Dh, L = shape

    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=sharding)

    q_shape = (b, H, Dh) if q_len is None else (b, q_len, H, Dh)
    pool = sds((num_blocks + 1, BLOCK_SIZE, L, Hkv, Dh), jnp.bfloat16)
    return (sds(q_shape, jnp.bfloat16), pool, pool,
            sds((b, n_pages), jnp.int32), sds((b,), jnp.int32),
            sds((), jnp.int32))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_paged_attention_compiles_for_v5e(one_chip, shape):
    args = _operands(one_chip, SHAPES[shape], q_len=None)
    compiled = paged_attention.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("q_len", [1, 4])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_paged_attention_multi_compiles_for_v5e(one_chip, shape, q_len):
    args = _operands(one_chip, SHAPES[shape], q_len=q_len)
    compiled = paged_attention_multi.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_at_serve_chat_shape(one_chip):
    """The serving benchmark's decode call: 32 slots, a 128-page table and a
    4,608-block pool at smollm-360m widths. The kernel's custom call keeps
    ``paged_attention`` in its op name, which the benchmark's trace
    reduction matches to time the kernel."""
    args = _operands(one_chip, SHAPES["smollm-360m"], q_len=None, b=32,
                     n_pages=128, num_blocks=4608)
    text = paged_attention.lower(*args, interpret=False).compile().as_text()
    calls = [ln.split("=")[0].strip() for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert calls and all("paged_attention" in c for c in calls), calls
