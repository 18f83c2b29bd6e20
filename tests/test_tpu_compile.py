"""Chip-compiler tests: the main path's Pallas kernel, compiled by the
TPU's own compiler for a described (not attached) v5e at real widths.

The ONE file with such tests.  The topology is described inside a
module-scoped fixture — never at import, in a ``skipif`` or a
``parametrize`` argument — because only one process may hold the TPU
library: under pytest-xdist every worker imports this file, and only the
worker that runs it may load the library.  Each case compiles in the
test's own process, in about two seconds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again): keep the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (id, q shape [B,S,H,D], kv heads, dtype, causal, window)
_SHAPES = [
    ("gpt_small", (8, 1024, 12, 64), 12, jnp.bfloat16, True, None),
    ("head_dim_128", (2, 4096, 16, 128), 16, jnp.bfloat16, True, None),
    ("gqa_12_to_4", (8, 1024, 12, 64), 4, jnp.bfloat16, True, None),
    ("window_512_at_2048", (8, 2048, 12, 64), 12, jnp.bfloat16, True, 512),
    ("fp32", (8, 1024, 12, 64), 12, jnp.float32, True, None),
]


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize(
    "shape,kv_heads,dtype,causal,window",
    [s[1:] for s in _SHAPES], ids=[s[0] for s in _SHAPES],
)
def test_flash_attention_compiles_for_v5e(one_chip, shape, kv_heads, dtype,
                                          causal, window, direction):
    """interpret=False: the kernel the chip would run, default 512x256
    tiles, forward and backward, as a tpu_custom_call."""
    b, s, _, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, d), dtype, sharding=one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=False)

    if direction == "forward":
        fn = attend
    else:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attend(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
