"""Mosaic compiles the Medusa burst kernels for a TPU v5e chip.

The interpret-mode tests prove the kernel bodies bit-exact; these prove they
lower on the chip at the serving path's real widths — stablelm-1.6b, whose
32 KV heads make N = 32 ports, one ``[32, 64]`` bf16 frame per timestep (the
u32 fold carries it as 32 words).  Each case AOT-compiles one kernel with
``interpret=False`` for a described (not attached) ``v5e:2x2`` topology and
checks that the compiled program holds the Mosaic kernel
(``tpu_custom_call``): the decode step's fused gather and scatter bursts,
the admission wave's scatter, and the dense packed burst.

The topology is described inside a module fixture — never at import — so
that under several test workers only the worker running this file loads the
TPU compiler; where it cannot be described the cases skip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.medusa_transpose import (burst_network_tiles,
                                            gather_burst_network_tiles,
                                            scatter_burst_network_tiles)

N = 32                         # stablelm-1.6b: 32 KV heads = 32 ports
HEAD_DIM = 64
LAYERS = 24
POOL_FRAMES = 12 * 64          # 12 pages of 64 timesteps (4 requests x 160)
LIVE = 2048                    # the engine's live-plan bucket (N x page)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described chip's executables cannot be read back from the
    persistent cache, so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _word(dtype):
    """Frame width in words: bf16 rides raw (64 lanes) or folded in pairs
    into u32 (32 lanes) — ``word_fold="auto"``."""
    return HEAD_DIM * 2 // jnp.dtype(dtype).itemsize


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.uint32])
@pytest.mark.parametrize("kernel", ["burst", "gather", "scatter"])
def test_burst_kernel_compiles_for_v5e(kernel, dtype, one_chip,
                                       no_compile_cache):
    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    w = _word(dtype)
    lines = LAYERS * POOL_FRAMES
    k = LAYERS * LIVE
    if kernel == "burst":
        # a packed dense burst wider than one VMEM tile: word-tiled grid
        text = _compile(lambda x: burst_network_tiles(x, N, interpret=False),
                        sds((N, N, 4096)))
    elif kernel == "gather":
        text = _compile(
            lambda x, i: gather_burst_network_tiles(x, i, N,
                                                    interpret=False),
            sds((lines, N, w)), sds((k,), jnp.int32))
    else:
        text = _compile(
            lambda b, i, into: scatter_burst_network_tiles(
                b, i, into, N, interpret=False),
            sds((k // N, N, N, w)), sds((k,), jnp.int32),
            sds((lines, N, w)))
    assert "tpu_custom_call" in text
