import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, ops
from repro.kernels.medusa_transpose import (medusa_transpose_tiles,
                                            read_network_tiles)
from repro.kernels.rotator import barrel_rotate_groups
from repro.kernels.stream_matmul import stream_matmul
from repro.core.transpose import read_network_oracle


@pytest.mark.parametrize("r,c,w,tile", [
    (8, 8, 4, 8), (16, 32, 8, 8), (32, 32, 128, 16), (64, 8, 2, 8),
    (128, 128, 16, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_transpose_kernel_sweep(r, c, w, tile, dtype):
    x = jnp.arange(r * c * w).reshape(r, c, w).astype(dtype)
    out = medusa_transpose_tiles(x, tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.transpose_ref(x)))


@pytest.mark.parametrize("r,c,w", [(7, 13, 5), (100, 36, 3), (1, 9, 2),
                                   (129, 64, 1)])
def test_transpose_wrapper_padding(r, c, w):
    x = jax.random.normal(jax.random.PRNGKey(r * c), (r, c, w))
    np.testing.assert_allclose(np.asarray(ops.transpose_rc(x)),
                               np.asarray(ref.transpose_ref(x)))


@pytest.mark.parametrize("n,g,w", [(8, 4, 4), (16, 2, 8), (32, 1, 16)])
def test_read_network_kernel(n, g, w):
    lines = jax.random.normal(jax.random.PRNGKey(0), (g * n, n, w))
    np.testing.assert_allclose(
        np.asarray(read_network_tiles(lines, n, interpret=True)),
        np.asarray(read_network_oracle(lines, n)))


@pytest.mark.parametrize("n,w", [(8, 4), (16, 2), (64, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rotator_kernel(n, w, dtype):
    g = 5
    x = jax.random.normal(jax.random.PRNGKey(1), (g, n, w)).astype(dtype)
    amts = jnp.array([0, 1, n - 1, n, 3])
    out = barrel_rotate_groups(x, amts, interpret=True)
    for i in range(g):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            np.asarray(jnp.roll(x[i], -int(amts[i]) % n, axis=0)))


@pytest.mark.parametrize("m,k,n,dtype,tol", [
    (128, 128, 128, jnp.float32, 1e-5),
    (256, 384, 128, jnp.float32, 1e-5),
    (128, 256, 256, jnp.bfloat16, 2e-2)])
def test_stream_matmul(m, k, n, dtype, tol):
    x = jax.random.normal(jax.random.PRNGKey(2), (m, k)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n)).astype(dtype)
    out = stream_matmul(x, w, bm=128, bn=128, bk=128,
                        interpret=True)
    want = ref.matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_kv_line_to_port():
    kv = jax.random.normal(jax.random.PRNGKey(4), (32, 8, 16))
    np.testing.assert_allclose(np.asarray(ops.kv_line_to_port(kv)),
                               np.asarray(ref.kv_layout_ref(kv)))


def test_ops_fallback_routing():
    was = ops.kernels_enabled()
    try:
        ops.use_kernels(False)
        x = jax.random.normal(jax.random.PRNGKey(5), (6, 10, 3))
        np.testing.assert_allclose(np.asarray(ops.transpose_rc(x)),
                                   np.asarray(ref.transpose_ref(x)))
    finally:
        ops.use_kernels(was)
