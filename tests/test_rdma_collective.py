"""Raw-RDMA ring collective matmuls: interpret-mode validation on the
virtual 8-device CPU mesh — exact against the dense oracle AND the
shard_map+ppermute twins (same contract, different transport)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from aiko_services_tpu.parallel.collective_matmul import (
    allgather_matmul_sharded, matmul_reducescatter_sharded,
)
from aiko_services_tpu.parallel.rdma_collective import (
    rdma_allgather_matmul_sharded, rdma_matmul_reducescatter_sharded,
)


@pytest.fixture(scope="module")
def mesh():
    devices = np.array(jax.devices()[:8])
    return Mesh(devices, ("tp",))


def test_rdma_allgather_matmul_exact(mesh):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
    out = rdma_allgather_matmul_sharded(x, w, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)
    twin = allgather_matmul_sharded(x, w, mesh)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(twin))


def test_rdma_matmul_reducescatter_exact(mesh):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 40)), jnp.float32)
    out = rdma_matmul_reducescatter_sharded(x, w, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)


def test_rdma_bf16_blocks(mesh):
    """bf16 activations with f32 accumulation — the serving dtype."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((32, 16)), jnp.bfloat16)
    out = rdma_allgather_matmul_sharded(x, w, mesh)
    oracle = (x.astype(jnp.float32) @ w.astype(jnp.float32)) \
        .astype(jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(oracle, np.float32),
        rtol=2e-2, atol=2e-2)


def test_rdma_hardware_gate():
    """interpret=False must refuse to dispatch off-hardware, loudly:
    a single chip (or the CPU) has no neighbor to RDMA to."""
    devices = np.array(jax.devices()[:8])
    mesh = Mesh(devices, ("tp",))
    x = jnp.zeros((8, 16), jnp.float32)
    w = jnp.zeros((16, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="multi-chip"):
        rdma_allgather_matmul_sharded(x, w, mesh, interpret=False)
