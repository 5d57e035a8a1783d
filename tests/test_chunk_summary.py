"""The chunk-summary kernel (``ops/chunk_summary.py``) interpreted
against its jnp form: float and int8 pools, scales as planes and as a
decode scan's lane rows, slots whose step ends no chunk."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aiko_services_tpu.models.llama import _kv_quantize
from aiko_services_tpu.ops.chunk_summary import (chunk_summary,
                                                 chunk_summary_reference)

BLOCKS, BLOCK, HEADS, HD = 9, 16, 8, 128


def _inputs(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(keys[0], (BLOCKS, BLOCK, HEADS, HD))
    v = jax.random.normal(keys[1], (BLOCKS, BLOCK, HEADS, HD))
    phi = jax.random.normal(keys[2], (HEADS, HD))
    mu = 0.2 * jax.random.normal(keys[3], (HEADS, HD))
    return k, v, phi, mu


@pytest.mark.parametrize("layout", ["float32", "bfloat16", "int8_planes",
                                    "int8_lane_rows"])
def test_kernel_is_the_jnp_form(layout):
    k, v, phi, mu = _inputs()
    block_ids = jnp.asarray([3, 0, 7, 1, 7], jnp.int32)
    ends = jnp.asarray([True, False, True, False, True])
    scale = HD ** -0.5
    if layout.startswith("int8"):
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        pool = {"k": kq, "v": vq, "ks": ks, "vs": vs}
        rows_k, rows_v = kq * ks[..., None], vq * vs[..., None]
        if layout == "int8_lane_rows":
            pool.update(ks=ks.reshape(-1, 128), vs=vs.reshape(-1, 128))
    else:
        pool = {"k": k.astype(layout), "v": v.astype(layout)}
        rows_k, rows_v = pool["k"], pool["v"]
    got_k, got_v = chunk_summary(pool, block_ids, ends, phi, mu,
                                 sm_scale=scale, interpret=True)
    want_k, want_v = chunk_summary_reference(
        rows_k[block_ids], rows_v[block_ids], phi, mu, scale)
    live = np.asarray(ends)
    assert got_k.shape == (5, HEADS, HD) and got_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_k)[live],
                               np.asarray(want_k)[live], atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_v)[live],
                               np.asarray(want_v)[live], atol=2e-5)
    # A slot whose step ends no chunk is given zeros, whatever it names.
    assert not np.asarray(got_k)[~live].any()
    assert not np.asarray(got_v)[~live].any()


def test_the_summary_weighs_rows_by_phi_and_adds_mu():
    k, v, phi, mu = _inputs(1)
    pooled_k, pooled_v = chunk_summary_reference(
        k[2], v[2], phi, mu, HD ** -0.5)
    flat_k, _ = chunk_summary_reference(k[2], v[2], 0 * phi, 0 * mu,
                                        HD ** -0.5)
    np.testing.assert_allclose(flat_k, k[2].mean(0), atol=1e-5)
    assert np.abs(np.asarray(pooled_k - mu) - np.asarray(flat_k)).max() \
        > 0.05
    assert pooled_v.shape == (HEADS, HD)
