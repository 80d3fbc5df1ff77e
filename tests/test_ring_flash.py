"""Ring-flash attention (Pallas kernels inside sequence parallelism) vs
the jnp ring and the single-device oracle — fwd + grads, causal and full.

Runs the kernels in interpret mode on the CPU mesh (same code path as on
chip minus Mosaic lowering); the on-chip counterpart is the `tpu`-marked
test in test_pallas_tpu.py.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.ops.attention import dot_product_attention
from apex_tpu.parallel.ring_attention import _ring_flash

N = 4          # ring size
B, T, H, D = 1, 512, 2, 32      # global seq 512 -> 128 per shard


@pytest.fixture
def sp_mesh():
    return Mesh(np.array(jax.devices("cpu")[:N]), ("sp",))


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
                 for _ in range(3))


def _run_ring_flash(mesh, q, k, v, causal):
    """Drive the head-major core with interpret=True under shard_map.

    check_vma=False throughout: the interpret-mode pallas evaluator
    rejects rank-varying SMEM scalar operands (the dynamic ring offsets)
    under vma tracking — a tracker limitation whose error message says to
    use exactly this workaround.  Numerics are asserted vs the oracle.
    """
    def fn(q, k, v):
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        out = _ring_flash(qt, kt, vt, "sp", causal, D ** -0.5, 128, 128,
                          True)
        return out.transpose(0, 2, 1, 3)

    return jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_forward_matches_oracle(sp_mesh, causal):
    q, k, v = _qkv()
    out = _run_ring_flash(sp_mesh, q, k, v, causal)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_grads_match_oracle(sp_mesh, causal):
    q, k, v = _qkv(1)

    def loss_ring(q, k, v):
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        out = _ring_flash(qt, kt, vt, "sp", causal, D ** -0.5, 128, 128,
                          True)
        # per-rank partial sums add up to the global sum through
        # shard_map's transpose, so grads match the dense loss exactly
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def run(q, k, v):
        return jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)

    # check_vma=False: the interpret-mode pallas evaluator rejects
    # rank-varying SMEM scalar operands (the dynamic ring offsets) under
    # vma tracking — a tracker limitation the error message itself says to
    # work around this way.  Numerics are asserted against the dense
    # oracle below either way.
    g = jax.jit(shard_map(
        run, mesh=sp_mesh,
        in_specs=(P(None, "sp"),) * 3,
        out_specs=(P(None, "sp"),) * 3,
        check_vma=False))(q, k, v)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(
            dot_product_attention(q, k, v, causal=causal)
            .astype(jnp.float32)))

    r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g, r):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_ring_flash_public_fallback_off_tpu(sp_mesh):
    """ring_flash_attention off-TPU (no interpret) silently runs the jnp
    ring path with the same numerics."""
    from apex_tpu.parallel import ring_flash_attention

    q, k, v = _qkv(2)

    def fn(q, k, v):
        return ring_flash_attention(q, k, v, "sp", causal=True)

    out = jax.jit(shard_map(
        fn, mesh=sp_mesh,
        in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp")))(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
