"""SPMD pipeline parallelism vs the sequential oracle (fwd + grads).

Beyond-parity (reference is DP-only): the collective-permute pipeline of
``apex_tpu/parallel/pipeline.py`` on a 4-stage virtual CPU mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.pipeline import spmd_pipeline, stack_stage_params

S = 4          # stages
M = 4          # microbatches
D = 16


@pytest.fixture
def pp_mesh():
    return Mesh(np.array(jax.devices("cpu")[:S]), ("pp",))


def _stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _params():
    rng = np.random.RandomState(0)
    return [{"w": jnp.asarray(rng.randn(D, D) * 0.3, jnp.float32),
             "b": jnp.asarray(rng.randn(D) * 0.1, jnp.float32)}
            for _ in range(S)]


def _sequential(per_stage, x):
    for p in per_stage:
        x = _stage_fn(p, x)
    return x


def test_pipeline_forward_matches_sequential(pp_mesh):
    per_stage = _params()
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(np.random.RandomState(1).randn(8, D), jnp.float32)

    y = jax.jit(shard_map(
        lambda sp, x: spmd_pipeline(_stage_fn, sp, x, axis_name="pp",
                                    num_microbatches=M),
        mesh=pp_mesh, in_specs=(P("pp"), P()), out_specs=P()))(stacked, x)
    ref = _sequential(per_stage, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_pipeline_grads_match_sequential(pp_mesh):
    per_stage = _params()
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(np.random.RandomState(2).randn(8, D), jnp.float32)

    def loss_pipe(sp, x):
        y = spmd_pipeline(_stage_fn, sp, x, axis_name="pp",
                          num_microbatches=M)
        return jnp.mean(y ** 2)

    def run(sp, x):
        return jax.grad(loss_pipe, argnums=(0, 1))(sp, x)

    gs, gx = jax.jit(shard_map(
        run, mesh=pp_mesh, in_specs=(P("pp"), P()),
        out_specs=(P("pp"), P())))(stacked, x)

    def loss_seq(per_stage, x):
        return jnp.mean(_sequential(per_stage, x) ** 2)

    rs, rx = jax.grad(loss_seq, argnums=(0, 1))(per_stage, x)
    rs_stacked = stack_stage_params(rs)
    for a, b in zip(jax.tree_util.tree_leaves(gs),
                    jax.tree_util.tree_leaves(rs_stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_rejects_indivisible_microbatches(pp_mesh):
    stacked = stack_stage_params(_params())
    x = jnp.ones((6, D), jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(shard_map(
            lambda sp, x: spmd_pipeline(_stage_fn, sp, x, axis_name="pp",
                                        num_microbatches=4),
            mesh=pp_mesh, in_specs=(P("pp"), P()), out_specs=P()))(stacked, x)


def test_pipeline_microbatch_count_invariance(pp_mesh):
    """M=2 and M=8 produce identical results (schedule-independence)."""
    per_stage = _params()
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(np.random.RandomState(3).randn(8, D), jnp.float32)

    def run(m):
        return jax.jit(shard_map(
            lambda sp, x: spmd_pipeline(_stage_fn, sp, x, axis_name="pp",
                                        num_microbatches=m),
            mesh=pp_mesh, in_specs=(P("pp"), P()), out_specs=P()))(stacked, x)

    np.testing.assert_allclose(np.asarray(run(2)), np.asarray(run(8)),
                               atol=1e-6, rtol=1e-6)


# -- interleaved (circular) schedule ------------------------------------------

from apex_tpu.parallel.pipeline import (spmd_pipeline_interleaved,
                                        stack_interleaved_stage_params)

V = 2          # chunks per rank -> S * V virtual stages


def _params_n(n, seed=0):
    rng = np.random.RandomState(seed)
    return [{"w": jnp.asarray(rng.randn(D, D) * 0.3, jnp.float32),
             "b": jnp.asarray(rng.randn(D) * 0.1, jnp.float32)}
            for _ in range(n)]


@pytest.mark.parametrize("m", [S, 2 * S])
def test_interleaved_forward_matches_sequential(pp_mesh, m):
    per_stage = _params_n(S * V)
    stacked = stack_interleaved_stage_params(per_stage, S)   # [V, S, ...]
    x = jnp.asarray(np.random.RandomState(1).randn(2 * m, D), jnp.float32)

    y = jax.jit(shard_map(
        lambda sp, x: spmd_pipeline_interleaved(
            _stage_fn, sp, x, axis_name="pp", num_microbatches=m),
        mesh=pp_mesh, in_specs=(P(None, "pp"), P()), out_specs=P()))(
            stacked, x)
    ref = _sequential(per_stage, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_interleaved_grads_match_sequential(pp_mesh):
    per_stage = _params_n(S * V, seed=3)
    stacked = stack_interleaved_stage_params(per_stage, S)
    x = jnp.asarray(np.random.RandomState(2).randn(8, D), jnp.float32)
    y_tgt = jnp.asarray(np.random.RandomState(4).randn(8, D), jnp.float32)

    def loss_pipe(sp, x):
        f = shard_map(
            lambda sp, x: spmd_pipeline_interleaved(
                _stage_fn, sp, x, axis_name="pp", num_microbatches=M),
            mesh=pp_mesh, in_specs=(P(None, "pp"), P()), out_specs=P())
        return jnp.mean((f(sp, x) - y_tgt) ** 2)

    def loss_seq(per, x):
        return jnp.mean((_sequential(per, x) - y_tgt) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(stacked, x)
    g_seq = jax.grad(loss_seq)(per_stage, x)
    g_seq_stacked = stack_interleaved_stage_params(g_seq, S)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq_stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def _scan_lengths(jaxpr):
    """All `scan` lengths found recursively in a (closed) jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(int(eqn.params["length"]))
        for p in eqn.params.values():
            if hasattr(p, "eqns"):                 # raw Jaxpr (shard_map)
                found.extend(_scan_lengths(p))
            elif hasattr(p, "jaxpr"):              # ClosedJaxpr (pjit, scan)
                found.extend(_scan_lengths(p.jaxpr))
    return found


def test_interleaved_tick_economics(pp_mesh):
    """The schedule property the variant exists for: m*v + p - 1 ticks of
    1/v-stage work vs GPipe's m + p - 1 ticks of full-stage work — the
    interleaved bubble is (p-1)/v full-stage units, a v-fold reduction.
    The tick counts are read from the TRACED programs' scan lengths, so a
    schedule regression (e.g. dropped drain ticks) fails here."""
    p, v, m = S, V, 2 * S
    x = jnp.zeros((2 * m, D), jnp.float32)

    stacked_i = stack_interleaved_stage_params(_params_n(p * v), p)
    jx_i = jax.make_jaxpr(shard_map(
        lambda sp, x: spmd_pipeline_interleaved(
            _stage_fn, sp, x, axis_name="pp", num_microbatches=m),
        mesh=pp_mesh, in_specs=(P(None, "pp"), P()), out_specs=P()))(
            stacked_i, x)
    stacked_g = stack_stage_params(_params_n(p))
    jx_g = jax.make_jaxpr(shard_map(
        lambda sp, x: spmd_pipeline(
            _stage_fn, sp, x, axis_name="pp", num_microbatches=m),
        mesh=pp_mesh, in_specs=(P("pp"), P()), out_specs=P()))(stacked_g, x)

    inter_ticks = m * v + p - 1
    gpipe_ticks = m + p - 1
    assert inter_ticks in _scan_lengths(jx_i.jaxpr)
    assert gpipe_ticks in _scan_lengths(jx_g.jaxpr)
    # wall in virtual-stage units (one gpipe tick = v virtual stages):
    # bubble interleaved (p-1), gpipe (p-1)*v
    assert inter_ticks - m * v == p - 1
    assert gpipe_ticks * v - m * v == (p - 1) * v


def test_interleaved_rejects_partial_groups(pp_mesh):
    per_stage = _params_n(S * V)
    stacked = stack_interleaved_stage_params(per_stage, S)
    x = jnp.asarray(np.random.RandomState(1).randn(6, D), jnp.float32)
    with pytest.raises(ValueError, match="multiple of the"):
        jax.jit(shard_map(
            lambda sp, x: spmd_pipeline_interleaved(
                _stage_fn, sp, x, axis_name="pp", num_microbatches=6),
            mesh=pp_mesh, in_specs=(P(None, "pp"), P()), out_specs=P()))(
                stacked, x)


def test_stack_interleaved_layout():
    per_stage = _params_n(S * V)
    stacked = stack_interleaved_stage_params(per_stage, S)
    w = jax.tree_util.tree_leaves(stacked)[0]
    assert w.shape[:2] == (V, S)
    # virtual stage s = c*p + r lives at [c, r]
    np.testing.assert_array_equal(
        np.asarray(stacked["b"][1, 2]), np.asarray(per_stage[1 * S + 2]["b"]))
