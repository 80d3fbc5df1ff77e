"""The phase scopes of ``make_train_step`` (``training.PHASE_SCOPES``): every
scope that applies reaches the compiled module's ``op_name``s, the backward
pass shows as the transposed spelling of ``apex.forward``, and the scopes
change no program."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import training


@pytest.fixture(autouse=True)
def no_persistent_compile_cache():
    """The persistent cache's key leaves metadata out, so where a test of
    another file has enabled it in this worker (``apex_tpu.cache.enable``),
    the step compiled without scopes reads the scoped executable back and
    these tests fail by the order the files ran in."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _loss_fn(p, batch):
    h = jnp.tanh(batch["x"].astype(p["w1"].dtype) @ p["w1"])
    return jnp.mean((h @ p["w2"]).astype(jnp.float32) ** 2)


def tiny_step(opt_level="O2", accum_steps=1, axis_name=None, check_vma=True):
    """``(jitted step, state, batch)`` of a two-matmul model; with
    ``axis_name`` the step runs under a one-device ``shard_map``."""
    init_fn, step_fn = training.make_train_step(
        _loss_fn, training.adam(1e-3), opt_level=opt_level,
        loss_scale="dynamic", accum_steps=accum_steps, axis_name=axis_name)
    state = init_fn({"w1": jnp.ones((16, 32)), "w2": jnp.ones((32, 8))})
    if axis_name is not None:
        mesh = Mesh(np.array(jax.devices()[:1]), (axis_name,))
        step_fn = jax.shard_map(step_fn, mesh=mesh,
                                in_specs=(P(), P(axis_name)), out_specs=P(),
                                check_vma=check_vma)
    return jax.jit(step_fn), state, {"x": jnp.ones((4, 16))}


def test_scope_names_are_the_documented_vocabulary():
    assert training.PHASE_SCOPES == (
        "apex.cast", "apex.forward", "apex.allreduce", "apex.scaler",
        "apex.optimizer", "apex.metrics")


@pytest.mark.parametrize("axis_name", [None, "data"])
@pytest.mark.parametrize("accum_steps", [1, 2])
@pytest.mark.parametrize("opt_level", ["O0", "O2"])
def test_scopes_reach_the_compiled_step_and_change_no_program(
        opt_level, accum_steps, axis_name, monkeypatch):
    # the microbatch scan does not trace under shard_map's vma checking, so
    # that case runs without it; which is also where reduce_gradients has to
    # issue the all-reduce itself
    vma = accum_steps == 1
    build = lambda: tiny_step(opt_level, accum_steps, axis_name, vma)
    step, state, batch = build()
    lowered = step.lower(state, batch)
    compiled = lowered.compile().as_text()
    expected = ["/jvp(apex.forward)/", "/transpose(jvp(apex.forward))/",
                "/apex.scaler/", "/apex.optimizer/"]
    if opt_level == "O2":
        expected.append("apex.cast)/" if accum_steps == 1 else "/apex.cast/")
    if accum_steps == 2:
        expected.append("/while/body/closed_call/jvp(apex.forward)/")
    if axis_name is not None and vma:
        # the gradient all-reduce is autodiff's (the transpose of the
        # replicated parameters' broadcast): it shows in the backward pass
        # and reduce_gradients has nothing left to issue but the average
        expected += ["/transpose(jvp(apex.forward))/psum_invariant",
                     "/apex.metrics/"]
    elif axis_name is not None:
        expected.append("/apex.allreduce/psum")
    for scope in expected:
        assert scope in compiled, f"{scope} is not in the compiled step"
    if axis_name is None or vma:
        assert "apex.allreduce" not in compiled

    # scopes are metadata: the lowered program (printed without debug info,
    # so without them) is the same, byte for byte, when they are off
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, state, batch = build()
    bare = bare.lower(state, batch)
    assert "apex." not in bare.compile().as_text()
    assert bare.as_text() == lowered.as_text()
