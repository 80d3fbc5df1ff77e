"""Custom-VJP ops under ``shard_map`` data parallelism (ISSUE 21).

The class of break: a parameterised custom-VJP op whose backward rule
returns a per-shard cotangent for a *replicated* parameter.  jax rejects
that under the default ``check_vma=True`` ("Custom VJP bwd rule must
produce an output with the same type as the args tuple"), which took
the flagship imagenet step down with its default flags while every
single-device test stayed green.  Each op here is differentiated w.r.t.
its replicated parameters under ``in_specs=(P(), P("data"))``: on the
jnp path the gradient must arrive already summed (``out_specs=P()``) and
equal the single-device one; on the kernel path (the ops that have one)
the same trace must type check.  The flagship step itself is lowered
with its default flags.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.normalization import bn_relu_residual, fused_layer_norm_affine
from apex_tpu.quant.kernels import quantized_matmul

NDEV = 2
ROWS, C = 16, 128


def _rand(seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


def _layer_norm(kw):
    def loss(p, x):
        out = fused_layer_norm_affine(x, p["w"], p["b"], (C,), **kw)
        return jnp.sum(out ** 2)
    return loss, {"w": _rand(1, C) + 1.0, "b": _rand(2, C)}, _rand(0, ROWS, C)


def _bn(kw):
    """One implementation (jnp under a custom VJP): ``kw`` is not its."""
    def loss(p, xz):
        out = bn_relu_residual(xz[0], p["mean"], p["invstd"], p["scale"],
                               p["bias"], z=xz[1])
        return jnp.sum(out ** 2)
    params = {"mean": _rand(1, C) * 0.1, "invstd": jnp.abs(_rand(2, C)) + 0.5,
              "scale": _rand(3, C) + 1.0, "bias": _rand(4, C) * 0.1}
    return loss, params, (_rand(0, ROWS, C), _rand(5, ROWS, C))


def _bn_nhwc(kw):
    """The BN epilogue as a ResNet site calls it: a 4-D activation
    sharded on its batch axis, worked on as it is (reductions over axes
    (0, 1, 2))."""
    loss2d, params, _ = _bn(kw)
    return loss2d, params, (_rand(0, 4, 6, 6, C), _rand(5, 4, 6, 6, C))


def _qmm(kw):
    def loss(p, x):
        out = quantized_matmul(x, p["w"], x_scale=4.0 / 127.0, **kw)
        return jnp.sum(out ** 2)
    return loss, {"w": _rand(1, C, C) * 0.1}, _rand(0, ROWS, C)


def _cases(*builders):
    return pytest.mark.parametrize("case", builders,
                                   ids=lambda f: f.__name__.strip("_"))


def _sharded_grad(loss):
    mesh = Mesh(np.array(jax.devices("cpu")[:NDEV]), ("data",))
    return shard_map(jax.grad(loss), mesh=mesh, in_specs=(P(), P("data")),
                     out_specs=P())   # P() out: the grad must carry no vma


@_cases(_layer_norm, _bn, _bn_nhwc, _qmm)
def test_replicated_param_grad_is_summed(case):
    loss, params, x = case({"impl": "jnp"})
    got = jax.jit(_sharded_grad(loss))(params, x)
    want = jax.grad(loss)(params, x)
    for name in params:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


@_cases(_layer_norm, _qmm)
def test_kernel_path_types_check_under_vma(case, monkeypatch):
    """The Mosaic path, trace only: ``pallas_call`` binds abstractly, so
    the forward's operand alignment and the backward's cotangent types
    are checked exactly as on the chip without compiling a kernel.  (The
    Pallas *interpreter* cannot stand in here: it evaluates the kernel
    body under the shard_map trace and trips vma checks of its own.)
    The psum that makes the values right is the same ``match_vma`` call
    the jnp path's value test above goes through."""
    import importlib
    for mod in ("normalization.fused_layer_norm", "quant.kernels"):
        monkeypatch.setattr(importlib.import_module("apex_tpu." + mod),
                            "_use_pallas", lambda: True)
    loss, params, x = case({"impl": "pallas"})
    text = str(jax.make_jaxpr(_sharded_grad(loss))(params, x))
    assert "pallas_call" in text and "psum" in text


def test_imagenet_step_default_flags_lowers_on_mesh(tmp_path):
    """The flagship example's device loop, built by the example itself
    with its DEFAULT flags (BN epilogues through the norm, fused loss),
    lowers under the 8-device mesh.  Trace only — no compile."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "examples", "imagenet"))
    try:
        import main_amp
    finally:
        sys.path.pop(0)
    from apex_tpu import cache

    n_dev = len(jax.devices())
    args = main_amp.parse([
        "--synthetic", "-a", "resnet18", "-b", str(2 * n_dev),
        "--image-size", "32", "--opt-level", "O2", "--loss-scale",
        "dynamic", "--steps-per-call", "2",
        "--compilation-cache", str(tmp_path)])
    run = main_amp.build(args)
    assert run.n_dev == n_dev == 8
    window = (jax.ShapeDtypeStruct((2, 2 * n_dev, 32, 32, 3), jnp.float32,
                                   sharding=run.data_sh),
              jax.ShapeDtypeStruct((2, 2 * n_dev), jnp.int32,
                                   sharding=run.data_sh))
    lowered = run.pipe.loop.lower(
        *cache.abstractify((run.state, window, np.ones((2,), np.bool_))))
    assert "all-reduce" in lowered.as_text() \
        or "all_reduce" in lowered.as_text()
