"""Each plain reference against the system at tiny widths, in float32 where
they must agree tightly, and the comparison that decides ``correct``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.reference import gpt as gpt_reference
from benchmark.reference import resnet as resnet_reference

TOL = {"loss_rel": 1e-5, "grad_cos_min": 0.999999,
       "grad_norm_ratio": [0.9999, 1.0001], "leaf_rel": 1e-3, "leaf_abs": 1e-5}


def _gpt_case():
    from apex_tpu.models import gpt_tiny

    model = gpt_tiny(dtype=jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 1, 1024)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    # biases and norms start at 0 and 1: move them so that they matter
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)

    def loss_fn(p):
        logits = model.apply({"params": p}, x)
        logp = jax.nn.log_softmax(logits.reshape(-1, logits.shape[-1]))
        return -jnp.take_along_axis(logp, y.reshape(-1, 1), axis=1).mean()

    return params, (x, y), loss_fn


def test_gpt_reference_matches_the_model_in_float32():
    params, batch, loss_fn = _gpt_case()
    loss, grads = jax.value_and_grad(loss_fn)(params)
    ref_loss, ref_grads = gpt_reference.loss_and_grads(params, *batch)
    out = compare.verdict(float(loss), float(ref_loss), grads, ref_grads, TOL)
    assert out["correct"], out


def _resnet_case(norm_cls):
    from apex_tpu.models import ResNet18

    model = ResNet18(num_classes=10, dtype=jnp.float32, norm_cls=norm_cls)
    images = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 64, 3))
    labels = jnp.array([1, 7, 3, 3])
    variables = model.init(jax.random.PRNGKey(0), images, train=True)
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), variables["params"])

    def loss_fn(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()

    return params, (images, labels), loss_fn


@pytest.mark.parametrize("norm", ["flax_batchnorm", "groupbn"])
def test_resnet_reference_matches_the_model_in_float32(norm):
    norm_cls = None
    if norm == "groupbn":
        from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
        norm_cls = functools.partial(BatchNorm2d_NHWC, bn_group=1)
    params, batch, loss_fn = _resnet_case(norm_cls)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    ref_loss, ref_grads = resnet_reference.loss_and_grads(params, *batch)
    tol = dict(TOL, grad_cos_min=0.9999, leaf_rel=2e-2, loss_rel=1e-4,
               grad_norm_ratio=[0.999, 1.001])
    out = compare.verdict(float(loss), float(ref_loss), grads, ref_grads, tol)
    assert out["correct"], out


@pytest.mark.parametrize("fault", ["none", "dropped_leaf", "small_leaf_wrong",
                                   "int8_like_noise", "scaled", "loss_off",
                                   "nan_loss"])
def test_verdict_catches_what_it_must(fault):
    """The tolerances of the configuration files, on a synthetic gradient:
    a dropped term, one wrong small leaf, rounding to 7 bits, a wrong scale
    and a wrong loss all fail; bf16-sized noise passes."""
    import json
    import os

    from benchmark import run

    tol = json.load(open(os.path.join(
        run.ROOT, "benchmark", "configs", "gpt2_small_o2.json")))["tolerance"]
    rng = np.random.RandomState(0)
    ref = {"big": rng.randn(4096), "mid": 0.1 * rng.randn(4096),
           "small": 0.02 * rng.randn(512), "zero": np.zeros(64)}
    sys_ = {k: v * (1 + 2.0 ** -8 * rng.randn(*v.shape)) for k, v in ref.items()}
    sys_["zero"] = 1e-7 * rng.randn(64)         # cancels to noise, not to 0
    loss = 6.9
    if fault == "dropped_leaf":
        sys_["mid"] = np.zeros(4096)
    elif fault == "small_leaf_wrong":
        sys_["small"] = -sys_["small"]
    elif fault == "int8_like_noise":
        for k in ("big", "mid", "small"):
            step = np.abs(ref[k]).max() / 127
            sys_[k] = np.round(ref[k] / step + rng.randn(*ref[k].shape)) * step
    elif fault == "scaled":
        sys_ = {k: 2.0 * v for k, v in sys_.items()}
    elif fault == "loss_off":
        loss = 7.3
    elif fault == "nan_loss":
        loss = float("nan")
    out = compare.verdict(loss, 6.9, sys_, ref, tol)
    assert out["correct"] == (fault == "none"), out


def test_verdict_refuses_different_trees():
    with pytest.raises(ValueError):
        compare.verdict(1.0, 1.0, {"a": np.ones(3)}, {"b": np.ones(3)}, TOL)
