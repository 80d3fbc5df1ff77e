"""``benchmark/flops.py`` against a count of the matrix work in the jaxpr of
the plain references, at tiny widths."""

import math

import jax
import jax.numpy as jnp
import pytest

from benchmark import flops
from benchmark.reference import gpt as gpt_reference
from benchmark.reference import resnet as resnet_reference


def _matrix_flops(jaxpr):
    """2 x multiply-adds of every dot_general and convolution, sub-jaxprs
    included."""
    total = 0
    for eqn in jaxpr.eqns:
        out = eqn.outvars[0].aval.shape
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * math.prod(out) * math.prod(lhs[d] for d in contract)
        elif eqn.primitive.name == "conv_general_dilated":
            kh, kw, cin, _ = eqn.invars[1].aval.shape        # HWIO
            total += 2 * math.prod(out) * kh * kw * cin
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _matrix_flops(sub)
    return total


@pytest.mark.parametrize("seq", [16, 64])
def test_gpt_forward_flops(seq):
    from apex_tpu.models import gpt_tiny

    model = {"vocab_size": 1024, "n_embd": 128, "n_layer": 2, "n_inner": 256}
    x = jnp.ones((3, seq), jnp.int32)
    params = jax.eval_shape(
        lambda: gpt_tiny().init(jax.random.PRNGKey(0), x)["params"])
    counted = _matrix_flops(jax.make_jaxpr(
        lambda p: gpt_reference.loss(p, x, x))(params).jaxpr)
    # the plain reference multiplies the whole seq x seq score matrix; the
    # closed form counts the causal half
    other_half = 3 * model["n_layer"] * 2 * seq * seq * model["n_embd"]
    assert counted == 3 * flops.gpt_forward(model, seq) + other_half
    assert flops.gpt_train(model, 3, seq) == 9 * flops.gpt_forward(model, seq)


@pytest.mark.parametrize("arch,size", [("resnet18", 32), ("resnet18", 64),
                                       ("resnet50", 64)])
def test_resnet_forward_flops(arch, size):
    from apex_tpu import models

    net = {"resnet18": models.ResNet18, "resnet50": models.ResNet50}[arch]
    images = jnp.ones((2, size, size, 3))
    labels = jnp.zeros((2,), jnp.int32)
    params = jax.eval_shape(lambda: net(num_classes=10).init(
        jax.random.PRNGKey(0), images, train=True)["params"])
    counted = _matrix_flops(jax.make_jaxpr(
        lambda p: resnet_reference.loss(p, images, labels))(params).jaxpr)
    assert counted == 2 * flops.resnet_forward(arch, size, 10)


def test_published_sizes():
    # torchvision quotes 4.09 G multiply-adds for ResNet-50 at 224
    assert abs(flops.resnet_forward("resnet50", 224, 1000) / 2 - 4.09e9) < 2e7
    gpt2 = {"vocab_size": 50257, "n_embd": 768, "n_layer": 12, "n_inner": 3072}
    # 6 x (85M dense + 38.6M tied head) parameters x tokens, plus attention
    assert abs(flops.gpt_train(gpt2, 8, 1024) / 6.54e12 - 1) < 0.01
    assert flops.gpt_train(gpt2, 8, 1024) > flops.gpt_train(gpt2, 64, 128)
