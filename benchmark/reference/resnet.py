"""ResNet (He et al. 2015, in torchvision's v1.5 form: the stride sits on
the 3x3 convolution of a bottleneck) in plain float32: convolutions by
``lax.conv_general_dilated``, BatchNorm in training mode (batch mean and
biased variance, eps 1e-5), mean softmax cross entropy.

The depth and the block type are read from the parameter tree
(``stage<i>_block<j>``; a ``conv3`` makes a bottleneck).

Departure from torchvision, made because the program makes it: a stride-2
3x3 convolution pads as XLA's ``SAME`` does, (0, 1), where torchvision pads
(1, 1).  Shapes and FLOPs are the same; the sampling grid is shifted by one.
"""

import jax
import jax.numpy as jnp

_EPS = 1e-5


def _conv(x, kernel, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _leaves(p):
    """``{"scale", "bias"}`` of a norm site, through whatever single-child
    wrappers the program's norm module puts around them."""
    while "scale" not in p:
        (p,) = p.values()
    return p


def _bn(x, p):
    p = _leaves(p)
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + _EPS) * p["scale"] + p["bias"]


def _block(x, p, stride):
    conv_bn = lambda h, conv, bn, s=1: _bn(_conv(h, p[conv]["kernel"], s), p[bn])
    if "conv3" in p:                                    # bottleneck
        y = jax.nn.relu(conv_bn(x, "conv1", "bn1"))
        y = jax.nn.relu(conv_bn(y, "conv2", "bn2", stride))
        y = conv_bn(y, "conv3", "bn3")
    else:                                               # basic
        y = jax.nn.relu(conv_bn(x, "conv1", "bn1", stride))
        y = conv_bn(y, "conv2", "bn2")
    residual = x
    if "downsample_conv" in p:
        residual = conv_bn(x, "downsample_conv", "downsample_bn", stride)
    return jax.nn.relu(residual + y)


def loss(params, images, labels):
    """Mean cross entropy of ``labels`` given ``images`` (``[n, h, w, 3]``)."""
    with jax.default_matmul_precision("highest"):
        x = _conv(images, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
        x = jax.nn.relu(_bn(x, params["bn_init"]))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        stage = 1
        while f"stage{stage}_block1" in params:
            block = 1
            while f"stage{stage}_block{block}" in params:
                stride = 2 if stage > 1 and block == 1 else 1
                x = _block(x, params[f"stage{stage}_block{block}"], stride)
                block += 1
            stage += 1
        x = x.mean((1, 2))
        logits = x @ params["head"]["kernel"] + params["head"]["bias"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def loss_and_grads(params, images, labels):
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    # the batch is an argument: a closed-over array would be a constant of
    # the program, and every seed would compile anew
    return jax.jit(jax.value_and_grad(loss))(params, images, labels)
