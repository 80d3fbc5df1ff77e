"""PR 26's finding, held at the model's level (ISSUE 29): a residual block
as the imagenet example wires it (``nn.Conv`` + ``BatchNorm2d_NHWC``
through the norm-factory hook) works on its NHWC activations as they
are.  On the v5e every activation-sized ``pad``, ``reshape``, ``copy`` or
``transpose`` between a convolution and its BatchNorm was a pass over HBM
of its own (96 of 382 ms a step, PERF.md section 6); the traced block,
forward and backward, must hold none.  ``tests/test_fused_bn_act.py``
holds the same line for the epilogue alone.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
from apex_tpu.models.resnet import BasicBlock, BottleneckBlock

from test_fused_bn_act import activation_sized

LAYOUT_OPS = ("pad", "reshape", "transpose", "copy", "copy_p", "squeeze",
              "expand_dims", "concatenate", "gather", "dynamic_slice")
N, HW, F = 4, 16, 8


def _block(block_cls, strides, dtype, **bn):
    conv = functools.partial(nn.Conv, use_bias=False, dtype=dtype,
                             param_dtype=jnp.float32)
    norm = functools.partial(BatchNorm2d_NHWC, use_running_average=False,
                             **bn)
    return block_cls(F, strides, conv=conv, norm=norm,
                     norm_act=functools.partial(norm, fuse_relu=True))


def _traced(block, x, wrap=lambda fn: fn, axis=None):
    variables = jax.eval_shape(
        lambda x: block.init(jax.random.PRNGKey(0), x), x)

    def step(variables, x):
        def loss(params, x):
            y, updated = block.apply(
                {"params": params,
                 "batch_stats": variables["batch_stats"]}, x,
                mutable=["batch_stats"])
            if axis:       # as make_train_step hands the model state on
                updated = jax.lax.pmean(updated, axis)
            return jnp.sum(y.astype(jnp.float32) ** 2), (y, updated)
        return jax.grad(loss, argnums=(0, 1), has_aux=True)(
            variables["params"], x)

    closed, ((_, dx), (y, _)) = jax.make_jaxpr(
        wrap(step), return_shape=True)(variables, x)
    largest_param = max(p.size for p in
                        jax.tree_util.tree_leaves(variables["params"]))
    return closed, y, dx, largest_param


KINDS = {
    # a block kind, its strides, and the channels of the input that makes
    # it an identity block (no downsample) or a downsampling one
    "bottleneck": (BottleneckBlock, (1, 1), 4 * F),
    "bottleneck_down": (BottleneckBlock, (2, 2), 2 * F),
    "basic": (BasicBlock, (1, 1), F),
    "basic_down": (BasicBlock, (2, 2), F // 2),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_block_issues_no_activation_sized_layout_op(kind, dtype):
    block_cls, strides, cin = KINDS[kind]
    x = jax.ShapeDtypeStruct((N, HW, HW, cin), dtype)
    closed, y, dx, largest_param = _traced(
        _block(block_cls, strides, dtype), x)
    out_hw = HW // strides[0]
    expansion = 4 if block_cls is BottleneckBlock else 1
    assert y.shape == (N, out_hw, out_hw, expansion * F)
    assert y.dtype == dx.dtype == dtype and dx.shape == x.shape
    # the smallest activation of the block: a conv output at the block's
    # own width after the stride; every parameter is smaller than it
    smallest = N * out_hw * out_hw * F
    assert largest_param < smallest
    assert "pallas_call" not in str(closed)
    found = activation_sized(closed.jaxpr, LAYOUT_OPS, smallest)
    assert not found, [(e.primitive.name, [v.aval for v in e.invars])
                       for e in found]
    # 2 or 3 convs (+1 downsample), each with a dgrad and a wgrad
    convs = activation_sized(closed.jaxpr, ("conv_general_dilated",), 0)
    n_conv = (3 if expansion == 4 else 2) + (kind.endswith("_down"))
    assert len(convs) == 3 * n_conv


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_block_with_synced_statistics_issues_none_either(dtype):
    """``--sync_bn``: the statistics cross the mesh as ``[C]`` vectors;
    the activations still are not touched between conv and norm."""
    ndev = 2
    mesh = Mesh(np.array(jax.devices("cpu")[:ndev]), ("data",))
    wrap = lambda fn: shard_map(
        fn, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=((P(), P("data")), (P("data"), P())))
    block = _block(BottleneckBlock, (2, 2), dtype, bn_group=ndev,
                   axis_name="data", world_size=ndev)
    x = jax.ShapeDtypeStruct((ndev * N, HW, HW, 2 * F), dtype)
    closed, y, dx, _ = _traced(block, x, wrap, axis="data")
    assert y.shape == (ndev * N, HW // 2, HW // 2, 4 * F)
    text = str(closed)
    assert "psum" in text or "ppermute" in text
    found = activation_sized(closed.jaxpr, LAYOUT_OPS,
                             N * (HW // 2) ** 2 * F)
    assert not found, [e.primitive.name for e in found]


def test_the_check_sees_a_flattened_norm():
    """A norm that works on ``[rows, C]``, as the removed kernel did."""
    class Flat(nn.Module):
        use_running_average: bool = False
        fuse_relu: bool = False
        scale_init: object = nn.initializers.ones

        @nn.compact
        def __call__(self, x, z=None):
            flat = x.reshape(-1, x.shape[-1])
            out = BatchNorm2d_NHWC(fuse_relu=self.fuse_relu,
                                   scale_init=self.scale_init,
                                   use_running_average=False)(
                flat, None if z is None else z.reshape(flat.shape))
            return out.reshape(x.shape)

    conv = functools.partial(nn.Conv, use_bias=False)
    block = BasicBlock(F, (1, 1), conv=conv, norm=Flat,
                       norm_act=functools.partial(Flat, fuse_relu=True))
    closed, *_ = _traced(block, jax.ShapeDtypeStruct((N, HW, HW, F),
                                                     jnp.float32))
    assert activation_sized(closed.jaxpr, ("reshape",), N * HW * HW * F)
