"""ResNet family (flax, NHWC, TPU-first) — the flagship benchmark model.

The reference benchmarks apex with torchvision ResNet-50
(``examples/imagenet/main_amp.py``); this is the TPU-native equivalent:
channels-last (the natural TPU conv layout), bf16-friendly (norm layers
created fp32 via the keep-bn-fp32 path convention — parameters live under
``bn``-prefixed names so ``amp.convert_params`` keeps them fp32), and
SyncBatchNorm-pluggable for the ``--sync_bn`` flow
(``main_amp.py:141-146``).

**BN epilogues through the norm (ISSUE 7).**  Every residual block is a
chain of ``conv -> bn -> relu`` with a trailing ``bn -> (+residual) ->
relu``.  The blocks route each chain through a *norm-factory hook*: when
the norm module supports the apex ``bn_relu``/``bn_add_relu`` contract
(``fuse_relu=`` ctor flag + ``z=`` residual call arg — SyncBatchNorm and
``contrib.groupbn.BatchNorm2d_NHWC`` both do, through
:func:`apex_tpu.normalization.bn_relu_residual`), the chain is one call
into the norm; plain ``nn.BatchNorm`` keeps the explicit ``relu(bn(y) +
residual)`` statements.  On the TPU both are jnp that XLA fuses alike:
measured on the v5e, a Mosaic kernel at each of these sites made the
ResNet-50 amp-O2 step several times slower, so none is chosen
(``PERF.md`` section 6, PR 26).  ``norm_cls`` injects an external factory
(e.g. ``functools.partial(BatchNorm2d_NHWC, bn_group=...)``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel import SyncBatchNorm

ModuleDef = Any


def _norm_factory_cls(norm) -> Any:
    """The module class under a (possibly nested) functools.partial."""
    while isinstance(norm, functools.partial):
        norm = norm.func
    return norm


def norm_supports_epilogue(norm) -> bool:
    """True when ``norm`` builds modules with the fused-epilogue contract
    (``fuse_relu`` ctor flag, ``z=`` residual call arg) — the hook the
    blocks key their ``bn -> relu -> (+residual)`` routing on."""
    return hasattr(_norm_factory_cls(norm), "fuse_relu")


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    #: fused bn(+z)+relu factory (``fuse_relu=True`` pre-bound), or None
    #: for the explicit relu/add statements (plain ``nn.BatchNorm``).
    norm_act: Optional[ModuleDef] = None

    def _bn_relu(self, y, name):
        if self.norm_act is not None:
            return self.norm_act(name=name)(y)
        return nn.relu(self.norm(name=name)(y))

    def _bn_add_relu(self, y, residual, name, **kw):
        """The trailing ``bn -> (+residual) -> relu`` chain — the apex
        ``bn_add_relu`` epilogue when the norm supports it."""
        if self.norm_act is not None:
            return self.norm_act(name=name, **kw)(y, residual)
        return nn.relu(residual + self.norm(name=name, **kw)(y))

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1), name="conv1")(x)
        y = self._bn_relu(y, "bn1")
        y = self.conv(self.filters, (3, 3), self.strides, name="conv2")(y)
        y = self._bn_relu(y, "bn2")
        y = self.conv(self.filters * 4, (1, 1), name="conv3")(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return self._bn_add_relu(y, residual, "bn3",
                                 scale_init=nn.initializers.zeros)


class BasicBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    norm_act: Optional[ModuleDef] = None

    _bn_relu = BottleneckBlock._bn_relu
    _bn_add_relu = BottleneckBlock._bn_add_relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides, name="conv1")(x)
        y = self._bn_relu(y, "bn1")
        y = self.conv(self.filters, (3, 3), name="conv2")(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return self._bn_add_relu(y, residual, "bn2",
                                 scale_init=nn.initializers.zeros)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32
    sync_bn: bool = False
    axis_name: Optional[str] = None
    bn_process_group: Optional[Sequence[Sequence[int]]] = None
    bn_momentum: float = 0.1
    #: external norm factory (a module class or functools.partial over
    #: one), e.g. ``functools.partial(contrib.groupbn.BatchNorm2d_NHWC,
    #: bn_group=2, axis_name="data", world_size=8)``.  The factory is
    #: called per site as ``norm(name=..., [scale_init=...])`` and must
    #: accept ``use_running_average``; when it carries the fused-epilogue
    #: contract the blocks route their chains through it.  Overrides
    #: ``sync_bn``.
    norm_cls: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 param_dtype=jnp.float32)
        if self.norm_cls is not None:
            norm = functools.partial(self.norm_cls,
                                     use_running_average=not train)
        elif self.sync_bn:
            norm = functools.partial(
                SyncBatchNorm, momentum=self.bn_momentum,
                axis_name=self.axis_name if train else None,
                process_group=self.bn_process_group,
                use_running_average=not train)
        else:
            norm = functools.partial(
                nn.BatchNorm, use_running_average=not train,
                momentum=1.0 - self.bn_momentum, epsilon=1e-5,
                dtype=self.dtype, param_dtype=jnp.float32)

        norm_act = (functools.partial(norm, fuse_relu=True)
                    if norm_supports_epilogue(norm) else None)

        x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                 name="conv_init")(x)
        if norm_act is not None:
            x = norm_act(name="bn_init")(x)
        else:
            x = norm(name="bn_init")(x)
            x = nn.relu(x)  # jaxlint: disable=J011 -- this IS the deliberate unfused fallback (plain nn.BatchNorm); the fused routing is the branch above
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i, strides,
                                   conv=conv, norm=norm, norm_act=norm_act,
                                   name=f"stage{i + 1}_block{j + 1}")(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=BasicBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BasicBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3],
                              block_cls=BottleneckBlock)
