"""apex_tpu.parallel — distributed data parallelism & friends (SURVEY.md §2.2).

Public surface mirrors ``apex/parallel/__init__.py``: ``DistributedDataParallel``,
``Reducer``, ``SyncBatchNorm``, ``LARC``, ``convert_syncbn_model``,
``create_syncbn_process_group`` — re-designed over ``jax.sharding.Mesh`` +
XLA collectives instead of NCCL hooks/buckets/streams.
"""

from typing import Optional

import dataclasses

import flax.linen as nn
import jax

from .distributed import (DistributedDataParallel, Reducer,  # noqa: F401
                          reduce_gradients, broadcast_params)
from .sync_batchnorm import (SyncBatchNorm, welford_parallel,  # noqa: F401
                             adopt_batchnorm_stats)
from .LARC import LARC, larc_transform, larc_gradients       # noqa: F401
from .ring_attention import (ring_attention,  # noqa: F401
                             ring_flash_attention, ulysses_attention)
from .tensor_parallel import (column_parallel_dense,  # noqa: F401
                              row_parallel_dense, tp_mlp,
                              tp_self_attention, shard_column, shard_row)
from .pipeline import (spmd_pipeline, spmd_pipeline_interleaved,  # noqa: F401
                       stack_interleaved_stage_params,  # noqa: F401
                       stack_stage_params)  # noqa: F401
from .expert_parallel import moe_layer, MoEAux  # noqa: F401
from .zero import zero1, zero1_partition_spec, Zero1State  # noqa: F401
from .mesh import (MeshPlan, MeshTrainStep,  # noqa: F401
                   make_mesh_train_step, zero_sharded, MeshZeroState)
from .multiproc import (initialize, is_coordinator,  # noqa: F401
                        process_identity)


def convert_syncbn_model(module: nn.Module, axis_name: str = "data",
                         process_group=None, channel_last: bool = True):
    """Recursively replace ``nn.BatchNorm`` definitions inside a flax module
    tree with ``SyncBatchNorm`` (reference ``apex/parallel/__init__.py:20-52``
    — which walks ``named_children`` preserving affine/running state; flax
    modules are immutable dataclasses, so this rebuilds the definition tree;
    parameters/batch_stats keep their pytree paths, so existing state dicts
    remain loadable, the analog of the reference copying running stats).

    Works for modules whose submodules are dataclass fields, or entries in
    list/tuple/dict fields.  InstanceNorm-style usage (BatchNorm with
    ``use_running_average`` fixed False and no axis) is left untouched only
    if it subclasses BatchNorm differently — matching the reference's
    InstanceNorm skip.
    """
    def convert(obj):
        if isinstance(obj, nn.BatchNorm):
            return SyncBatchNorm(
                eps=obj.epsilon,
                momentum=1.0 - obj.momentum,  # flax momentum is the EMA decay
                affine=obj.use_scale or obj.use_bias,
                axis_name=axis_name,
                process_group=process_group,
                channel_last=channel_last,
                use_running_average=obj.use_running_average,
            )
        if isinstance(obj, nn.Module) and dataclasses.is_dataclass(obj):
            changes = {}
            for f in dataclasses.fields(obj):
                if not f.init:
                    continue
                try:
                    v = getattr(obj, f.name)
                except AttributeError:
                    continue
                nv = convert_container(v)
                if nv is not v:
                    changes[f.name] = nv
            if changes:
                return obj.clone(**changes)
            return obj
        return obj

    def convert_container(v):
        if isinstance(v, nn.Module):
            return convert(v)
        if isinstance(v, (list, tuple)):
            items = [convert_container(x) for x in v]
            if any(a is not b for a, b in zip(items, v)):
                return type(v)(items)
            return v
        if isinstance(v, dict):
            items = {k: convert_container(x) for k, x in v.items()}
            if any(items[k] is not v[k] for k in v):
                return items
            return v
        return v

    return convert(module)


def create_syncbn_process_group(group_size: int, world_size: Optional[int] = None):
    """Partition the world into BN sub-groups of ``group_size`` ranks.

    Reference ``apex/parallel/__init__.py:55-96`` (every rank must create all
    groups — here the returned ``axis_index_groups`` list is inherently
    global).  Returns a list of rank lists usable as
    ``SyncBatchNorm(process_group=...)`` / ``psum(axis_index_groups=...)``.
    ``group_size=0`` means "use the whole world" → None.
    """
    if group_size == 0:
        return None
    if world_size is None:
        world_size = jax.device_count()
    if world_size < group_size:
        raise ValueError("world_size < group_size")
    if world_size % group_size != 0:
        raise ValueError("world_size must be divisible by group_size")
    return [list(range(g * group_size, (g + 1) * group_size))
            for g in range(world_size // group_size)]
