"""Model zoo + fully-jitted train step tests (small shapes, CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu import training
from apex_tpu.models import (ResNet18, ResNet50, bert_tiny, Generator,
                             Discriminator)
from apex_tpu.training import make_train_step, TrainState


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@pytest.mark.slow
def test_resnet_forward_shapes():
    model = ResNet18(num_classes=10)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("opt_level", [
    pytest.param("O0", marks=pytest.mark.slow),   # O2 is the flagship
    "O2",                                         # config; O0/O3 ride the
    pytest.param("O3", marks=pytest.mark.slow)])  # full (slow) suite
def test_resnet_train_step_loss_decreases(opt_level):
    model = ResNet18(num_classes=10, dtype=jnp.bfloat16
                     if opt_level in ("O2", "O3") else jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(8, 32, 32, 3),
                    jnp.float32)
    y = jnp.asarray(np.arange(8) % 10)
    variables = model.init(jax.random.PRNGKey(0), x)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(p, ms, batch):
        xb, yb = batch
        logits, updated = model.apply(
            {"params": p, "batch_stats": ms}, xb, train=True,
            mutable=["batch_stats"])
        return _xent(logits, yb), updated["batch_stats"]

    tx = training.sgd(lr=0.1, momentum=0.9)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level=opt_level,
                                       has_model_state=True)
    state = init_fn(params, batch_stats)
    step = jax.jit(step_fn)
    state, m0 = step(state, (x, y))
    for _ in range(8):
        state, m = step(state, (x, y))
    assert float(m["loss"]) < float(m0["loss"])


def test_train_step_dynamic_scale_overflow_masks_update():
    params = {"w": jnp.ones((4,), jnp.float32)}

    def loss_fn(p, batch):
        return jnp.sum(p["w"] * batch)

    tx = training.sgd(lr=1.0)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level="O2",
                                       loss_scale="dynamic",
                                       keep_batchnorm_fp32=False)
    state = init_fn(params)
    step = jax.jit(step_fn)
    state, m = step(state, jnp.ones((4,)))
    assert not bool(m["overflow"])
    w_after = np.asarray(state.params["w"])
    # Inf in the batch -> inf grads -> masked update, halved scale.
    state, m = step(state, jnp.asarray([np.inf, 1, 1, 1], np.float32))
    assert bool(m["overflow"])
    np.testing.assert_array_equal(np.asarray(state.params["w"]), w_after)
    assert float(m["loss_scale"]) == 2.**15


def test_train_step_o2_params_stay_fp32_master():
    params = {"dense": {"kernel": jnp.ones((4, 4), jnp.float32)}}

    def loss_fn(p, batch):
        # O2: inside the step the compute copy is bf16.
        assert p["dense"]["kernel"].dtype == jnp.bfloat16
        return jnp.sum((batch @ p["dense"]["kernel"].astype(jnp.float32)) ** 2)

    tx = training.adam(lr=1e-2)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level="O2",
                                       keep_batchnorm_fp32=False)
    state = init_fn(params)
    state, _ = jax.jit(step_fn)(state, jnp.ones((2, 4)))
    # Source of truth stays fp32 (master weights without duplicate storage).
    assert state.params["dense"]["kernel"].dtype == jnp.float32


def test_dp_train_step_on_mesh():
    """8-way DP: shard_map'ed train step with grad psum; replicas stay
    bitwise identical (the DDP contract)."""
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("data",))
    params = {"w": jnp.ones((3,), jnp.float32) * 0.5}

    def loss_fn(p, batch):
        x, y = batch
        pred = x @ jnp.broadcast_to(p["w"], (x.shape[-1],))
        return jnp.mean((pred - y) ** 2)

    tx = training.sgd(lr=0.1)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level="O2",
                                       keep_batchnorm_fp32=False,
                                       axis_name="data")
    state = init_fn(params)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 3).astype(np.float32))
    y = jnp.asarray(rng.randn(64).astype(np.float32))

    sharded = shard_map(
        step_fn, mesh=mesh,
        in_specs=(P(), (P("data"), P("data"))),
        out_specs=(P(), P()),
    )
    new_state, metrics = jax.jit(sharded)(state, (x, y))
    assert np.isfinite(float(metrics["loss"]))

    # Oracle: single-device step on the full batch (grad of mean over all
    # shards == psum-mean of shard grads).
    init2, step2 = make_train_step(loss_fn, tx, opt_level="O2",
                                   keep_batchnorm_fp32=False)
    ref_state, _ = jax.jit(step2)(init2(params), (x, y))
    np.testing.assert_allclose(np.asarray(new_state.params["w"]),
                               np.asarray(ref_state.params["w"]),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.slow
def test_bert_tiny_forward_and_train():
    model = bert_tiny(dtype=jnp.bfloat16)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 1024, (2, 16)))
    variables = model.init(jax.random.PRNGKey(0), ids)
    logits = model.apply(variables, ids)
    assert logits.shape == (2, 2)

    def loss_fn(p, batch):
        ids_b, labels = batch
        return _xent(model.apply({"params": p}, ids_b), labels)

    tx = training.lamb(lr=1e-3)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level="O2")
    state = init_fn(variables["params"])
    labels = jnp.asarray([0, 1])
    step = jax.jit(step_fn)
    state, m0 = step(state, (ids, labels))
    for _ in range(5):
        state, m = step(state, (ids, labels))
    assert float(m["loss"]) < float(m0["loss"])


@pytest.mark.slow
def test_dcgan_shapes():
    g = Generator(ngf=8, nc=3)
    d = Discriminator(ndf=8)
    z = jnp.ones((2, 16))
    gv = g.init(jax.random.PRNGKey(0), z)
    img = g.apply(gv, z, train=False)
    assert img.shape == (2, 64, 64, 3)
    dv = d.init(jax.random.PRNGKey(1), img)
    logit = d.apply(dv, img, train=False)
    assert logit.shape == (2, 1)
