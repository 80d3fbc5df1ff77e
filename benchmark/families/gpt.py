"""GPT-2 family: ``apex_tpu.models.gpt2_small`` under amp, as ``examples/lm`` trains it.

Built from the library in the order ``examples/lm/main_amp.py:_train`` uses
it (model -> loss_fn -> make_train_step -> StepPipeline), because that
example has no ``build()`` and reads ``sys.argv``.  One difference, on
purpose: the training length is exactly the cell's ``seq`` (ids of
``seq + 1``), where the example's ``--seq-len n`` trains ``n - 1`` positions,
which no attention block size divides.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, flops, traffic_gen
from benchmark.reference import gpt as reference


def build(config, traffic, devices, seed):
    from apex_tpu import models, runtime, training
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    if len(devices) != 1:
        raise SystemExit("the gpt family runs on one chip; a sharded layout "
                         "is a family of its own")
    m, r = config["model"], config["recipe"]
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    if seq > m["n_positions"]:
        raise SystemExit(f"seq {seq} exceeds n_positions {m['n_positions']}")
    model = models.gpt2_small(
        vocab_size=m["vocab_size"], hidden_size=m["n_embd"],
        num_layers=m["n_layer"], num_heads=m["n_head"], mlp_dim=m["n_inner"],
        max_len=m["n_positions"], dtype=jnp.dtype(r["compute_dtype"]))

    def loss_fn(p, batch_):
        xb, yb = batch_
        logits = model.apply({"params": p}, xb)
        losses = softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), yb.reshape(-1))
        return jnp.mean(losses)

    def amp_step(tx):
        return training.make_train_step(
            loss_fn, tx, opt_level=r["opt_level"], loss_scale=r["loss_scale"])

    init = lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    init_key = jax.random.PRNGKey(seed)
    init_fn, step_fn = amp_step(
        training.adam(r["lr"], weight_decay=r["weight_decay"]))
    # weights, optimizer state and scaler in one program, on the device
    state = jax.jit(lambda key: init_fn(init(key)))(init_key)
    k = 1                       # steps in one dispatch: examples/lm's default
    pipe = runtime.StepPipeline(step_fn, k, donate_window=False)

    # ids in [1, vocab): 0 is the fused loss's padding index
    ids_spec = [{"shape": [batch, seq + 1], "dtype": "int32",
                 "dist": "randint", "low": 1, "high": m["vocab_size"]}]
    split = lambda ids: (ids[..., :-1], ids[..., 1:])
    (ids,), _ = traffic_gen.window(ids_spec, k, seed)
    (check_ids,), (sample_ids,) = traffic_gen.window(
        ids_spec, k, seed + 1, tile_from=traffic["check_sample"])

    cell = types.SimpleNamespace(
        state=state, pipe=pipe, k=k, window=split(ids),
        samples_per_step=batch * seq,
        flops_per_step=flops.gpt_train(m, batch, seq))
    kept = {}

    def first_dispatch():
        cell.state, metrics = pipe.step_window(cell.state, split(check_ids), k)
        kept["loss"] = float(np.ravel(jax.device_get(metrics)["loss"])[0])

    def check():
        """Loss: the timed executable's first step on the tiled sample.
        Gradients: one amp step of plain SGD at lr 1 from the same initial
        parameters on the sample, so ``p0 - p1`` is the gradient as
        ``make_train_step`` casts, scales and unscales it."""
        p0 = jax.jit(init)(init_key)
        sample = split(sample_ids)
        sgd_init, sgd_step = amp_step(training.sgd(lr=1.0))
        s1, _ = jax.jit(sgd_step)(sgd_init(p0), sample)
        sys_grads = jax.tree_util.tree_map(jnp.subtract, p0, s1.params)
        ref_loss, ref_grads = reference.loss_and_grads(
            p0, *sample, eps=m["layer_norm_epsilon"])
        return compare.verdict(kept["loss"], float(ref_loss), sys_grads,
                               ref_grads, config["tolerance"])

    cell.first_dispatch, cell.check = first_dispatch, check
    return cell
