"""Granite-4.0-H family: ``apex_tpu.models.GraniteHybrid`` under amp, built
the way ``examples/lm/main_amp.py`` builds it (model -> loss_fn ->
make_train_step -> StepPipeline).  Every size comes from the configuration's
published keys; as in the gpt family the training length is exactly the
cell's ``seq`` (ids of ``seq + 1``).

What differs from the gpt family is the size, and what ``check()`` holds the
timed step to.  The training state is 9.5 GiB of a 15.75 GiB chip, so
``check()`` first moves the trained parameters to the host and frees the
state, and puts the parameters back at its end (``run.py`` reads
``cell.state.params`` afterwards; the optimizer's moments are not kept).
In between, everything it compares comes from **the timed executable**: one
step from the initial state on a batch of distinct sequences.  Adam's first
moment after that step is ``(1 - beta1) g``, so it holds the step's own
gradient over the whole batch; that gradient is held against the
reference's (which walks the batch ``check_sample`` sequences at a time,
one layer at a time), and the step's parameter change against a plain AdamW
first step on the host from the same gradient.  A step that trains on part
of the batch fails the first, one that leaves the parameters or the moments
where they were fails the second.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, hybrid_flops, traffic_gen
from benchmark.reference import granite_hybrid as reference


def build(config, traffic, devices, seed):
    from apex_tpu import models, runtime, training
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    try:
        from apex_tpu.models import granite_hybrid
    except ImportError:
        raise SystemExit("this checkout's apex_tpu.models has no "
                         "granite_hybrid: the configuration cannot run here")

    if len(devices) != 1:
        raise SystemExit("the granite_hybrid family runs on one chip; a "
                         "sharded layout is a family of its own")
    c, r = config, config["recipe"]
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    rows = traffic["check_sample"]      # what the reference holds at once
    if batch % rows:
        raise SystemExit(f"batch {batch} is not a multiple of the check's "
                         f"sample of {rows}")
    if seq > c["max_position_embeddings"]:
        raise SystemExit(f"seq {seq} exceeds max_position_embeddings")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise SystemExit("layer_types does not have num_hidden_layers entries")
    model = models.GraniteHybrid(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        layer_types=tuple(c["layer_types"]),
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        mlp_dim=c["shared_intermediate_size"],
        mamba_heads=c["mamba_n_heads"], mamba_head_dim=c["mamba_d_head"],
        mamba_state=c["mamba_d_state"], mamba_groups=c["mamba_n_groups"],
        mamba_conv=c["mamba_d_conv"], mamba_chunk=c["mamba_chunk_size"],
        embedding_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        attention_multiplier=c["attention_multiplier"],
        logits_scaling=c["logits_scaling"], eps=c["rms_norm_eps"],
        dtype=jnp.dtype(r["compute_dtype"]))

    def loss_fn(p, batch_):
        xb, yb = batch_
        logits = model.apply({"params": p}, xb)
        losses = softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), yb.reshape(-1))
        return jnp.mean(losses)

    init_fn, step_fn = training.make_train_step(
        loss_fn, training.adam(r["lr"], weight_decay=r["weight_decay"],
                               beta1=r["beta1"], beta2=r["beta2"], eps=r["eps"]),
        opt_level=r["opt_level"], loss_scale=r["loss_scale"],
        norm_predicate=granite_hybrid.keep_fp32)
    # weights, optimizer state and scaler in one program, on the device
    initial_state = jax.jit(lambda key: init_fn(model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"]))
    init_key = jax.random.PRNGKey(seed)
    k = 1                       # steps in one dispatch: examples/lm's default
    pipe = runtime.StepPipeline(step_fn, k, donate_window=False)

    # ids in [1, vocab): 0 is the fused loss's padding index
    ids_spec = [{"shape": [batch, seq + 1], "dtype": "int32",
                 "dist": "randint", "low": 1, "high": c["vocab_size"]}]
    split = lambda ids: (ids[..., :-1], ids[..., 1:])
    (ids,), _ = traffic_gen.window(ids_spec, k, seed)
    # the check's batch: sequences that differ, so that none can be left out
    (check_ids,), _ = traffic_gen.window(ids_spec, k, seed + 1)

    cell = types.SimpleNamespace(
        state=initial_state(init_key), pipe=pipe, k=k, window=split(ids),
        samples_per_step=batch * seq,
        flops_per_step=hybrid_flops.train(c, batch, seq))
    kept = {}

    def first_dispatch():
        cell.state, metrics = cell.pipe.step_window(
            cell.state, split(check_ids), k)
        kept["loss"] = float(np.ravel(jax.device_get(metrics)["loss"])[0])

    def check():
        """The timed executable's first step (module docstring): its loss
        and its gradient against the reference, its parameter change against
        AdamW on the host.  The order keeps the host under 15 GiB of arrays."""
        state = cell.state
        trained, scaler = jax.device_get((state.params, state.scaler))
        jax.tree_util.tree_map(lambda a: a.delete(), state)
        state0 = initial_state(init_key)
        p0 = jax.device_get(state0.params)
        state1, _ = cell.pipe.step_window(state0, split(check_ids), k)
        del state0
        p1, moment = jax.device_get((state1.params, state1.opt_state.exp_avg))
        jax.tree_util.tree_map(lambda a: a.delete(), state1)
        cell.state = state._replace(
            params=jax.device_put(trained, devices[0]), opt_state=None,
            scaler=scaler)
        del trained
        sys_grads = jax.tree_util.tree_map(
            lambda m: m / np.float32(1 - r["beta1"]), moment)
        del moment
        update = update_error(p0, p1, sys_grads, r)
        del p1
        on_device = jax.device_put(p0, devices[0])
        del p0
        x, y = (a[0] for a in split(check_ids))
        ref_loss, ref_grads = reference_mean(on_device, x, y, rows, c)
        jax.tree_util.tree_map(lambda a: a.delete(), on_device)
        # float64 already, so that compare.verdict copies nothing; leaf by
        # leaf, each float32 leaf dropped as its copy is made
        leaves, tree = jax.tree_util.tree_flatten(sys_grads)
        del sys_grads
        for i in range(len(leaves)):
            leaves[i] = np.asarray(leaves[i], np.float64)
        sys_grads = tree.unflatten(leaves)
        tol = config["tolerance"]
        out = compare.verdict(kept["loss"], ref_loss, sys_grads, ref_grads, tol)
        out.update(update, host_available_gib=host_available_gib())
        out["correct"] = bool(out["correct"]
                              and update["update_rel"] <= tol["update_rel"])
        return out

    cell.first_dispatch, cell.check = first_dispatch, check
    return cell


def host_available_gib():
    """What the machine has left while both gradients are held as float64,
    the check's fullest moment (the chip tool's machines have 40 GiB and end
    a process that passes them)."""
    with open("/proc/meminfo", encoding="utf-8") as f:
        kb = {line.split(":")[0]: int(line.split()[1]) for line in f}
    return kb["MemAvailable"] / 2 ** 20


def reference_mean(p0, x, y, rows, cfg):
    """The reference's mean loss and gradient over the batch ``x``, ``y``,
    ``rows`` sequences at a time: its attention holds 2 GiB of scores a
    sequence.  Gradients as float64 numpy arrays on the host."""
    batch = x.shape[0]
    weight = lambda g: np.multiply(g, rows / batch, dtype=np.float64)
    loss, mean = 0.0, None
    for i in range(0, batch, rows):
        part_loss, part = reference.loss_and_grads_by_layer(
            p0, x[i:i + rows], y[i:i + rows], cfg)
        loss += float(part_loss) * rows / batch
        if mean is None:
            mean = jax.tree_util.tree_map(weight, part)
        else:
            jax.tree_util.tree_map(
                lambda m, g: np.add(m, weight(g), out=m), mean, part)
        del part
    return loss, mean


def adamw_first_step(p0, g, recipe):
    """AdamW's first step from zero moments, in float32 on the host:
    ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, both divided by their bias
    corrections, the decay decoupled."""
    f = np.float32
    b1, b2 = f(recipe["beta1"]), f(recipe["beta2"])
    m, v = (f(1) - b1) * g, (f(1) - b2) * np.square(g)
    update = (m / (f(1) - b1)) / (np.sqrt(v / (f(1) - b2)) + f(recipe["eps"]))
    return p0 - f(recipe["lr"]) * (update + f(recipe["weight_decay"]) * p0)


def update_error(p0, p1, grads, recipe):
    """How far the step's parameter change ``p1 - p0`` is from AdamW's on the
    host, leaf by leaf, as a share of the latter's norm: ``update_rel`` is the
    worst leaf's.  A leaf left where it was reads 1."""
    worst = (None, 0.0)
    flat = lambda tree: jax.tree_util.tree_flatten_with_path(tree)[0]
    for (path, a), (_, b), (_, g) in zip(flat(p0), flat(p1), flat(grads)):
        a = np.asarray(a, np.float32)
        want = np.asarray(adamw_first_step(a, g, recipe), np.float64) - a
        got = np.asarray(b, np.float64) - a
        rel = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-300)
        if not rel <= worst[1]:         # a NaN takes the place too
            worst = (jax.tree_util.keystr(path), float(rel))
    return {"update_rel": worst[1], "update_worst_at": worst[0]}
