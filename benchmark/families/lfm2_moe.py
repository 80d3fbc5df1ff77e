"""LFM2-MoE family: ``apex_tpu.models.Lfm2Moe`` under amp, built the way
``examples/lm/main_amp.py`` builds it (model -> loss_fn -> make_train_step
with the model's state -> StepPipeline).  Every size comes from the
configuration's keys: the published widths, the experts this chip holds of
the router's, the slice of the vocabulary.  The training length is exactly
the cell's ``seq`` (ids of ``seq + 1``).

The step the pipeline runs is ``make_train_step``'s with one thing added: the
rows each expert was sent, which the model keeps as state, are copied into
the step's metrics (``moe_load``: ``[expert layers, routed experts]``), where
the readers of the expert layer's metrics find them.

``check()`` follows ``families/granite_hybrid.py``, whose helpers it uses:
everything it compares comes from **the timed executable**, one step from the
initial state on a batch of sequences that differ.  Adam's first moment after
that step over ``1 - beta1`` is the step's gradient, held against the
reference's (walked ``check_sample`` sequences at a time, one layer at a
time); the parameter change is held against a plain AdamW first step on the
host; the step's load counts are held against the reference's.  New here: the
float32 reference routes by itself, and where a token's fourth and fifth
scores nearly tie the bf16 system chooses differently.  The check reads the
system's selection from one forward pass of the model on the same batch and
parameters, under the step's own cast, and reports the share of (token, slot)
pairs on which the two agree, per expert layer, held to a floor.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, lfm2_flops, traffic_gen
from benchmark.families import granite_hybrid as shared
from benchmark.reference import lfm2_moe as reference


def build(config, traffic, devices, seed):
    from apex_tpu import models, runtime, training
    from apex_tpu.amp import policy
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    try:
        from apex_tpu.models import lfm2_moe
    except ImportError:
        raise SystemExit("this checkout's apex_tpu.models has no lfm2_moe: "
                         "the configuration cannot run here")

    if len(devices) != 1:
        raise SystemExit("the lfm2_moe family runs one chip's share on one "
                         "chip; the exchange between chips is a family of "
                         "its own")
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
    model = models.Lfm2Moe(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        layer_types=tuple(c["layer_types"]),
        num_dense_layers=c["num_dense_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        mlp_dim=c["intermediate_size"], moe_dim=c["moe_intermediate_size"],
        num_experts=c["published"]["num_experts"],
        experts_held=c["num_experts"], expert_offset=c["expert_offset"],
        top_k=c["num_experts_per_tok"], norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=c["routed_scaling_factor"],
        conv_taps=c["conv_L_cache"],
        rope_theta=c["rope_parameters"]["rope_theta"], eps=c["norm_eps"],
        dtype=jnp.dtype(r["compute_dtype"]))
    expert_layers = [f"layer_{i}" for i in range(c["num_dense_layers"],
                                                 c["num_hidden_layers"])]

    def loss_fn(p, model_state, batch_):
        xb, yb = batch_
        logits, new = model.apply({"params": p, "moe": model_state}, xb,
                                  mutable=["moe"])
        losses = softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), yb.reshape(-1))
        return jnp.mean(losses), new["moe"]

    init_fn, step_fn = training.make_train_step(
        loss_fn, training.adam(r["lr"], weight_decay=r["weight_decay"],
                               beta1=r["beta1"], beta2=r["beta2"], eps=r["eps"]),
        opt_level=r["opt_level"], loss_scale=r["loss_scale"],
        norm_predicate=lfm2_moe.keep_fp32, has_model_state=True)

    def step_with_load(state, batch_):
        state, metrics = step_fn(state, batch_)
        load = jnp.stack([state.model_state[name]["experts"]["load"]
                          for name in expert_layers])
        return state, dict(metrics, moe_load=load)

    def init(key):
        variables = model.init(key, jnp.zeros((1, 8), jnp.int32))
        return init_fn(variables["params"], variables["moe"])

    # weights, optimizer state, model state and scaler in one program
    initial_state = jax.jit(init)
    init_key = jax.random.PRNGKey(seed)
    k = 1                       # steps in one dispatch: examples/lm's default
    pipe = runtime.StepPipeline(step_with_load, k, donate_window=False)

    # ids in [1, vocab): 0 is the fused loss's padding index
    ids_spec = [{"shape": [batch, seq + 1], "dtype": "int32",
                 "dist": "randint", "low": 1, "high": c["vocab_size"]}]
    split = lambda ids: (ids[..., :-1], ids[..., 1:])
    (ids,), _ = traffic_gen.window(ids_spec, k, seed)
    # the check's batch: sequences that differ, so that none can be left out
    (check_ids,), _ = traffic_gen.window(ids_spec, k, seed + 1)

    @jax.jit
    def selection(p, model_state, x):
        """What the step's forward pass selects: ``[expert layers, tokens,
        k]``, from the parameters as the step casts them."""
        cast = policy.convert_params(p, jnp.dtype(r["compute_dtype"]),
                                     norm_predicate=lfm2_moe.keep_fp32)
        _, seen = model.apply({"params": cast, "moe": model_state}, x,
                              mutable=["intermediates"])
        return jnp.stack([seen["intermediates"][name]["experts"]["selected"][0]
                          for name in expert_layers])

    cell = types.SimpleNamespace(
        state=initial_state(init_key), pipe=pipe, k=k, window=split(ids),
        samples_per_step=batch * seq,
        flops_per_step=lfm2_flops.train(c, batch, seq))
    kept = {}

    def first_dispatch():
        cell.state, metrics = cell.pipe.step_window(
            cell.state, split(check_ids), k)
        kept["loss"] = float(np.ravel(jax.device_get(metrics)["loss"])[0])

    def check():
        """The timed executable's first step (module docstring): its loss,
        gradient and load counts against the reference, its parameter change
        against AdamW on the host.  The order keeps the host under 15 GiB of
        arrays."""
        state = cell.state
        trained, scaler, model_state = jax.device_get(
            (state.params, state.scaler, state.model_state))
        jax.tree_util.tree_map(lambda a: a.delete(), state)
        state0 = initial_state(init_key)
        p0, ms0 = jax.device_get((state0.params, state0.model_state))
        state1, metrics = cell.pipe.step_window(state0, split(check_ids), k)
        del state0
        p1, moment, load = jax.device_get(
            (state1.params, state1.opt_state.exp_avg, metrics["moe_load"]))
        jax.tree_util.tree_map(lambda a: a.delete(), state1)
        cell.state = state._replace(
            params=jax.device_put(trained, devices[0]), opt_state=None,
            scaler=scaler, model_state=jax.device_put(model_state, devices[0]))
        del trained
        sys_grads = jax.tree_util.tree_map(
            lambda m: m / np.float32(1 - r["beta1"]), moment)
        del moment
        update = shared.update_error(p0, p1, sys_grads, r)
        del p1
        on_device = jax.device_put(p0, devices[0])
        del p0
        x, y = (a[0] for a in split(check_ids))
        chosen = np.asarray(selection(on_device, ms0, x))
        ref_loss, ref_grads, routing = reference_mean(on_device, x, y, rows,
                                                      c, ms0)
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
        out.update(update, host_available_gib=shared.host_available_gib())
        out.update(routing_numbers(chosen, np.asarray(load).reshape(
            len(expert_layers), -1), routing, expert_layers))
        out["correct"] = bool(
            out["correct"] and update["update_rel"] <= tol["update_rel"]
            and out["routing_agreement_min"] >= tol["routing_agreement_min"]
            and out["load_l1_rel"] <= tol["load_l1_rel"])
        return out

    cell.first_dispatch, cell.check = first_dispatch, check
    return cell


def reference_mean(p0, x, y, rows, cfg, model_state):
    """The reference's mean loss and gradient over the batch ``x``, ``y``,
    ``rows`` sequences at a time (its attention holds 2 GiB of scores a
    sequence), and its routing over the whole batch: per expert layer the
    selection ``[tokens, k]`` in the batch's order and the counts summed.
    Gradients as float64 numpy arrays on the host."""
    batch = x.shape[0]
    weight = lambda g: np.multiply(g, rows / batch, dtype=np.float64)
    loss, mean, routing = 0.0, None, {}
    for i in range(0, batch, rows):
        part_loss, part, routed = reference.loss_and_grads_by_layer(
            p0, x[i:i + rows], y[i:i + rows], cfg, model_state)
        loss += float(part_loss) * rows / batch
        for name, seen in routed.items():
            whole = routing.setdefault(name, {"selected": [], "counts": 0})
            whole["selected"].append(seen["selected"])
            whole["counts"] = whole["counts"] + seen["counts"]
        if mean is None:
            mean = jax.tree_util.tree_map(weight, part)
        else:
            jax.tree_util.tree_map(
                lambda m, g: np.add(m, weight(g), out=m), mean, part)
        del part
    for whole in routing.values():
        whole["selected"] = np.concatenate(whole["selected"])
    return loss, mean, routing


def routing_numbers(chosen, load, routing, expert_layers):
    """The system's routing against the reference's.  ``chosen``: ``[expert
    layers, tokens, k]``, the system's selection; ``load``: ``[expert layers,
    routed experts]``, the checked step's own counts.  The agreement of a
    layer is the share of the system's (token, slot) pairs whose expert the
    reference selected for that token too; ``load_l1_rel`` is the worst
    layer's ``sum |counts - reference's| / sum reference's`` (two for every
    pair that went elsewhere, at most)."""
    agreement, load_error = [], []
    for i, name in enumerate(expert_layers):
        ref = routing[name]
        same = (chosen[i][:, :, None] == ref["selected"][:, None, :]).any(-1)
        agreement.append(float(same.mean()))
        load_error.append(float(np.abs(load[i] - ref["counts"]).sum()
                                / ref["counts"].sum()))
    return {"routing_agreement": agreement,
            "routing_agreement_min": min(agreement),
            "load_l1_rel": max(load_error),
            "load_rows": int(load.sum())}
