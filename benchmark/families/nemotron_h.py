"""Nemotron-H family: ``apex_tpu.models.NemotronH`` under amp, built the way
``examples/lm/main_amp.py`` builds it (model -> loss_fn -> make_train_step
with the model's state -> StepPipeline).  Every size comes from the
configuration's keys: the published widths, and what this chip holds of each
layer (Mamba heads and groups, query and KV heads, experts of the router's,
rows of the vocabulary).  The training length is exactly the cell's ``seq``
(ids of ``seq + 1``).

The step the pipeline runs is ``make_train_step``'s with the latent layers'
counters, which the model keeps as state, copied into the step's metrics:
``moe_load`` (``[expert layers, routed experts]``, the rows each expert was
sent) and ``moe_rows_computed`` (``[expert layers]``, the rows the waves of
the expert chain went over; the rows held are ``moe_load`` over the experts
held).

``check()`` follows ``families/lfm2_moe.py``, whose helpers it uses:
everything it compares comes from **the timed executable**, one step from the
initial state on a batch of sequences that differ.  Adam's first moment after
that step over ``1 - beta1`` is the step's gradient, held against the
reference's (walked ``check_sample`` sequences at a time, one layer at a
time); the parameter change is held against a plain AdamW first step on the
host; the step's load counts are held against the reference's.

New here, because 22 of 512 are chosen: **the arithmetic and the discrete
choices are compared apart.**  One forward pass of the model on the same
batch and parameters, under the step's own cast, gives the system's selection
and what each router read.  The reference's gradient is computed **under the
system's selection** (``forced``): where a token's 22nd and 23rd scores
nearly tie the bf16 residual stream decides otherwise than float32 does, and
a gradient under another selection differs by the rows that moved (1.5% of
the pairs are a fifth of an expert leaf's norm), which would hide every
rounding below it.  The selection itself is held twice: against the
reference's own on its own stream (``routing_agreement``, which falls with
depth as the streams drift apart), and against the reference's router on
**the same input**, the normed hidden state the system's router read
(``router_agreement``: the router's own arithmetic, which a float32 router
matches pair for pair and a rounded score does not).  And the leaves that no
selection reaches but through the residual stream (everything but an expert
layer's router, routed experts and latent projections) are held to a limit of
their own, ``dense_leaf_rel``, half of ``leaf_rel``: they read 4 to 6% of
their own norm where the routed leaves read 17 to 30%, and it is among them
that a rounded decay shows (a mixer's ``dt_bias`` at 60%).
"""

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, nemotron_flops, traffic_gen
from benchmark.families import granite_hybrid as shared
from benchmark.families.lfm2_moe import routing_numbers
from benchmark.reference import nemotron_h as reference


def build(config, traffic, devices, seed):
    from apex_tpu import models, runtime, training
    from apex_tpu.amp import policy
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    try:
        from apex_tpu.models import nemotron_h
    except ImportError:
        raise SystemExit("this checkout's apex_tpu.models has no nemotron_h: "
                         "the configuration cannot run here")

    if len(devices) != 1:
        raise SystemExit("the nemotron_h family runs one chip's share on one "
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
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"]:
        raise SystemExit("hybrid_override_pattern does not have "
                         "num_hidden_layers letters")
    if c["n_shared_experts"] != 1 or c["n_group"] != 1 or c["topk_group"] != 1:
        raise SystemExit("the family is written for one shared expert and "
                         "no group limit on the selection")
    model = models.NemotronH(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        pattern=pattern, mamba_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"], mamba_state=c["ssm_state_size"],
        mamba_groups=c["n_groups"], mamba_conv=c["conv_kernel"],
        mamba_chunk=c["chunk_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        latent_size=c["moe_latent_size"], moe_dim=c["moe_intermediate_size"],
        shared_dim=c["moe_shared_expert_intermediate_size"],
        num_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"], expert_offset=c["expert_offset"],
        top_k=c["num_experts_per_tok"], norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=c["routed_scaling_factor"], eps=c["norm_eps"],
        dtype=jnp.dtype(r["compute_dtype"]))
    expert_layers = [f"layer_{i}" for i, kind in enumerate(pattern)
                     if kind == "E"]

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
        norm_predicate=nemotron_h.keep_fp32, has_model_state=True)

    def step_with_counters(state, batch_):
        state, metrics = step_fn(state, batch_)
        of = lambda leaf: jnp.stack([state.model_state[name]["experts"][leaf]
                                     for name in expert_layers])
        return state, dict(metrics, moe_load=of("load"),
                           moe_rows_computed=of("rows_computed"))

    def init(key):
        variables = model.init(key, jnp.zeros((1, 8), jnp.int32))
        return init_fn(variables["params"], variables["moe"])

    # weights, optimizer state, model state and scaler in one program
    initial_state = jax.jit(init)
    init_key = jax.random.PRNGKey(seed)
    k = 1                       # steps in one dispatch: examples/lm's default
    pipe = runtime.StepPipeline(step_with_counters, k, donate_window=False)

    # ids in [1, vocab): 0 is the fused loss's padding index
    ids_spec = [{"shape": [batch, seq + 1], "dtype": "int32",
                 "dist": "randint", "low": 1, "high": c["vocab_size"]}]
    split = lambda ids: (ids[..., :-1], ids[..., 1:])
    (ids,), _ = traffic_gen.window(ids_spec, k, seed)
    # the check's batch: sequences that differ, so that none can be left out
    (check_ids,), _ = traffic_gen.window(ids_spec, k, seed + 1)

    @jax.jit
    def selection(p, model_state, x):
        """What the step's forward pass selects, ``[expert layers, tokens,
        k]``, and what each layer's router read, ``[expert layers, tokens,
        hidden]``, from the parameters as the step casts them."""
        cast = policy.convert_params(p, jnp.dtype(r["compute_dtype"]),
                                     norm_predicate=nemotron_h.keep_fp32)
        _, seen = model.apply({"params": cast, "moe": model_state}, x,
                              mutable=["intermediates", "moe"])
        of = lambda leaf: [seen["intermediates"][name]["experts"][leaf][0]
                           for name in expert_layers]
        return jnp.stack(of("selected")), jnp.stack(
            [u.reshape(-1, u.shape[-1]) for u in of("router_in")])

    cell = types.SimpleNamespace(
        state=initial_state(init_key), pipe=pipe, k=k, window=split(ids),
        samples_per_step=batch * seq,
        flops_per_step=nemotron_flops.train(c, batch, seq))
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
        p1, moment, load, computed = jax.device_get(
            (state1.params, state1.opt_state.exp_avg, metrics["moe_load"],
             metrics["moe_rows_computed"]))
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
        chosen, read = selection(on_device, ms0, x)
        chosen = np.asarray(chosen)
        router = {name: reference.select(
            read[i], on_device[name]["experts"]["router"],
            ms0[name]["experts"]["correction_bias"], c)
            for i, name in enumerate(expert_layers)}
        del read
        ref_loss, ref_grads, routing = reference_mean(
            on_device, x, y, rows, c, ms0,
            dict(zip(expert_layers, chosen)))
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
        dense = compare.verdict(
            kept["loss"], ref_loss, _dense(sys_grads), _dense(ref_grads),
            dict(tol, leaf_rel=tol["dense_leaf_rel"]))
        out.update(dense_leaf_err_over_allowed=dense["leaf_err_over_allowed"],
                   dense_leaf_err_worst_at=dense["leaf_err_worst_at"][2:-2])
        out.update(update, host_available_gib=shared.host_available_gib())
        load = np.asarray(load).reshape(len(expert_layers), -1)
        out.update(routing_numbers(chosen, load, routing, expert_layers))
        same = [_agreement(chosen[i], router[name])
                for i, name in enumerate(expert_layers)]
        out.update(router_agreement=same, router_agreement_min=min(same))
        # not held: how far the selection of that forward pass, another
        # compiled program, lies from the checked step's own counts
        counts = np.stack([np.bincount(sel.ravel(), minlength=load.shape[1])
                           for sel in chosen])
        out["pass_load_l1_rel"] = float(
            (np.abs(counts - load).sum(-1) / load.sum(-1)).max())
        out.update(rows_held=int(nemotron_flops.held(c, load).sum()),
                   rows_computed=int(np.sum(computed)))
        out["correct"] = bool(
            out["correct"] and out["dense_leaf_err_over_allowed"] <= 1.0
            and update["update_rel"] <= tol["update_rel"]
            and out["routing_agreement_min"] >= tol["routing_agreement_min"]
            and out["router_agreement_min"] >= tol["router_agreement_min"]
            and out["load_l1_rel"] <= tol["load_l1_rel"])
        return out

    cell.first_dispatch, cell.check = first_dispatch, check
    return cell


#: the leaves of an expert layer that a token reaches through the selection
_ROUTED = ("router", "w1", "w2", "latent_down", "latent_up")


def _dense(grads):
    """The leaves of ``grads`` outside ``_ROUTED``, by their path."""
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    named = ((jax.tree_util.keystr(path), leaf) for path, leaf in flat)
    return {name: leaf for name, leaf in named if not name.endswith(
        tuple(f"['experts']['{leaf}']" for leaf in _ROUTED))}


def _agreement(chosen, ref):
    """The share of the (token, slot) pairs of ``chosen`` whose expert ``ref``
    selected for that token too (``families/lfm2_moe.routing_numbers``)."""
    return float((chosen[:, :, None] == ref[:, None, :]).any(-1).mean())


def reference_mean(p0, x, y, rows, cfg, model_state, forced=None):
    """The reference's mean loss and gradient over the batch ``x``, ``y``,
    ``rows`` sequences at a time (its attention holds 1 GiB of scores a
    sequence), and its routing over the whole batch: per expert layer the
    selection ``[tokens, k]`` in the batch's order and the counts summed.
    ``forced``: per expert layer the selection ``[tokens, k]`` in the batch's
    order that the reference applies in place of its own (which it still
    reports).  Gradients as float64 numpy arrays on the host."""
    batch, seq = x.shape
    of = lambda i: None if forced is None else {
        name: sel[i * seq:(i + rows) * seq] for name, sel in forced.items()}
    weight = lambda g: np.multiply(g, rows / batch, dtype=np.float64)
    loss, mean, routing = 0.0, None, {}
    for i in range(0, batch, rows):
        part_loss, part, routed = reference.loss_and_grads_by_layer(
            p0, x[i:i + rows], y[i:i + rows], cfg, model_state, of(i))
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
