"""The LFM2-24B-A2B configuration, its plain reference, its FLOP and byte
counts and its readers: what the configuration's file has to state (the cut,
the counts, the deployment), the model against the reference in float32 (loss,
every gradient leaf, the routing), the reference by layers against the
reference whole, the reference's four shares against its uncut layer, the
closed forms against the dot-generals of the traced jaxpr, the family through
the harness on a tiny cell with faults planted in the step and in the model,
and the readers on the recorded trace and on the program's counter."""

import importlib.util
import json
import math
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import lfm2_flops, run, scope_reduce
from benchmark.reference import lfm2_moe as reference

ROOT = run.ROOT
CELL = "lfm2_24b_a2b_o2.b4_seq4096"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "lfm2_24b_a2b_o2.json")))
#: LiquidAI/LFM2-24B-A2B config.json, every number of it
PUBLISHED = {
    "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 11776,
    "max_position_embeddings": 128000, "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "routed_scaling_factor": 1, "vocab_size": 65536}
PUBLISHED_LAYERS = ["conv", "conv"] + [
    "full_attention" if i % 4 == 0 else "conv" for i in range(38)]
READERS = ("moe_ms_per_step", "moe_experts_ms_per_step", "sconv_ms_per_step",
           "moe_experts_roofline_pct", "moe_load_max_over_mean")


def _controls():
    path = os.path.join(ROOT, "tests", "benchmark", "lfm2_moe_controls.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_controls", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


controls = _controls()
TINY_CONFIG, _ = controls.tiny(CONFIG, {"seq": 64})


def _tiny_model(cfg, **kw):
    from apex_tpu import models

    return models.Lfm2Moe(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"], moe_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"], **kw)


# -- the configuration's file --------------------------------------------------

def test_every_width_is_the_published_one_and_the_cut_is_on_file():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == {"num_hidden_layers", "num_dense_layers", "num_experts",
                       "vocab_size"}
    assert set(CONFIG["reduced"]) == changed | {"layer_types"}
    for key in changed:
        assert CONFIG["published"][key] == PUBLISHED[key]
    assert CONFIG["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    assert CONFIG["norm_topk_prob"] is True and CONFIG["use_expert_bias"] is True
    assert CONFIG["conv_bias"] is False and CONFIG["model_type"] == "lfm2_moe"
    # the floors of a model_config cut
    assert CONFIG["num_experts"] >= 8 and CONFIG["vocab_size"] * 8 >= 65536
    assert CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"] >= 4


def test_the_cut_is_one_dense_layer_and_a_whole_period():
    kinds = CONFIG["layer_types"]
    assert PUBLISHED_LAYERS.count("full_attention") == 10
    assert [i for i, k in enumerate(PUBLISHED_LAYERS) if k == "full_attention"
            ] == list(range(2, 40, 4))
    assert kinds == [PUBLISHED_LAYERS[0]] + PUBLISHED_LAYERS[2:6]
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv"]
    assert len(kinds) == CONFIG["num_hidden_layers"] == 5
    assert CONFIG["num_dense_layers"] == 1
    ratio = lambda layers: layers.count("full_attention") / len(layers)
    assert ratio(kinds[1:]) == ratio(PUBLISHED_LAYERS[2:38]) == 0.25
    from apex_tpu.models import lfm2_moe
    assert list(lfm2_moe.LAYER_TYPES) == PUBLISHED_LAYERS


def test_counts_deployment_assumptions_and_recipe_agree_with_each_other():
    counts = CONFIG["counts"]
    assert (counts["experts_held"], counts["experts_routed"],
            counts["expert_offset"]) == (16, 64, 0) == (
        CONFIG["num_experts"], CONFIG["published"]["num_experts"],
        CONFIG["expert_offset"])
    assert (counts["vocabulary_rows_held"], counts["vocabulary_rows"]) == (
        16384, 65536) == (CONFIG["vocab_size"],
                          CONFIG["published"]["vocab_size"])
    assert (counts["layers_held"], counts["layers"]) == (5, 40) == (
        CONFIG["num_hidden_layers"], CONFIG["published"]["num_hidden_layers"])
    assert counts["chips_per_layer"] == 4 == (
        counts["experts_routed"] // counts["experts_held"]) == (
        counts["vocabulary_rows"] // counts["vocabulary_rows_held"])
    d, f, i = (CONFIG["hidden_size"], CONFIG["moe_intermediate_size"],
               CONFIG["intermediate_size"])
    conv, attention = 4 * d * d + 3 * d, 2 * d * d + 2 * d * 512 + 2 * 64
    routed = 16 * 3 * d * f + d * 64
    assert counts["parameters"] == 788_052_096 == (
        (conv + 3 * d * i + 2 * d) + (attention + routed + 2 * d)
        + 3 * (conv + routed + 2 * d) + d + 16384 * d)
    for word in ("Four chips share each layer", "16 of its 64", "16,384",
                 "pipeline stages", "experts 0 to 15", "5 of 40 layers",
                 "788.1M", "48 experts"):
        assert word in CONFIG["deployment"], word
    assert {"lr, weight_decay, beta1, beta2, eps", "tied head",
            "selection bias", "auxiliary loss", "initialisation"} <= set(
        CONFIG["assumed"])
    assert CONFIG["departures"] and CONFIG["tolerance"]["reason"]
    recipe = CONFIG["recipe"]
    assert (recipe["opt_level"], recipe["compute_dtype"], recipe["loss_scale"],
            recipe["optimizer"]) == ("O2", "bfloat16", "dynamic", "adamw")
    assert (recipe["lr"], recipe["weight_decay"]) == (3e-4, 0.1)
    assert (recipe["beta1"], recipe["beta2"], recipe["eps"]) == (
        0.9, 0.999, 1e-8)
    traffic = run.resolve(CELL).traffic
    assert (traffic["batch_per_chip"], traffic["seq"],
            traffic["check_sample"]) == (4, 4096, 1)
    # each held expert's load is the deployment's: one sequence a chip a step
    rows = traffic["batch_per_chip"] * traffic["seq"] * 4 * 16 // 64
    assert rows == 16384 and rows // 16 == 4096 * 4 * 4 // 64 == 1024
    tol = CONFIG["tolerance"]
    assert 0.5 < tol["routing_agreement_min"] < 1 and 0 < tol["load_l1_rel"] < 1


def test_the_manifest_gains_one_configuration_one_cell_and_five_readers():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = manifest["configs"][-1]
    assert entry["name"] == "lfm2_24b_a2b_o2" and entry["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"]
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "lfm2_24b_a2b_o2", "b4_seq4096", 1)
    assert sum(c["config"] == "lfm2_24b_a2b_o2"
               for c in manifest["workloads"]) == 1
    assert tuple(m["name"] for m in manifest["per_layer"][-5:]) == READERS
    for m in manifest["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["moves"] == "samples_per_s"
    plan = run.resolve(CELL)
    names = {m["name"] for m in plan.per_layer}
    assert set(READERS) <= names and "mfu_pct" in names
    assert "ssm_ms_per_step" not in names
    other = {m["name"] for m in run.resolve(
        "granite4_h_micro_o2.b2_seq4096").per_layer}
    assert not set(READERS) & other


# -- the reference against the model -------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    cfg = TINY_CONFIG
    model = _tiny_model(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 41), 1, 512)
    x, y = ids[:, :-1], ids[:, 1:]
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # nothing stays at its initial 1 or 0: norm weights, the selection bias
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    params = tree.unflatten([leaf + 0.05 * jax.random.normal(k, leaf.shape)
                             for leaf, k in zip(leaves, keys)])
    state = variables["moe"]
    for i, name in enumerate(state):
        state[name]["experts"]["selection_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), (8,))

    def loss_fn(p):
        logits, seen = model.apply({"params": p, "moe": state}, x,
                                   mutable=["moe", "intermediates"])
        return jnp.mean(softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1))), seen

    return types.SimpleNamespace(cfg=cfg, model=model, params=params,
                                 state=state, x=x, y=y, loss_fn=loss_fn)


def _worst_leaf(got, want):
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    errs = {}
    for (path, g), (_, w) in zip(flat(got), flat(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape
        errs[jax.tree_util.keystr(path)] = (
            np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
    return max(errs.items(), key=lambda kv: kv[1]), len(errs)


def test_model_equals_reference_in_float32_loss_every_leaf_and_the_routing(tiny):
    (loss, seen), grads = jax.jit(jax.value_and_grad(
        tiny.loss_fn, has_aux=True))(tiny.params)
    ref_loss, ref_grads, routing = reference.loss_and_grads(
        tiny.params, tiny.x, tiny.y, tiny.cfg, tiny.state)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    (name, err), leaves = _worst_leaf(grads, ref_grads)
    assert leaves == 49
    assert err < 1e-4, name
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(ref_grads))
    assert set(routing) == {"layer_1", "layer_2", "layer_3", "layer_4"}
    for name, ref in routing.items():
        chosen = seen["intermediates"][name]["experts"]["selected"][0]
        np.testing.assert_array_equal(np.sort(chosen, -1),
                                      np.sort(ref["selected"], -1))
        np.testing.assert_array_equal(seen["moe"][name]["experts"]["load"],
                                      ref["counts"])
        assert ref["counts"].sum() == 2 * 40 * 4 and ref["counts"].shape == (8,)


def test_reference_by_layers_equals_reference_whole(tiny):
    ref_loss, ref_grads, routing = reference.loss_and_grads(
        tiny.params, tiny.x, tiny.y, tiny.cfg, tiny.state)
    loss, grads, by_layer = reference.loss_and_grads_by_layer(
        tiny.params, tiny.x, tiny.y, tiny.cfg, tiny.state)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert all(isinstance(g, np.ndarray)
               for g in jax.tree_util.tree_leaves(grads))
    (name, err), _ = _worst_leaf(grads, ref_grads)
    assert err < 1e-5, name
    for name in routing:
        np.testing.assert_array_equal(by_layer[name]["selected"],
                                      routing[name]["selected"])
    # without a state the bias is zeros: another selection
    _, _, unbiased = reference.loss_and_grads(tiny.params, tiny.x, tiny.y,
                                              tiny.cfg)
    assert any(not np.array_equal(unbiased[n]["selected"],
                                  routing[n]["selected"]) for n in routing)


def test_the_references_four_shares_add_up_to_its_uncut_layer(tiny):
    """The dense loop over two of eight experts from offsets 0, 2, 4, 6, each
    with its own slice of the expert weights, against the loop over all
    eight: forward and input gradient."""
    p = tiny.params["layer_2"]["experts"]
    whole = {k: jnp.concatenate([p[k]] * 4) if k != "router" else p[k]
             for k in p}
    whole = {k: v + 0.01 * jnp.arange(v.shape[0])[:, None, None]
             if k != "router" else v for k, v in whole.items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 7, 64))
    bias = tiny.state["layer_2"]["experts"]["selection_bias"]
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def share(x, offset, held):
        cut = {k: v if k == "router" else v[offset:offset + held]
               for k, v in whole.items()}
        return reference._experts(x, cut, bias,
                                  dict(tiny.cfg, expert_offset=offset))
    full, (sel, counts) = share(x, 0, 8)
    parts = [share(x, offset, 2) for offset in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(out for out, _ in parts), full, atol=1e-5)
    for _, (part_sel, part_counts) in parts:
        np.testing.assert_array_equal(part_sel, sel)
        np.testing.assert_array_equal(part_counts, counts)
    d_x = lambda offset, held: jax.grad(
        lambda x: jnp.sum(share(x, offset, held)[0] * cot))(x)
    np.testing.assert_allclose(sum(d_x(o, 2) for o in (0, 2, 4, 6)),
                               d_x(0, 8), atol=2e-5)


# -- FLOPs and bytes -------------------------------------------------------------

def _matrix_flops(jaxpr):
    """2 x multiply-adds of every dot_general, and apart the rows x k x n of
    every ragged dot (a grouped product over its static row bound),
    sub-jaxprs included."""
    dots = ragged = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            dots += 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
                lhs[d] for d in contract)
        elif eqn.primitive.name.startswith("ragged_dot"):
            rows, k = eqn.invars[0].aval.shape
            ragged += 2 * rows * k * eqn.invars[1].aval.shape[-1]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            more = _matrix_flops(sub)
            dots, ragged = dots + more[0], ragged + more[1]
    return dots, ragged


@pytest.mark.parametrize("seq", [32, 64])
def test_forward_flops_against_the_models_jaxpr(seq):
    """Attention off the kernel path multiplies the whole ``seq x seq``; the
    grouped products are traced over their worst-case row bound, of which the
    closed form counts the expected share ``held / routed``."""
    cfg, batch = TINY_CONFIG, 3
    model = _tiny_model(cfg)
    x = jnp.ones((batch, seq), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    dots, ragged = _matrix_flops(jax.make_jaxpr(
        lambda v: model.apply(v, x))(shapes).jaxpr)
    attention_half = 2 * seq * seq * cfg["hidden_size"]
    expected = ragged * cfg["num_experts"] // cfg["published"]["num_experts"]
    assert ragged == batch * seq * 4 * 4 * lfm2_flops.expert_flops_per_row(cfg)
    assert dots + expected == batch * (lfm2_flops.forward(cfg, seq)
                                       + attention_half)
    assert lfm2_flops.train(cfg, batch, seq) == 3 * batch * (
        lfm2_flops.forward(cfg, seq))


def test_published_sizes_and_the_experts_share():
    per_token = lfm2_flops.forward(CONFIG, 4096) / 4096
    # the issue's count: 460 MFLOP forward a token
    assert abs(per_token / 460.4e6 - 1) < 0.002
    assert lfm2_flops.expert_flops_per_row(CONFIG) == 6 * 2048 * 1536
    step = lfm2_flops.train(CONFIG, 4, 4096)
    assert abs(step / 22.63e12 - 1) < 0.002
    rows = 4 * 16384 * 4 * 16 // 64         # four layers, a quarter of the pairs
    experts = lfm2_flops.moe_experts_train_flops(CONFIG, rows)
    assert experts == 3 * 6 * 2048 * 1536 * 65536
    assert 0.16 < experts / step < 0.17
    weights = 4 * 16 * 3 * 2048 * 1536 * 2
    assert lfm2_flops.moe_experts_train_bytes(CONFIG, rows) == (
        5 * 4096 * rows + 3 * weights)
    # the least time for the grouped products is their FLOPs', on the v5e
    peaks = run.resolve(CELL).peaks["by_device_kind"]["TPU v5 lite"]
    assert (experts / peaks["bf16_flops_per_s"]
            > lfm2_flops.moe_experts_train_bytes(CONFIG, rows)
            / peaks["hbm_bytes_per_s"])
    load = np.zeros((3, 4, 64), np.int64)
    load[..., :16], load[..., 16:] = 1000, 7
    np.testing.assert_array_equal(lfm2_flops.held_rows(CONFIG, load),
                                  [64000] * 3)


# -- the family through the harness ----------------------------------------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_lfm2"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    loose = dict(CONFIG["tolerance"], loss_rel=0.02, grad_cos_min=0.999,
                 grad_norm_ratio=[0.99, 1.01], leaf_rel=0.4, leaf_abs=0.0,
                 routing_agreement_min=0.985, load_l1_rel=0.03)
    json.dump(dict(TINY_CONFIG, name="lfm2_tiny", tolerance=loose),
              open(os.path.join(bench, "configs", "lfm2_tiny.json"), "w"))
    json.dump({"batch_per_chip": 4, "seq": 64, "check_sample": 1},
              open(os.path.join(bench, "traffic", "s64.json"), "w"))
    manifest["configs"] = [{"name": "lfm2_tiny", "reduced": [], "why": "test",
                            "source": "https://example.org",
                            "file": "benchmark/configs/lfm2_tiny.json"}]
    manifest["workloads"] = [{"name": "lfm2_tiny.s64", "chips": 1,
                              "config": "lfm2_tiny", "traffic": "s64",
                              "why": "test"}]
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if "workloads" not in m]
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def test_untraced_run_of_a_tiny_cell_is_correct(tiny_root, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    # a seed past 32 signed bits, as the driver's are
    result = run.run_cell("lfm2_tiny.s64", seed=2 ** 31 + 77, seconds=1.0,
                          trace=False, allow_cpu=True, root=tiny_root)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"samples_per_s", "peak_hbm_gib",
                                      "setup_s"}


def test_check_gives_the_state_back_and_the_step_reports_its_load(tiny_root):
    plan = run.resolve("lfm2_tiny.s64", tiny_root)
    cell = plan.family.build(plan.config, plan.traffic, jax.devices()[:1], 4)
    cell.first_dispatch()
    cell.state, metrics = cell.pipe.step_window(cell.state, cell.window, cell.k)
    load = np.asarray(metrics["moe_load"])
    assert load.shape == (1, 4, 8) and load.dtype == np.int32
    assert (load.sum(-1) == 4 * 64 * 4).all()
    before = jax.device_get((cell.state.params, cell.state.model_state))
    verdict = cell.check()
    assert verdict["correct"] is True and verdict["leaves"] == 49
    assert len(verdict["routing_agreement"]) == 4
    assert verdict["load_rows"] == 4 * 4 * 64 * 4
    after = (cell.state.params, cell.state.model_state)
    assert all(isinstance(a, jax.Array) and np.array_equal(a, b)
               for a, b in zip(jax.tree_util.tree_leaves(after),
                               jax.tree_util.tree_leaves(before)))
    assert cell.flops_per_step == lfm2_flops.train(plan.config, 4, 64)
    assert cell.samples_per_step == 4 * 64


def _faulty(pipe, fault):
    """The pipeline with one of the contract's faults planted in its step."""
    def step_window(state, window, k):
        before = jax.tree_util.tree_map(jnp.copy, state)
        after, metrics = pipe.step_window(state, window, k)
        if fault == "state_left_unchanged":
            after = before
        elif fault == "parameters_left_unchanged":
            after = after._replace(params=before.params)
        elif fault == "one_leaf_left_unchanged":
            after.params["layer_3"]["experts"]["w2"] = before.params[
                "layer_3"]["experts"]["w2"]
        return after, metrics
    return types.SimpleNamespace(step_window=step_window)


def _seen(verdict, tol):
    return {"grad_norm_ratio": not (tol["grad_norm_ratio"][0]
                                    <= verdict["grad_norm_ratio"]
                                    <= tol["grad_norm_ratio"][1]),
            "grad_cos": verdict["grad_cos"] < tol["grad_cos_min"],
            "leaf": verdict["leaf_err_over_allowed"] > 1,
            "update_rel": verdict["update_rel"] > tol["update_rel"],
            "routing": (verdict["routing_agreement_min"]
                        < tol["routing_agreement_min"]),
            "load": verdict["load_l1_rel"] > tol["load_l1_rel"]}


@pytest.mark.parametrize("fault,fails", [
    (None, None),
    ("state_left_unchanged", "grad_norm_ratio"),
    ("parameters_left_unchanged", "update_rel"),
    ("one_leaf_left_unchanged", "update_rel"),
    ("half_batch", "grad_cos"),
    ("drop_expert", "leaf"),
    ("unnormalised", "leaf"),
    ("no_rope", "grad_cos"),
    ("bf16_router", "routing")])
def test_check_sees_a_planted_fault(tiny_root, fault, fails):
    """``check()`` steps the timed pipeline once more from the initial state.
    A step that leaves state where it was or trains on half the batch, and a
    model that leaves an expert's rows or the rotation out, leaves the weights
    unnormalised or rounds the router's scores, each comes out as not
    correct, by the number that is there to see it."""
    plan = run.resolve("lfm2_tiny.s64", tiny_root)
    tol = plan.config["tolerance"]
    in_model = fault in ("drop_expert", "unnormalised", "no_rope", "bf16_router")
    try:
        config = controls.degrade(fault if in_model else "base", plan.config)
        cell = plan.family.build(config, plan.traffic, jax.devices()[:1], 11)
        cell.first_dispatch()
        if fault == "half_batch":
            cell.pipe = controls.first_half_twice(cell.pipe)
        elif fault and not in_model:
            cell.pipe = _faulty(cell.pipe, fault)
        if fault == "unnormalised":     # the reference keeps the file's own
            reference_mean = plan.family.reference_mean
            plan.family.reference_mean = lambda *a: reference_mean(
                *a[:4], plan.config, a[5])
        verdict = cell.check()
    finally:
        controls.degrade("base", plan.config)
    seen = _seen(verdict, tol)
    assert verdict["correct"] is (fault is None), verdict
    if fault is None:
        assert not any(seen.values()), seen
        assert verdict["update_rel"] < 2e-4
        assert verdict["routing_agreement_min"] > 0.99
    else:
        assert seen[fails], verdict
    if fails == "update_rel":
        assert verdict["update_rel"] == pytest.approx(1.0, abs=1e-3)
    if fault == "one_leaf_left_unchanged":
        assert verdict["update_worst_at"] == "['layer_3']['experts']['w2']"
        assert not seen["grad_cos"] and not seen["leaf"]
    if fault == "drop_expert":
        assert "experts" in verdict["leaf_err_worst_at"] or (
            "ffn_norm" in verdict["leaf_err_worst_at"])
    if fault == "half_batch":
        assert seen["load"]


def test_routing_numbers_count_pairs_and_rows():
    family = run.resolve(CELL).family
    ref = {"layer_1": {"selected": np.array([[0, 1, 2, 3], [4, 5, 6, 7]]),
                       "counts": np.array([1, 1, 1, 1, 1, 1, 1, 1])}}
    same = family.routing_numbers(
        np.array([[[3, 2, 1, 0], [7, 6, 5, 4]]]), np.ones((1, 8), int), ref,
        ["layer_1"])
    assert same["routing_agreement"] == [1.0] and same["load_l1_rel"] == 0.0
    one_off = family.routing_numbers(
        np.array([[[3, 2, 1, 0], [7, 6, 5, 0]]]),
        np.array([[2, 1, 1, 1, 0, 1, 1, 1]]), ref, ["layer_1"])
    assert one_off["routing_agreement_min"] == 7 / 8
    assert one_off["load_l1_rel"] == 2 / 8 and one_off["load_rows"] == 8


# -- the readers ------------------------------------------------------------------

@pytest.fixture
def traced_cell(tmp_path, monkeypatch):
    """A ``ctx`` whose trace is the one-chip trace recorded on the v5e (a
    prefetch of 15,973 + 79 ns, two matmul-tanh fusions of 158,231 ns and a
    third fusion of 75,813 ns over six whole executions), laid out as
    ``run.py`` writes it under a benchmark directory of its own."""
    monkeypatch.setattr(scope_reduce, "__file__",
                        str(tmp_path / "scope_reduce.py"))
    monkeypatch.setattr(scope_reduce, "_memo", {})
    trace = tmp_path / "out" / CELL / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "benchmark", "testdata",
                             "tiny_1chip.xplane.pb"), trace / "host.xplane.pb")
    peaks = run.resolve(CELL).peaks["by_device_kind"]["TPU v5 lite"]
    load = np.zeros((5, 4, 64), np.int32)
    load[..., :16], load[:, 2, 3] = 1000, 1500
    ctx = types.SimpleNamespace(
        workload=CELL, k=1, hlo="", chips=1, samples_per_step=16384,
        peaks=peaks, step_metrics={"loss": np.zeros(5),
                                   "moe_load": load.ravel()})
    read = lambda: {name: run._load(ROOT, "layer_metrics", name).compute(ctx)
                    for name in READERS}
    return ctx, read


def test_readers_on_the_recorded_trace_and_the_programs_counter(traced_cell):
    ctx, read = traced_cell
    meta = 'metadata={op_name="jit(step)/%s/dot_general" stack_frame_id=1}'
    layer = "jvp(apex.forward)/Lfm2Moe/layer_2"
    ctx.hlo = "\n".join([
        "ENTRY %main.1 (p: bf16[1024,1024]) -> bf16[1024,1024] {",
        "  %copy-done = bf16[8]{0} copy-done(%copy-start)",
        "  %convolution_tanh_fusion.2 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % (layer + "/experts/apex.moe/apex.moe.route"),
        "  %convolution_tanh_fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % ("transpose(" + layer + "/experts/apex.moe/apex.moe.experts)"),
        "  ROOT %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, "
        + meta % (layer + "/conv/apex.sconv"), "}"])
    got = read()
    assert got["moe_ms_per_step"] == pytest.approx(158231e-6 / 6)
    assert 0 < got["moe_experts_ms_per_step"] < got["moe_ms_per_step"]
    assert got["sconv_ms_per_step"] == pytest.approx(75813e-6 / 6)
    rows = 4 * 16 * 1000 + 500
    share = lambda rows: (
        100 * lfm2_flops.moe_experts_train_flops(CONFIG, rows) / 197e12
        / (got["moe_experts_ms_per_step"] * 1e-3))
    assert got["moe_experts_roofline_pct"] == pytest.approx(share(rows))
    # the rows are those of the steps the trace holds whole (the load drifts
    # while the window trains on its one batch): here the fourth step alone
    load = ctx.step_metrics["moe_load"].reshape(5, 4, 64)
    load[3, :, :16] = 2000
    ctx.trace = {"steps": 1}
    assert read()["moe_experts_roofline_pct"] == pytest.approx(
        share(4 * 16 * 2000))
    ctx.trace = {"steps": 2}
    assert read()["moe_experts_roofline_pct"] == pytest.approx(
        share((4 * 16 * 2000 + rows) / 2))
    # the fullest held expert of the worst layer over the mean of the held
    assert got["moe_load_max_over_mean"] == pytest.approx(
        1500 / ((15 * 1000 + 1500) / 16))
    with open(os.path.join(os.path.dirname(scope_reduce.__file__), "out", CELL,
                           "scopes.json")) as f:
        by_scope = json.load(f)["ms_per_step_by_innermost_scope"]
    assert {"apex.moe.route", "apex.moe.experts", "apex.sconv"} <= set(by_scope)


def test_readers_return_nothing_where_the_program_has_neither(traced_cell):
    """The parent commit: no ``apex.moe`` or ``apex.sconv`` scope in the step,
    no ``moe_load`` among its metrics.  Every reader returns ``None`` and
    raises nothing."""
    ctx, read = traced_cell
    ctx.step_metrics = {"loss": np.zeros(5)}
    ctx.hlo = "\n".join([
        "ENTRY %main.1 (p: bf16[1024,1024]) -> bf16[1024,1024] {",
        "  ROOT %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={"
        'op_name="jit(step)/apex.optimizer/mul" stack_frame_id=1}', "}"])
    assert read() == dict.fromkeys(READERS)
    ctx.hlo = ""
    assert read() == dict.fromkeys(READERS)
