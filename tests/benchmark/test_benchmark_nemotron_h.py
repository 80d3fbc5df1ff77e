"""The Nemotron-3-Super configuration, its FLOP and byte counts, its family
and its readers: what the configuration's file has to state (every published
number, the cut, the counts, the deployment), the manifest's new entries, the
closed forms against a count by hand and against the dot-generals of the
traced jaxpr, the family through the harness on a tiny cell with faults
planted in the step and in the model, and the readers on the recorded trace
and on the program's counters.  The model against the reference, and the
shares against the uncut layer, are in ``tests/test_nemotron_h.py``."""

import importlib.util
import json
import math
import os
import shutil
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import nemotron_flops, run

ROOT = run.ROOT
CELL = "nemotron3_super_120b_o2.b2_seq8192"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "nemotron3_super_120b_o2.json")))
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
#: nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json, every number
PUBLISHED = {
    "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 4096, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_num_heads": 128,
    "max_position_embeddings": 262144, "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rope_theta": 10000, "routed_scaling_factor": 5, "ssm_state_size": 128,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "vocab_size": 131072}
READERS = ("latent_moe_ms_per_step", "latent_moe_route_ms_per_step",
           "latent_moe_experts_ms_per_step", "latent_moe_dense_ms_per_step",
           "latent_moe_experts_roofline_pct", "latent_moe_load_max_over_mean",
           "hybrid_ssm_ms_per_step")


def _controls():
    here = os.path.join(ROOT, "tests", "benchmark")
    sys.path.insert(0, here)
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_controls", os.path.join(here, "nemotron_h_controls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


controls = _controls()
TINY_CONFIG, _ = controls.tiny(CONFIG, {"seq": 64})


# -- the configuration's file --------------------------------------------------

def test_every_width_is_the_published_one_and_the_cut_is_on_file():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == {"num_hidden_layers", "mamba_num_heads", "n_groups",
                       "num_attention_heads", "num_key_value_heads",
                       "n_routed_experts", "vocab_size"}
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "mamba_num_heads",
        "n_groups", "num_attention_heads", "num_key_value_heads",
        "n_routed_experts", "vocab_size"]
    for key in changed:
        assert CONFIG["published"][key] == PUBLISHED[key]
    assert CONFIG["published"]["hybrid_override_pattern"] == PATTERN
    # no width among what changed
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])
    assert CONFIG["mlp_hidden_act"] == "relu2" and CONFIG["model_type"] == (
        "nemotron_h")
    assert CONFIG["norm_topk_prob"] is True
    assert CONFIG["tie_word_embeddings"] is False
    assert CONFIG["use_conv_bias"] is True and CONFIG["use_bias"] is False
    assert CONFIG["mtp_hybrid_override_pattern"] == "*E"
    # the floors of a model_config cut
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_cut_is_one_whole_period():
    letters = CONFIG["hybrid_override_pattern"]
    assert letters == PATTERN[:11] == "MEMEMEM*EME"
    assert len(letters) == CONFIG["num_hidden_layers"] == 11
    assert [PATTERN.count(c) for c in "ME*"] == [40, 40, 8]
    assert [letters.count(c) for c in "ME*"] == [5, 5, 1]
    from apex_tpu.models import nemotron_h
    assert nemotron_h.PATTERN == PATTERN


def test_counts_deployment_assumptions_and_recipe_agree_with_each_other():
    counts, pub = CONFIG["counts"], CONFIG["published"]
    assert (counts["experts_held"], counts["experts_routed"],
            counts["expert_offset"]) == (8, 512, 0) == (
        CONFIG["n_routed_experts"], pub["n_routed_experts"],
        CONFIG["expert_offset"])
    assert (counts["mamba_heads_held"], counts["mamba_heads"],
            counts["mamba_groups_held"], counts["mamba_groups"]) == (
        16, 128, 1, 8) == (CONFIG["mamba_num_heads"], pub["mamba_num_heads"],
                           CONFIG["n_groups"], pub["n_groups"])
    assert (counts["query_heads_held"], counts["query_heads"],
            counts["kv_heads_held"], counts["kv_heads"]) == (4, 32, 1, 2)
    assert (counts["vocabulary_rows_held"], counts["vocabulary_rows"]) == (
        16384, 131072) == (CONFIG["vocab_size"], pub["vocab_size"])
    assert (counts["layers_held"], counts["layers"]) == (11, 88)
    assert counts["chips_per_layer"] == 64 == (
        counts["experts_routed"] // counts["experts_held"])
    assert 8 == counts["mamba_heads"] // counts["mamba_heads_held"] == (
        counts["query_heads"] // counts["query_heads_held"]) == (
        counts["vocabulary_rows"] // counts["vocabulary_rows_held"])
    d = CONFIG["hidden_size"]
    mamba = d * 2320 + 1024 * d + 5 * 1280 + 3 * 16 + 1024
    attention = 2 * d * 512 + 2 * d * 128
    latent = (d * 512 + 2 * d * 1024 + 2 * d * 5376 + 8 * 2 * 1024 * 2688)
    assert counts["parameters"] == 700_862_960 == (
        5 * mamba + attention + 5 * latent + 12 * d + 2 * 16384 * d)
    for word in ("64 chips share each layer", "8-way tensor-parallel",
                 "64-way expert parallelism", "pipeline stages",
                 "MEMEMEM*EME", "16 of 128 heads", "4 of 32 query heads",
                 "experts 0 to 7 of 512", "shared expert", "16,384 of 131,072",
                 "700.9M"):
        assert word in CONFIG["deployment"], word
    assert {"lr, weight_decay, beta1, beta2, eps", "positions",
            "weight normalisation", "correction bias", "auxiliary loss",
            "initialisation"} <= set(CONFIG["assumed"])
    assert CONFIG["departures"] and CONFIG["tolerance"]["reason"]
    recipe = CONFIG["recipe"]
    assert (recipe["opt_level"], recipe["compute_dtype"], recipe["loss_scale"],
            recipe["optimizer"]) == ("O2", "bfloat16", "dynamic", "adamw")
    assert (recipe["lr"], recipe["weight_decay"], recipe["beta1"],
            recipe["beta2"], recipe["eps"]) == (3e-6, 0.1, 0.9, 0.999, 1e-8)
    traffic = run.resolve(CELL).traffic
    assert (traffic["batch_per_chip"], traffic["seq"],
            traffic["check_sample"]) == (2, 8192, 1)
    # a held expert's load: a quarter of the deployment's 2,816
    rows = traffic["batch_per_chip"] * traffic["seq"] * 22 * 8 // 512
    assert rows == 5632 and rows // 8 == 704 == (8 * 8192 * 22 // 512) // 4
    tol = CONFIG["tolerance"]
    assert 0.5 < tol["routing_agreement_min"] < 1 and 0 < tol["load_l1_rel"] < 1


def test_the_manifest_gains_one_configuration_one_cell_and_seven_readers():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "nemotron3_super_120b_o2"]
    assert entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    assert CONFIG["source"].startswith(entry["source"])
    assert entry["reduced"] == CONFIG["reduced"]
    (cell,) = [c for c in manifest["workloads"]
               if c["config"] == "nemotron3_super_120b_o2"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "b2_seq8192", 1)
    ours = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in ours) == READERS
    for m in ours:
        assert m["layer"] == "kernels" and m["moves"] == "samples_per_s"
    # the accepted expert, conv and scan readers keep their cells
    for m in manifest["per_layer"]:
        if m["name"].startswith(("moe_", "sconv_", "ssm_")):
            assert CELL not in m["workloads"]
    names = {m["name"] for m in run.resolve(CELL).per_layer}
    assert set(READERS) <= names and "mfu_pct" in names
    assert "ssm_ms_per_step" not in names and "moe_ms_per_step" not in names
    other = {m["name"] for m in run.resolve(
        "lfm2_24b_a2b_o2.b4_seq4096").per_layer}
    assert not set(READERS) & other


def test_new_entries_are_the_lists_last_and_lfm2s_hold_by_name():
    """New entries go at the end of their lists (a manifest's entry is read by
    its place when two manifests are compared), so LFM2's are no longer the
    last, which ``test_benchmark_lfm2_moe.py`` asserts by ``[-1]``.  What that
    test holds of LFM2's entries besides their place is held here by name."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert manifest["configs"][-1]["name"] == "nemotron3_super_120b_o2"
    assert manifest["workloads"][-1]["name"] == CELL
    assert tuple(m["name"] for m in manifest["per_layer"][-7:]) == READERS
    lfm2_cell = "lfm2_24b_a2b_o2.b4_seq4096"
    lfm2_readers = ("moe_ms_per_step", "moe_experts_ms_per_step",
                    "sconv_ms_per_step", "moe_experts_roofline_pct",
                    "moe_load_max_over_mean")
    lfm2 = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "lfm2_24b_a2b_o2.json")))
    # the entries just before ours, unedited
    entry = manifest["configs"][-2]
    assert entry["name"] == "lfm2_24b_a2b_o2" and entry["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert entry["reduced"] == lfm2["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"]
    cell = manifest["workloads"][-2]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        lfm2_cell, "lfm2_24b_a2b_o2", "b4_seq4096", 1)
    assert sum(c["config"] == "lfm2_24b_a2b_o2"
               for c in manifest["workloads"]) == 1
    theirs = manifest["per_layer"][-12:-7]
    assert tuple(m["name"] for m in theirs) == lfm2_readers
    for m in theirs:
        assert m["workloads"] == [lfm2_cell] and m["layer"] == "kernels"
        assert m["moves"] == "samples_per_s"
    names = {m["name"] for m in run.resolve(lfm2_cell).per_layer}
    assert set(lfm2_readers) <= names and "mfu_pct" in names
    assert "ssm_ms_per_step" not in names
    other = {m["name"] for m in run.resolve(
        "granite4_h_micro_o2.b2_seq4096").per_layer}
    assert not set(lfm2_readers) & other and not set(READERS) & other


# -- FLOPs and bytes -------------------------------------------------------------

def test_flops_and_bytes_against_a_count_by_hand():
    d, seq = 4096, 8192
    mamba = (2 * (d * (1024 + 1024 + 256 + 16) + 1024 * d)
             + 2 * 64 * 128 + 2 * 64 * 1024 + 2 * 2 * 128 * 1024)
    attention = 2 * (2 * d * 512 + 2 * d * 128) + 2 * seq * 512
    latent = (2 * d * 512 + 4 * d * 1024 + 4 * d * 5376
              + 22 * 8 / 512 * 4 * 1024 * 2688)
    per_token = 5 * mamba + attention + 5 * latent + 2 * d * 16384
    assert nemotron_flops.forward(CONFIG, seq) == pytest.approx(
        seq * per_token, rel=1e-12)
    step = nemotron_flops.train(CONFIG, 2, seq)
    assert step == 3 * 2 * nemotron_flops.forward(CONFIG, seq)
    assert abs(step / 42.15e12 - 1) < 0.002
    # the five latent layers are two thirds of the forward pass, the shared
    # expert alone half of it
    assert 0.65 < 5 * latent / per_token < 0.67
    assert 0.51 < 5 * 4 * d * 5376 / per_token < 0.52
    assert nemotron_flops.expert_flops_per_row(CONFIG) == 4 * 1024 * 2688
    rows = 5 * 5632
    experts = nemotron_flops.latent_experts_train_flops(CONFIG, rows)
    assert experts == 3 * 4 * 1024 * 2688 * rows
    weights = 5 * 8 * 2 * 1024 * 2688 * 2
    assert nemotron_flops.latent_experts_train_bytes(CONFIG, rows) == (
        5 * 2048 * rows + 3 * weights)
    # the least time for the grouped products is their FLOPs', on the v5e,
    # even at 704 rows an expert
    peaks = run.resolve(CELL).peaks["by_device_kind"]["TPU v5 lite"]
    assert (experts / peaks["bf16_flops_per_s"]
            > nemotron_flops.latent_experts_train_bytes(CONFIG, rows)
            / peaks["hbm_bytes_per_s"])
    load = np.zeros((3, 5, 512), np.int64)
    load[..., :8], load[..., 8:] = 700, 7
    np.testing.assert_array_equal(nemotron_flops.held_rows(CONFIG, load),
                                  [5 * 8 * 700] * 3)
    flat = {"moe_load": load.ravel()}
    assert nemotron_flops.load_counts(flat, CONFIG).shape == (3, 5, 512)
    assert nemotron_flops.load_counts({}, CONFIG) is None


def _matrix_flops(jaxpr):
    """2 x multiply-adds of every dot_general, and apart the rows x k x n of
    every ragged dot (a grouped product over its static row bound),
    sub-jaxprs included (a loop's body once)."""
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
    """Attention off the kernel path multiplies the whole ``seq x seq``, the
    scan every in-chunk pair; the grouped products are traced as one wave of
    every pair, of which the closed form counts the expected share."""
    from apex_tpu.models import nemotron_h

    cfg, batch = TINY_CONFIG, 3
    model = _model(cfg)
    x = jnp.ones((batch, seq), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    dots, ragged = _matrix_flops(jax.make_jaxpr(
        lambda v: model.apply(v, x, mutable=["moe"]))(shapes).jaxpr)
    assert ragged == 5 * batch * seq * 4 * nemotron_flops.expert_flops_per_row(
        cfg)
    share = cfg["n_routed_experts"] / cfg["published"]["n_routed_experts"]
    q, n, hp = cfg["chunk_size"], cfg["ssm_state_size"], 4 * 16
    # what the closed form halves: attention's and the scan's causal products
    halved = (2 * seq * 2 * 16 + 5 * (2 * (q // 2) * n + 2 * (q // 2) * hp))
    closed = nemotron_flops.forward(cfg, seq) + seq * halved
    # the scan's state recurrence between chunks is small matrix work of its
    # own, which the closed form does not count
    assert closed <= (dots + ragged * share) / batch <= 1.12 * closed
    assert nemotron_h.PATTERN[:11] == cfg["hybrid_override_pattern"]


def _model(cfg, **kw):
    from apex_tpu import models

    return models.NemotronH(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        mamba_state=cfg["ssm_state_size"], mamba_groups=cfg["n_groups"],
        mamba_chunk=cfg["chunk_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        latent_size=cfg["moe_latent_size"],
        moe_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        num_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], top_k=cfg["num_experts_per_tok"],
        **kw)


# -- the family through the harness ----------------------------------------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_nemotron"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    loose = dict(CONFIG["tolerance"], loss_rel=0.02, grad_cos_min=0.999,
                 grad_norm_ratio=[0.99, 1.01], leaf_rel=0.5, leaf_abs=0.0,
                 update_rel=0.01, routing_agreement_min=0.978,
                 router_agreement_min=0.999, load_l1_rel=0.03,
                 dense_leaf_rel=0.45)
    json.dump(dict(TINY_CONFIG, name="nemotron_tiny", tolerance=loose),
              open(os.path.join(bench, "configs", "nemotron_tiny.json"), "w"))
    json.dump({"batch_per_chip": 2, "seq": 64, "check_sample": 1},
              open(os.path.join(bench, "traffic", "s64.json"), "w"))
    manifest["configs"] = [{"name": "nemotron_tiny", "reduced": [],
                            "why": "test", "source": "https://example.org",
                            "file": "benchmark/configs/nemotron_tiny.json"}]
    manifest["workloads"] = [{"name": "nemotron_tiny.s64", "chips": 1,
                              "config": "nemotron_tiny", "traffic": "s64",
                              "why": "test"}]
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if "workloads" not in m]
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def test_untraced_run_of_a_tiny_cell_is_correct(tiny_root, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    # a seed past 32 signed bits, as the driver's are
    result = run.run_cell("nemotron_tiny.s64", seed=2 ** 31 + 77, seconds=1.0,
                          trace=False, allow_cpu=True, root=tiny_root)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"samples_per_s", "peak_hbm_gib",
                                      "setup_s"}


def test_check_gives_the_state_back_and_the_step_reports_its_counters(
        tiny_root):
    plan = run.resolve("nemotron_tiny.s64", tiny_root)
    cell = plan.family.build(plan.config, plan.traffic, jax.devices()[:1], 4)
    cell.first_dispatch()
    cell.state, metrics = cell.pipe.step_window(cell.state, cell.window, cell.k)
    load = np.asarray(metrics["moe_load"])
    assert load.shape == (1, 5, 16) and load.dtype == np.int32
    assert (load.sum(-1) == 2 * 64 * 4).all()
    computed = np.asarray(metrics["moe_rows_computed"])
    assert computed.shape == (1, 5)
    # 512 pairs a layer are one wave: all of them, where a row is held
    assert set(computed.ravel()) <= {0, 2 * 64 * 4}
    before = jax.device_get((cell.state.params, cell.state.model_state))
    verdict = cell.check()
    assert verdict["correct"] is True, verdict
    assert len(verdict["routing_agreement"]) == 5
    assert verdict["load_rows"] == 5 * 2 * 64 * 4
    assert 0 < verdict["rows_held"] <= verdict["rows_computed"]
    after = (cell.state.params, cell.state.model_state)
    assert all(isinstance(a, jax.Array) and np.array_equal(a, b)
               for a, b in zip(jax.tree_util.tree_leaves(after),
                               jax.tree_util.tree_leaves(before)))
    assert cell.flops_per_step == nemotron_flops.train(plan.config, 2, 64)
    assert cell.samples_per_step == 2 * 64


def _faulty(pipe, fault):
    """The pipeline with one of the contract's faults planted in its step."""
    def step_window(state, window, k):
        before = jax.tree_util.tree_map(jnp.copy, state)
        after, metrics = pipe.step_window(state, window, k)
        if fault == "state_left_unchanged":
            after = before
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
            "dense_leaf": verdict["dense_leaf_err_over_allowed"] > 1,
            "update_rel": verdict["update_rel"] > tol["update_rel"],
            "routing": (verdict["routing_agreement_min"]
                        < tol["routing_agreement_min"]),
            "router": (verdict["router_agreement_min"]
                       < tol["router_agreement_min"]),
            "load": verdict["load_l1_rel"] > tol["load_l1_rel"]}


@pytest.mark.parametrize("fault,fails", [
    (None, None),
    ("state_left_unchanged", "grad_norm_ratio"),
    ("one_leaf_left_unchanged", "update_rel"),
    ("half_batch", "grad_cos"),
    ("bf16_router", "router"),
    ("drop_expert", "leaf"),
    ("no_skip", "dense_leaf")])
def test_check_sees_a_planted_fault(tiny_root, fault, fails):
    """``check()`` steps the timed pipeline once more from the initial state.
    A step that leaves state where it was or trains on half the batch, and a
    model that rounds the router's scores, drops one held expert's pairs or
    leaves a mixer's ``D x`` out, each comes out as not correct, by the number
    that is there to see it."""
    plan = run.resolve("nemotron_tiny.s64", tiny_root)
    tol = plan.config["tolerance"]
    in_model = fault in ("drop_expert", "bf16_router", "no_skip")
    try:
        config = controls.degrade(fault if in_model else "base", plan.config)
        cell = plan.family.build(config, plan.traffic, jax.devices()[:1], 11)
        cell.first_dispatch()
        if fault == "half_batch":
            cell.pipe = controls.first_half_twice(cell.pipe)
        elif fault and not in_model:
            cell.pipe = _faulty(cell.pipe, fault)
        verdict = cell.check()
    finally:
        controls.degrade("base", plan.config)
    seen = _seen(verdict, tol)
    assert verdict["correct"] is (fault is None), verdict
    if fault is None:
        assert not any(seen.values()), seen
        assert verdict["update_rel"] < 2e-3
    else:
        assert seen[fails], verdict
    if fault == "one_leaf_left_unchanged":
        assert verdict["update_rel"] == pytest.approx(1.0, abs=1e-3)
        assert verdict["update_worst_at"] == "['layer_3']['experts']['w2']"
        assert not seen["grad_cos"] and not seen["leaf"]
    if fault == "drop_expert":
        assert "experts" in verdict["leaf_err_worst_at"]
        # a routed leaf's fault: the leaves no selection reaches are sound
        assert not seen["dense_leaf"]
        assert not verdict["dense_leaf_err_worst_at"].endswith(
            ("['router']", "['w1']", "['w2']", "['latent_down']",
             "['latent_up']"))
    if fault == "no_skip":
        assert "['mamba']" in verdict["dense_leaf_err_worst_at"]
    if fault == "bf16_router":
        # on the same input a float32 router is matched pair for pair, so
        # the rounding is read alone; on its own stream the reference differs
        # from the sound system too
        assert seen["routing"] and verdict["router_agreement_min"] < 0.99
    else:
        assert verdict["router_agreement_min"] == 1.0


def test_the_reference_under_a_forced_selection_and_its_router_alone():
    """``forced`` applies a given selection and still reports the
    reference's own; its own selection forced is the reference itself, another
    one moves the expert leaves and nothing before the first expert layer;
    ``select`` on what a router read is that layer's selection."""
    from benchmark.reference import nemotron_h as reference

    cfg = TINY_CONFIG
    model = _model(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 1, 512)
    x, y = ids[:, :-1], ids[:, 1:]
    variables = model.init(jax.random.PRNGKey(0), x)
    params, state = variables["params"], variables["moe"]
    loss, grads, routing = reference.loss_and_grads(params, x, y, cfg, state)
    own = {name: seen["selected"] for name, seen in routing.items()}
    assert sorted(own) == ["layer_1", "layer_10", "layer_3", "layer_5",
                           "layer_8"]
    same = reference.loss_and_grads_by_layer(params, x, y, cfg, state, own)
    assert float(same[0]) == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(same[1]),
                    jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)
    # every token's held experts instead of its own choice
    other = {name: np.broadcast_to(np.asarray([2, 3, 0, 1], np.int32),
                                   sel.shape) for name, sel in own.items()}
    moved = reference.loss_and_grads(params, x, y, cfg, state, other)
    assert float(moved[0]) != float(loss)
    for name in own:       # reported: the reference's own, on the moved stream
        assert moved[2][name]["selected"].shape == own[name].shape
    np.testing.assert_array_equal(moved[2]["layer_1"]["selected"],
                                  own["layer_1"])
    w2 = lambda g: np.asarray(g["layer_1"]["experts"]["w2"])
    assert np.abs(w2(moved[1]) - w2(grads)).max() > 1e-6
    # the router alone, on what the first expert layer's router read
    _, seen = model.apply(variables, x, mutable=["intermediates", "moe"])
    read = seen["intermediates"]["layer_1"]["experts"]["router_in"][0]
    assert read.shape == x.shape + (cfg["hidden_size"],)
    np.testing.assert_array_equal(
        reference.select(read, params["layer_1"]["experts"]["router"],
                         state["layer_1"]["experts"]["correction_bias"], cfg),
        own["layer_1"])


# -- the readers ------------------------------------------------------------------

@pytest.fixture
def traced_cell(tmp_path, monkeypatch):
    """A ``ctx`` whose trace is the one-chip trace recorded on the v5e (a
    prefetch of 15,973 + 79 ns, two matmul-tanh fusions of 158,231 ns and a
    third fusion of 75,813 ns over six whole executions), laid out as
    ``run.py`` writes it under a benchmark directory of its own."""
    monkeypatch.setattr(nemotron_flops, "__file__",
                        str(tmp_path / "nemotron_flops.py"))
    monkeypatch.setattr(nemotron_flops, "_memo", {})
    trace = tmp_path / "out" / CELL / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "benchmark", "testdata",
                             "tiny_1chip.xplane.pb"), trace / "host.xplane.pb")
    peaks = run.resolve(CELL).peaks["by_device_kind"]["TPU v5 lite"]
    load = np.zeros((5, 5, 512), np.int32)
    load[..., :8], load[:, 2, 3] = 700, 1400
    ctx = types.SimpleNamespace(
        workload=CELL, k=1, hlo="", chips=1, samples_per_step=16384,
        peaks=peaks, step_metrics={"loss": np.zeros(5),
                                   "moe_load": load.ravel()})
    read = lambda: {name: run._load(ROOT, "layer_metrics", name).compute(ctx)
                    for name in READERS}
    return ctx, read


def _hlo(first, second, third):
    meta = 'metadata={op_name="jit(step)/%s/dot_general" stack_frame_id=1}'
    return "\n".join([
        "ENTRY %main.1 (p: bf16[1024,1024]) -> bf16[1024,1024] {",
        "  %copy-done = bf16[8]{0} copy-done(%copy-start)",
        "  %convolution_tanh_fusion.2 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % first,
        "  %convolution_tanh_fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % second,
        "  ROOT %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, "
        + meta % third, "}"])


def test_readers_on_the_recorded_trace_and_the_programs_counter(traced_cell):
    ctx, read = traced_cell
    layer = "jvp(apex.forward)/NemotronH/layer_1/experts/apex.moe"
    ctx.hlo = _hlo(
        layer + "/apex.moe.route",
        "transpose(" + layer + "/while/body/apex.moe.experts)",
        "jvp(apex.forward)/NemotronH/layer_0/mamba/apex.ssm/apex.ssm.scan")
    got = read()
    assert got["latent_moe_ms_per_step"] == pytest.approx(158231e-6 / 6)
    assert 0 < got["latent_moe_experts_ms_per_step"] < got[
        "latent_moe_ms_per_step"]
    assert got["latent_moe_route_ms_per_step"] == pytest.approx(
        got["latent_moe_ms_per_step"] - got["latent_moe_experts_ms_per_step"])
    assert got["latent_moe_dense_ms_per_step"] is None
    assert got["hybrid_ssm_ms_per_step"] == pytest.approx(75813e-6 / 6)
    rows = 5 * 8 * 700 + 700
    share = lambda rows: (
        100 * nemotron_flops.latent_experts_train_flops(CONFIG, rows) / 197e12
        / (got["latent_moe_experts_ms_per_step"] * 1e-3))
    assert got["latent_moe_experts_roofline_pct"] == pytest.approx(share(rows))
    # the rows are those of the steps the trace holds whole: the fourth alone
    load = ctx.step_metrics["moe_load"].reshape(5, 5, 512)
    load[3, :, :8] = 1000
    ctx.trace = {"steps": 1}
    assert read()["latent_moe_experts_roofline_pct"] == pytest.approx(
        share(5 * 8 * 1000))
    assert got["latent_moe_load_max_over_mean"] == pytest.approx(
        1400 / ((7 * 700 + 1400) / 8))
    # the two dense scopes add up in one reader
    ctx.hlo = _hlo(layer + "/apex.moe.latent", layer + "/apex.moe.shared",
                   layer + "/apex.moe.combine")
    nemotron_flops._memo.clear()
    again = read()
    assert again["latent_moe_dense_ms_per_step"] == pytest.approx(
        158231e-6 / 6)
    assert again["latent_moe_ms_per_step"] == pytest.approx(
        (158231 + 75813) * 1e-6 / 6)
    assert again["hybrid_ssm_ms_per_step"] is None


def test_a_loops_wrapper_event_is_left_out():
    """``while``, ``conditional`` and ``call`` events span the events of the
    computation they call, which are events of their own."""
    name = ("%while.3 = (s32[], f32[8]{0}) while(%tuple.1), condition=%c, "
            "body=%b")
    assert nemotron_flops.trace_reduce.describe(name)[1] in (
        nemotron_flops._WRAPPERS)
    fusion = "%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f"
    assert nemotron_flops.trace_reduce.describe(fusion)[1].split("/")[0] not in (
        nemotron_flops._WRAPPERS)


def test_readers_return_nothing_where_the_program_has_neither(traced_cell):
    """A checkout from before the latent layer: no ``apex.moe`` or
    ``apex.ssm`` scope in the step, no ``moe_load`` among its metrics.  Every
    reader returns ``None`` and raises nothing."""
    ctx, read = traced_cell
    ctx.step_metrics = {"loss": np.zeros(5)}
    ctx.hlo = "\n".join([
        "ENTRY %main.1 (p: bf16[1024,1024]) -> bf16[1024,1024] {",
        "  ROOT %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={"
        'op_name="jit(step)/apex.optimizer/mul" stack_frame_id=1}', "}"])
    assert read() == dict.fromkeys(READERS)
    ctx.hlo = ""
    assert read() == dict.fromkeys(READERS)
