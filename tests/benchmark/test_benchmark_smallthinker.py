"""The SmallThinker-21BA3B configuration, its FLOP and byte counts, its
family and its readers: what the configuration's file has to state (every
published number, the cut, the counts, the deployment), the manifest's new
entries by name, the closed forms against a count by hand, the family through
the harness on a tiny cell with faults planted in the step and in the model,
and the readers on the recorded trace and on the program's counter.  The
model against the reference, and the shares against the uncut layer, are in
``tests/test_smallthinker.py``."""

import importlib.util
import json
import os
import shutil
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import nemotron_flops, run, smallthinker_flops

ROOT = run.ROOT
CELL = "smallthinker_21b_a3b_o2.b1_seq16384"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "smallthinker_21b_a3b_o2.json")))
#: PowerInfer/SmallThinker-21BA3B-Instruct config.json, every number
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_theta": 1500000, "sliding_window_size": 4096, "vocab_size": 151936}
LAYOUT = [0, 1, 1, 1] * 13
READERS = ("smallthinker_window_attn_ms_per_step",
           "smallthinker_window_attn_roofline_pct",
           "smallthinker_full_attn_ms_per_step",
           "smallthinker_moe_ms_per_step",
           "smallthinker_moe_route_ms_per_step",
           "smallthinker_moe_experts_roofline_pct",
           "smallthinker_moe_load_max_over_mean")


def _controls():
    here = os.path.join(ROOT, "tests", "benchmark")
    sys.path.insert(0, here)
    spec = importlib.util.spec_from_file_location(
        "smallthinker_controls", os.path.join(here, "smallthinker_controls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


controls = _controls()


# -- the configuration's file --------------------------------------------------

def test_every_width_is_the_published_one_and_the_cut_is_on_file():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == {"num_hidden_layers", "moe_num_primary_experts",
                       "vocab_size"}
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"]
    for key in changed:
        assert CONFIG["published"][key] == PUBLISHED[key]
    # the layout lists: one whole period of the published ones
    assert CONFIG["sliding_window_layout"] == CONFIG["rope_layout"] == (
        LAYOUT[:4]) == [0, 1, 1, 1]
    assert len(CONFIG["sliding_window_layout"]) == CONFIG["num_hidden_layers"]
    assert CONFIG["moe_primary_router_apply_softmax"] is True
    assert CONFIG["norm_topk_prob"] is True
    assert CONFIG["tie_word_embeddings"] is False
    assert CONFIG["rope_scaling"] is None
    # no width among what changed; the floors of a model_config cut
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])
    assert CONFIG["moe_num_primary_experts"] >= 8
    assert CONFIG["num_hidden_layers"] >= 4
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    from apex_tpu.models import smallthinker
    assert list(smallthinker.SLIDING_WINDOW_LAYOUT) == LAYOUT
    assert list(smallthinker.ROPE_LAYOUT) == LAYOUT


def test_counts_deployment_assumptions_and_recipe_agree_with_each_other():
    counts = CONFIG["counts"]
    assert (counts["experts_held"], counts["experts_routed"],
            counts["expert_offset"]) == (16, 64, 0) == (
        CONFIG["moe_num_primary_experts"],
        CONFIG["published"]["moe_num_primary_experts"], CONFIG["expert_offset"])
    assert (counts["vocabulary_rows_held"], counts["vocabulary_rows"]) == (
        37984, 151936) == (CONFIG["vocab_size"] * 1, 4 * 37984)
    assert (counts["layers_held"], counts["layers"]) == (4, 52)
    assert counts["chips_per_layer"] == 4 == (
        counts["experts_routed"] // counts["experts_held"])
    d = CONFIG["hidden_size"]
    attention = 2 * d * 28 * 128 + 2 * d * 4 * 128
    layer = attention + d * 64 + 16 * 3 * d * 768 + 2 * d
    assert layer == 115_512_320
    assert counts["parameters"] == 656_529_920 == (
        4 * layer + 2 * 37984 * d + d)
    for word in ("Four chips share each layer", "16 of its 64 routed experts",
                 "pipeline stages", "[0, 1, 1, 1]", "[1, 37984)",
                 "656.5M", "no code stands in"):
        assert word in CONFIG["deployment"], word
    assert {"router input", "expert", "router weights", "attention bias",
            "window", "auxiliary loss", "lr, weight_decay, beta1, beta2, eps",
            "initialisation", "weights and ids"} <= set(CONFIG["assumed"])
    assert CONFIG["departures"] and CONFIG["tolerance"]["reason"]
    recipe = CONFIG["recipe"]
    assert (recipe["opt_level"], recipe["compute_dtype"], recipe["loss_scale"],
            recipe["optimizer"]) == ("O2", "bfloat16", "dynamic", "adamw")
    traffic = run.resolve(CELL).traffic
    assert (traffic["batch_per_chip"], traffic["seq"],
            traffic["check_sample"]) == (1, 16384, 1)
    # the rows a layer: a quarter of the pairs, as in the four-chip group
    assert 16384 * 6 * 16 // 64 == 24576 == 4 * 6144
    tol = CONFIG["tolerance"]
    assert 0.5 < tol["routing_agreement_min"] < tol["router_agreement_min"] <= 1
    assert 0 < tol["dense_leaf_rel"] < tol["leaf_rel"]


def test_the_manifest_resolves_the_cell_and_its_seven_readers_by_name():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "smallthinker_21b_a3b_o2"]
    assert entry["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/"
        "main/config.json")
    assert CONFIG["source"].startswith(entry["source"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "benchmark/configs/smallthinker_21b_a3b_o2.json"
    (cell,) = [c for c in manifest["workloads"]
               if c["config"] == "smallthinker_21b_a3b_o2"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "b1_seq16384", 1)
    ours = {m["name"]: m for m in manifest["per_layer"]
            if m["name"] in READERS}
    assert set(ours) == set(READERS)
    for m in ours.values():
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["moves"] == "samples_per_s"
    # appended after what was there: the Nemotron cell's entries come first
    names = lambda key: [c["name"] for c in manifest[key]]
    assert (names("configs").index("smallthinker_21b_a3b_o2")
            > names("configs").index("nemotron3_super_120b_o2"))
    assert (names("workloads").index(CELL)
            > names("workloads").index("nemotron3_super_120b_o2.b2_seq8192"))
    assert min(names("per_layer").index(n) for n in READERS) > max(
        names("per_layer").index(n) for n in names("per_layer")
        if n.startswith(("moe_", "latent_moe_", "hybrid_ssm_")))
    # the accepted expert readers keep their cells
    for m in manifest["per_layer"]:
        if m["name"].startswith(("moe_", "latent_moe_")):
            assert CELL not in m["workloads"]
    plan = run.resolve(CELL)
    resolved = {m["name"] for m in plan.per_layer}
    assert set(READERS) <= resolved and "mfu_pct" in resolved
    assert "moe_ms_per_step" not in resolved
    assert "latent_moe_ms_per_step" not in resolved
    assert plan.config["family"] == "smallthinker"
    assert plan.traffic["name"] == "b1_seq16384"
    assert not set(READERS) & {m["name"] for m in run.resolve(
        "lfm2_24b_a2b_o2.b4_seq4096").per_layer}


# -- FLOPs and bytes -------------------------------------------------------------

def test_flops_and_bytes_against_a_count_by_hand():
    assert smallthinker_flops.visible_pairs(16384, 4096) == 58_722_304 == (
        4096 * 4097 // 2 + 12288 * 4096)
    assert smallthinker_flops.visible_pairs(16384) == 134_225_920 == (
        16384 * 16385 // 2)
    assert smallthinker_flops.visible_pairs(100, 4096) == 5050
    assert smallthinker_flops.visible_pairs(5, 2) == 1 + 4 * 2
    d, seq, heads = 2560, 16384, 28 * 128
    projections = 2 * (2 * d * heads + 2 * d * 512)
    experts = 6 * 16 / 64 * 2 * 3 * d * 768
    per_token = 4 * (projections + 2 * d * 64 + experts) + 2 * d * 37984
    core = 4 * heads * (134_225_920 + 3 * 58_722_304)
    assert smallthinker_flops.forward(CONFIG, seq) == pytest.approx(
        seq * per_token + core, rel=1e-12)
    step = smallthinker_flops.train(CONFIG, 1, seq)
    assert step == 3 * smallthinker_flops.forward(CONFIG, seq)
    assert abs(step / 34.70e12 - 1) < 0.001
    # attention's core is 38% of the forward, the global layer 117.4 MFLOP a
    # token and the three window layers 154.1
    assert 0.38 < core / (seq * per_token + core) < 0.39
    assert 4 * heads * 134_225_920 / seq == pytest.approx(117.45e6, rel=1e-4)
    window = smallthinker_flops.window_attention_train_flops(CONFIG, seq)
    assert window == 3 * 4 * heads * 3 * 58_722_304
    q, kv = seq * heads * 2, seq * 512 * 2
    assert smallthinker_flops.window_attention_train_bytes(CONFIG, seq) == (
        3 * (6 * q + 6 * kv))
    assert smallthinker_flops.expert_flops_per_row(CONFIG) == 6 * d * 768
    rows = 4 * 24576
    assert smallthinker_flops.experts_train_flops(CONFIG, rows) == (
        3 * 6 * d * 768 * rows)
    assert smallthinker_flops.experts_train_bytes(CONFIG, rows) == (
        5 * 2 * d * rows + 3 * 4 * 16 * 3 * d * 768 * 2)
    peaks = run.resolve(CELL).peaks["by_device_kind"]["TPU v5 lite"]
    # both least times are the FLOPs', on the v5e
    assert (window / peaks["bf16_flops_per_s"] > smallthinker_flops
            .window_attention_train_bytes(CONFIG, seq) / peaks["hbm_bytes_per_s"])
    load = np.zeros((3, 4, 64), np.int64)
    load[..., :16], load[..., 16:] = 1500, 7
    np.testing.assert_array_equal(smallthinker_flops.held_rows(CONFIG, load),
                                  [4 * 16 * 1500] * 3)
    assert smallthinker_flops.load_counts({"moe_load": load.ravel()},
                                          CONFIG).shape == (3, 4, 64)
    assert smallthinker_flops.load_counts({}, CONFIG) is None


# -- the family through the harness ----------------------------------------------

TINY_CONFIG, TINY_TRAFFIC = controls.tiny(CONFIG, {"check_sample": 1})


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_smallthinker"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    loose = dict(CONFIG["tolerance"], loss_rel=0.02, grad_cos_min=0.999,
                 grad_norm_ratio=[0.99, 1.01], leaf_rel=0.5, leaf_abs=0.0,
                 update_rel=0.01, routing_agreement_min=0.97,
                 router_agreement_min=0.999, load_l1_rel=0.03,
                 dense_leaf_rel=0.45)
    json.dump(dict(TINY_CONFIG, name="smallthinker_tiny", tolerance=loose),
              open(os.path.join(bench, "configs", "smallthinker_tiny.json"),
                   "w"))
    json.dump(dict(TINY_TRAFFIC, name="s64"),
              open(os.path.join(bench, "traffic", "s64.json"), "w"))
    manifest["configs"] = [{"name": "smallthinker_tiny", "reduced": [],
                            "why": "test", "source": "https://example.org",
                            "file": "benchmark/configs/smallthinker_tiny.json"}]
    manifest["workloads"] = [{"name": "smallthinker_tiny.s64", "chips": 1,
                              "config": "smallthinker_tiny", "traffic": "s64",
                              "why": "test"}]
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if "workloads" not in m]
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def test_untraced_run_of_a_tiny_cell_is_correct(tiny_root, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    # a seed past 32 signed bits, as the driver's are
    result = run.run_cell("smallthinker_tiny.s64", seed=2 ** 31 + 77,
                          seconds=1.0, trace=False, allow_cpu=True,
                          root=tiny_root)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"samples_per_s", "peak_hbm_gib",
                                      "setup_s"}


def test_check_gives_the_state_back_and_the_step_reports_its_counter(
        tiny_root):
    plan = run.resolve("smallthinker_tiny.s64", tiny_root)
    cell = plan.family.build(plan.config, plan.traffic, jax.devices()[:1], 4)
    cell.first_dispatch()
    cell.state, metrics = cell.pipe.step_window(cell.state, cell.window, cell.k)
    load = np.asarray(metrics["moe_load"])
    assert load.shape == (1, 4, 16) and load.dtype == np.int32
    assert (load.sum(-1) == 2 * 64 * 4).all()
    before = jax.device_get((cell.state.params, cell.state.model_state))
    verdict = cell.check()
    assert verdict["correct"] is True, verdict
    assert len(verdict["routing_agreement"]) == 4
    assert len(verdict["router_agreement"]) == 4
    assert verdict["load_rows"] == 4 * 2 * 64 * 4
    assert 0 < verdict["rows_held"] < verdict["load_rows"]
    after = (cell.state.params, cell.state.model_state)
    assert all(isinstance(a, jax.Array) and np.array_equal(a, b)
               for a, b in zip(jax.tree_util.tree_leaves(after),
                               jax.tree_util.tree_leaves(before)))
    assert cell.flops_per_step == smallthinker_flops.train(plan.config, 2, 64)
    assert cell.samples_per_step == 2 * 64


def _faulty(pipe, fault):
    """The pipeline with one of the contract's faults planted in its step."""
    def step_window(state, window, k):
        before = jax.tree_util.tree_map(jnp.copy, state)
        after, metrics = pipe.step_window(state, window, k)
        if fault == "state_left_unchanged":
            after = before
        elif fault == "one_leaf_left_unchanged":
            after.params["layer_2"]["experts"]["w2"] = before.params[
                "layer_2"]["experts"]["w2"]
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


IN_MODEL = ("sigmoid", "silu", "rope_on_nope", "no_window", "router_n2",
            "bf16_router", "bf16_parts")


@pytest.mark.parametrize("fault,fails", [
    (None, None),
    ("state_left_unchanged", "grad_norm_ratio"),
    ("one_leaf_left_unchanged", "update_rel"),
    ("half_sequence", "grad_cos"),
    ("sigmoid", "leaf"),
    ("silu", "dense_leaf"),
    ("rope_on_nope", "dense_leaf"),
    ("no_window", "dense_leaf"),
    ("router_n2", "router"),
    ("bf16_router", "router"),
    ("bf16_parts", "router")])
def test_check_sees_a_planted_fault(tiny_root, fault, fails):
    """``check()`` steps the timed pipeline once more from the initial state.
    A step that leaves state where it was or trains on half the sequence,
    and a model with the wrong router, expert, positions, window or router
    input, or with its float32 parts rounded, each comes out as not correct,
    by the number that is there to see it."""
    plan = run.resolve("smallthinker_tiny.s64", tiny_root)
    tol = plan.config["tolerance"]
    real = plan.family.reference_mean
    try:
        config = controls.degrade(fault if fault in IN_MODEL else "base",
                                  plan.config)
        # the reference always reads the configuration as it is
        plan.family.reference_mean = lambda p0, x, y, rows, cfg, forced: real(
            p0, x, y, rows, plan.config, forced)
        cell = plan.family.build(config, plan.traffic, jax.devices()[:1], 11)
        cell.first_dispatch()
        if fault == "half_sequence":
            cell.pipe = controls.first_half_twice(cell.pipe)
        elif fault and fault not in IN_MODEL:
            cell.pipe = _faulty(cell.pipe, fault)
        verdict = cell.check()
    finally:
        controls.degrade("base", plan.config)
        plan.family.reference_mean = real
    seen = _seen(verdict, tol)
    assert verdict["correct"] is (fault is None), verdict
    if fault is None:
        assert not any(seen.values()), seen
        assert verdict["update_rel"] < 2e-3
        assert verdict["router_agreement_min"] == 1.0
    else:
        assert seen[fails], (seen, verdict)
    if fault == "one_leaf_left_unchanged":
        assert verdict["update_rel"] == pytest.approx(1.0, abs=1e-3)
        assert verdict["update_worst_at"] == "['layer_2']['experts']['w2']"
        assert not seen["grad_cos"] and not seen["leaf"]
    if fault == "rope_on_nope":
        assert "['layer_0']['attention']" in verdict["dense_leaf_err_worst_at"]
    if fault in ("router_n2", "bf16_router", "bf16_parts"):
        assert seen["router"]


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
    load = np.zeros((5, 4, 64), np.int32)
    load[..., :16], load[:, 2, 3] = 1500, 3000
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
    model = "jvp(apex.forward)/SmallThinker/"
    ctx.hlo = _hlo(
        model + "layer_1/attention/apex.attn.window",
        "transpose(" + model + "layer_3/experts/apex.moe/apex.moe.experts)",
        model + "layer_0/attention/apex.attn.full")
    got = read()
    window, moe = (got["smallthinker_window_attn_ms_per_step"],
                   got["smallthinker_moe_ms_per_step"])
    assert window > 0 and moe > 0
    assert window + moe == pytest.approx(158231e-6 / 6)
    assert got["smallthinker_full_attn_ms_per_step"] == pytest.approx(
        75813e-6 / 6)
    assert got["smallthinker_moe_route_ms_per_step"] is None
    least = max(
        smallthinker_flops.window_attention_train_flops(CONFIG, 16384)
        / 197e12,
        smallthinker_flops.window_attention_train_bytes(CONFIG, 16384)
        / 819e9)
    assert got["smallthinker_window_attn_roofline_pct"] == pytest.approx(
        100 * least / (window * 1e-3))
    rows = 4 * 16 * 1500 + 1500
    share = lambda rows: (
        100 * smallthinker_flops.experts_train_flops(CONFIG, rows) / 197e12
        / (moe * 1e-3))
    assert got["smallthinker_moe_experts_roofline_pct"] == pytest.approx(
        share(rows))
    # the rows are those of the steps the trace holds whole: the fourth alone
    load = ctx.step_metrics["moe_load"].reshape(5, 4, 64)
    load[3, :, :16] = 2000
    ctx.trace = {"steps": 1}
    assert read()["smallthinker_moe_experts_roofline_pct"] == pytest.approx(
        share(4 * 16 * 2000))
    assert got["smallthinker_moe_load_max_over_mean"] == pytest.approx(
        3000 / ((15 * 1500 + 3000) / 16))
    # the route inside the layer's scope
    ctx.hlo = _hlo(model + "layer_1/experts/apex.moe/apex.moe.route",
                   model + "layer_2/experts/apex.moe/apex.moe.combine",
                   model + "layer_2/attention/apex.rope")
    nemotron_flops._memo.clear()
    again = read()
    assert again["smallthinker_moe_ms_per_step"] == pytest.approx(
        158231e-6 / 6)
    assert 0 < again["smallthinker_moe_route_ms_per_step"] < (
        again["smallthinker_moe_ms_per_step"])
    assert again["smallthinker_window_attn_ms_per_step"] is None
    assert again["smallthinker_full_attn_ms_per_step"] is None
    assert again["smallthinker_moe_experts_roofline_pct"] is None


def test_readers_return_nothing_where_the_program_has_neither(traced_cell):
    """A checkout from before the model: no ``apex.attn.*`` or ``apex.moe``
    scope in the step, no ``moe_load`` among its metrics.  Every reader
    returns ``None`` and raises nothing."""
    ctx, read = traced_cell
    ctx.step_metrics = {"loss": np.zeros(5)}
    ctx.hlo = "\n".join([
        "ENTRY %main.1 (p: bf16[1024,1024]) -> bf16[1024,1024] {",
        "  ROOT %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={"
        'op_name="jit(step)/apex.optimizer/mul" stack_frame_id=1}', "}"])
    assert read() == dict.fromkeys(READERS)
    ctx.hlo = ""
    assert read() == dict.fromkeys(READERS)
