"""The granite-4.0-h configuration, its plain reference, its FLOP and byte
counts and its readers: the model against the reference in float32 (loss and
every gradient leaf), the reference by layers against the reference whole,
the closed forms against the dot-generals of the traced jaxpr, what the
configuration's file has to state, the family through the harness on a tiny
cell, and the scope readers on the recorded trace."""

import json
import math
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import hybrid_flops, run, scope_reduce
from benchmark.reference import granite_hybrid as reference

ROOT = run.ROOT
CELL = "granite4_h_micro_o2.b2_seq4096"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "granite4_h_micro_o2.json")))
#: ibm-granite/granite-4.0-h-micro config.json, every number of it
PUBLISHED = {
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "max_position_embeddings": 131072,
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "vocab_size": 100352}
PUBLISHED_LAYERS = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
TINY = dict(vocab_size=512, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, shared_intermediate_size=128,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=16, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"])


def _tiny_model(cfg, **kw):
    from apex_tpu import models

    return models.GraniteHybrid(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["shared_intermediate_size"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], mamba_chunk=cfg["mamba_chunk_size"],
        **kw)


# -- the configuration's file --------------------------------------------------

def test_every_width_is_the_published_one_and_the_cut_is_on_file():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert set(CONFIG["reduced"]) == changed | {"layer_types"}
    assert CONFIG["published"]["num_hidden_layers"] == 40
    assert CONFIG["published"]["vocab_size"] == 100352
    assert CONFIG["vocab_size"] in (50176, 25088) and CONFIG["vocab_size"] * 8 >= 100352
    for key in ("tie_word_embeddings", "mamba_conv_bias"):
        assert CONFIG[key] is True
    for key in ("attention_bias", "mamba_proj_bias"):
        assert CONFIG[key] is False
    assert CONFIG["position_embedding_type"] == "nope"
    assert CONFIG["normalization_function"] == "rmsnorm"


def test_the_cut_is_a_whole_period_in_the_published_ratio():
    kinds = CONFIG["layer_types"]
    assert len(kinds) == CONFIG["num_hidden_layers"] == 10
    assert kinds == PUBLISHED_LAYERS[:10]
    assert all(PUBLISHED_LAYERS[i:i + 10] == kinds for i in range(0, 40, 10))
    ratio = lambda layers: layers.count("attention") / len(layers)
    assert ratio(kinds) == ratio(PUBLISHED_LAYERS) == 0.1


def test_assumptions_deployment_and_recipe_are_stated():
    assert {"lr, weight_decay, beta1, beta2, eps",
            "initialisation"} <= set(CONFIG["assumed"])
    for word in ("four pipeline stages", "divided two ways", "50,176",
                 "Layers are not divided"):
        assert word in CONFIG["deployment"], word
    assert CONFIG["departures"] and CONFIG["tolerance"]["reason"]
    recipe = CONFIG["recipe"]
    assert (recipe["opt_level"], recipe["compute_dtype"], recipe["loss_scale"],
            recipe["optimizer"]) == ("O2", "bfloat16", "dynamic", "adamw")
    assert (recipe["lr"], recipe["weight_decay"]) == (3e-4, 0.1)
    assert (recipe["beta1"], recipe["beta2"], recipe["eps"]) == (
        0.9, 0.999, 1e-8)
    assert any("initializer_range" in line for line in CONFIG["departures"])
    traffic = run.resolve(CELL).traffic
    assert (traffic["batch_per_chip"], traffic["seq"],
            traffic["check_sample"]) == (2, 4096, 1)
    assert traffic["seq"] % CONFIG["mamba_chunk_size"] == 0


def test_the_new_metrics_are_read_in_the_new_cell_only():
    plan = run.resolve(CELL)
    names = {m["name"] for m in plan.per_layer}
    new = {"ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline_pct"}
    assert new <= names and "mfu_pct" in names
    other = {m["name"] for m in run.resolve("gpt2_small_o2.seq1024").per_layer}
    assert not new & other


# -- the reference against the model -------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    cfg = dict(CONFIG, **TINY)
    model = _tiny_model(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 41), 1, 512)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # nothing stays at its initial 1 or 0: norm weights, D, the conv bias
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    params = tree.unflatten([leaf + 0.05 * jax.random.normal(k, leaf.shape)
                             for leaf, k in zip(leaves, keys)])

    def loss_fn(p):
        logits = model.apply({"params": p}, x)
        return jnp.mean(softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1)))

    return types.SimpleNamespace(cfg=cfg, model=model, params=params, x=x, y=y,
                                 loss_fn=loss_fn)


def _worst_leaf(got, want):
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    errs = {}
    for (path, g), (_, w) in zip(flat(got), flat(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape
        errs[jax.tree_util.keystr(path)] = (
            np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
    return max(errs.items(), key=lambda kv: kv[1]), len(errs)


def test_model_equals_reference_in_float32_loss_and_every_leaf(tiny):
    loss, grads = jax.jit(jax.value_and_grad(tiny.loss_fn))(tiny.params)
    ref_loss, ref_grads = reference.loss_and_grads(
        tiny.params, tiny.x, tiny.y, tiny.cfg)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    (name, err), leaves = _worst_leaf(grads, ref_grads)
    assert leaves == 34
    assert err < 1e-4, name
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(ref_grads))


def test_reference_by_layers_equals_reference_whole(tiny):
    ref_loss, ref_grads = reference.loss_and_grads(
        tiny.params, tiny.x, tiny.y, tiny.cfg)
    loss, grads = reference.loss_and_grads_by_layer(
        tiny.params, tiny.x, tiny.y, tiny.cfg)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert all(isinstance(g, np.ndarray)
               for g in jax.tree_util.tree_leaves(grads))
    (name, err), _ = _worst_leaf(grads, ref_grads)
    assert err < 1e-5, name


def test_a_dropped_term_fails_the_comparison(tiny):
    """The skip term ``D x`` left out of one mixer: the loss barely moves,
    the comparison's leaf test and cosine do."""
    from benchmark import compare

    ref_loss, ref_grads = reference.loss_and_grads(
        tiny.params, tiny.x, tiny.y, tiny.cfg)
    broken = jax.tree_util.tree_map(lambda a: a, tiny.params)
    broken["layer_0"]["mamba"]["D"] = jnp.zeros_like(
        broken["layer_0"]["mamba"]["D"])
    loss, grads = jax.value_and_grad(tiny.loss_fn)(broken)
    verdict = compare.verdict(float(loss), float(ref_loss), grads, ref_grads,
                              CONFIG["tolerance"])
    assert verdict["correct"] is False
    right = compare.verdict(float(ref_loss), float(ref_loss), ref_grads,
                            ref_grads, CONFIG["tolerance"])
    assert right["correct"] is True


# -- FLOPs and bytes -------------------------------------------------------------

def _matrix_flops(jaxpr):
    """2 x multiply-adds of every dot_general, sub-jaxprs included."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
                lhs[d] for d in contract)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _matrix_flops(sub)
    return total


@pytest.mark.parametrize("seq", [32, 64])
def test_forward_flops_against_the_models_jaxpr(seq):
    """The model's chunked form multiplies whole ``chunk x chunk`` blocks and
    carries the chunk states by a triangular product the closed form leaves
    out; attention off the kernel path multiplies the whole ``seq x seq``."""
    cfg, batch = dict(CONFIG, **TINY), 3
    model = _tiny_model(cfg)
    x = jnp.ones((batch, seq), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)["params"]
    counted = _matrix_flops(jax.make_jaxpr(
        lambda p: model.apply({"params": p}, x))(params).jaxpr)
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    hp, chunks = cfg["mamba_n_heads"] * cfg["mamba_d_head"], seq // q
    mamba_layers = cfg["layer_types"].count("mamba")
    other_half = mamba_layers * seq * (q * n + q * hp)
    carried = mamba_layers * 2 * chunks * chunks * hp * n
    attention_half = 2 * seq * seq * cfg["hidden_size"]
    assert counted == batch * (hybrid_flops.forward(cfg, seq) + other_half
                               + carried + attention_half)
    assert hybrid_flops.train(cfg, batch, seq) == 3 * batch * (
        hybrid_flops.forward(cfg, seq))


def test_published_sizes_and_the_scans_share():
    # the issue's count: about 3.2 MFLOP a token and layer forward
    assert hybrid_flops.ssd_forward_flops(CONFIG) == 3_178_496
    step = hybrid_flops.train(CONFIG, 2, 4096)
    assert abs(step / 42.84e12 - 1) < 0.01
    scan = hybrid_flops.ssd_train_flops(CONFIG, 8192)
    assert scan == 3 * 9 * 8192 * 3_178_496 and scan < 0.02 * step
    # x and y 8 KiB each, B and C 256 B each, dt 256 B a token and layer;
    # forward reads four and writes one, backward reads five and writes four
    assert hybrid_flops.ssd_train_bytes(CONFIG, 1) == 9 * (
        (8192 + 512 + 256 + 8192) + 2 * (8192 + 512 + 256) + 8192)
    # the least time for the scan is its bytes', on the v5e
    peaks = run.resolve(CELL).peaks["by_device_kind"]["TPU v5 lite"]
    assert (hybrid_flops.ssd_train_bytes(CONFIG, 8192) / peaks["hbm_bytes_per_s"]
            > scan / peaks["bf16_flops_per_s"])


# -- the family through the harness ----------------------------------------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_hybrid"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    loose = dict(CONFIG["tolerance"], loss_rel=0.02, grad_cos_min=0.98,
                 grad_norm_ratio=[0.9, 1.1], leaf_rel=0.5, leaf_abs=0.01)
    json.dump(dict(CONFIG, **TINY, name="hybrid_tiny", tolerance=loose),
              open(os.path.join(bench, "configs", "hybrid_tiny.json"), "w"))
    json.dump({"batch_per_chip": 2, "seq": 64, "check_sample": 1},
              open(os.path.join(bench, "traffic", "s64.json"), "w"))
    manifest["configs"] = [{"name": "hybrid_tiny", "reduced": [], "why": "test",
                            "source": "https://example.org",
                            "file": "benchmark/configs/hybrid_tiny.json"}]
    manifest["workloads"] = [{"name": "hybrid_tiny.s64", "chips": 1,
                              "config": "hybrid_tiny", "traffic": "s64",
                              "why": "test"}]
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if "workloads" not in m]
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def test_untraced_run_of_a_tiny_cell_is_correct(tiny_root, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    # a seed past 32 signed bits, as the driver's are
    result = run.run_cell("hybrid_tiny.s64", seed=2 ** 31 + 77, seconds=1.0,
                          trace=False, allow_cpu=True, root=tiny_root)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"samples_per_s", "peak_hbm_gib",
                                      "setup_s"}


def test_check_gives_the_trained_parameters_back(tiny_root):
    plan = run.resolve("hybrid_tiny.s64", tiny_root)
    cell = plan.family.build(plan.config, plan.traffic, jax.devices()[:1], 4)
    cell.first_dispatch()
    before = jax.device_get(cell.state.params)
    verdict = cell.check()
    assert verdict["correct"] is True and verdict["leaves"] == 34
    after = cell.state.params
    assert all(isinstance(a, jax.Array) and np.array_equal(a, b)
               for a, b in zip(jax.tree_util.tree_leaves(after),
                               jax.tree_util.tree_leaves(before)))
    assert cell.flops_per_step == hybrid_flops.train(plan.config, 2, 64)


def _faulty(pipe, fault):
    """The pipeline with one of the contract's faults planted in its step."""
    def step_window(state, window, k):
        if fault == "half_the_batch":       # the second sequence is left out
            window = jax.tree_util.tree_map(
                lambda a: jnp.concatenate([a[:, :1], a[:, :1]], axis=1), window)
        before = jax.tree_util.tree_map(jnp.copy, state)
        after, metrics = pipe.step_window(state, window, k)
        if fault == "state_left_unchanged":
            after = before
        elif fault == "parameters_left_unchanged":
            after = after._replace(params=before.params)
        elif fault == "one_leaf_left_unchanged":
            after.params["layer_1"]["attention"]["key"] = before.params[
                "layer_1"]["attention"]["key"]
        return after, metrics
    return types.SimpleNamespace(step_window=step_window)


@pytest.mark.parametrize("fault,fails", [
    (None, None),
    ("state_left_unchanged", "grad_norm_ratio"),
    ("parameters_left_unchanged", "update_rel"),
    ("one_leaf_left_unchanged", "update_rel"),
    ("half_the_batch", "grad_cos")])
def test_check_sees_a_planted_fault_in_the_timed_step(tiny_root, fault, fails):
    """``check()`` steps the timed pipeline once more from the initial state:
    a step that leaves state where it was, or trains on half the batch, comes
    out as not correct, by the number that is there to see it."""
    plan = run.resolve("hybrid_tiny.s64", tiny_root)
    tol = plan.config["tolerance"]
    cell = plan.family.build(plan.config, plan.traffic, jax.devices()[:1], 11)
    cell.first_dispatch()
    if fault:
        cell.pipe = _faulty(cell.pipe, fault)
    verdict = cell.check()
    assert verdict["correct"] is (fault is None), verdict
    seen = {"grad_norm_ratio": not (tol["grad_norm_ratio"][0]
                                    <= verdict["grad_norm_ratio"]
                                    <= tol["grad_norm_ratio"][1]),
            "grad_cos": verdict["grad_cos"] < tol["grad_cos_min"],
            "update_rel": verdict["update_rel"] > tol["update_rel"]}
    if fault is None:
        assert not any(seen.values())
        assert verdict["update_rel"] < 1e-4
    else:
        assert seen[fails], verdict
    if fails == "update_rel":
        assert verdict["update_rel"] == pytest.approx(1.0, abs=1e-3)
    if fault == "one_leaf_left_unchanged":
        assert verdict["update_worst_at"] == "['layer_1']['attention']['key']['kernel']"
        assert verdict["grad_cos"] >= tol["grad_cos_min"]


def test_the_hosts_adamw_is_the_programs_first_step():
    from apex_tpu import training

    recipe = CONFIG["recipe"]
    key_p, key_g = jax.random.split(jax.random.PRNGKey(3))
    p0 = jax.random.normal(key_p, (257, 33)) * 0.02
    g = jax.random.normal(key_g, (257, 33)) * jnp.logspace(-12, -2, 33)
    tx = training.adam(recipe["lr"], weight_decay=recipe["weight_decay"])
    p1, state = tx.update(g, tx.init(p0), p0)
    family = run.resolve(CELL).family
    want = family.adamw_first_step(np.asarray(p0), np.asarray(g), recipe)
    assert want.dtype == np.float32
    np.testing.assert_allclose(np.asarray(p1) - np.asarray(p0),
                               want - np.asarray(p0), rtol=2e-4, atol=1e-10)
    np.testing.assert_allclose(np.asarray(state.exp_avg) / (1 - recipe["beta1"]),
                               np.asarray(g), rtol=1e-6)
    error = family.update_error({"w": np.asarray(p0)}, {"w": np.asarray(p1)},
                                {"w": np.asarray(g)}, recipe)
    assert error["update_rel"] < 1e-4 and error["update_worst_at"] == "['w']"


# -- the scope readers on the recorded trace --------------------------------------

READERS = ("ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline_pct")


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
    ctx = types.SimpleNamespace(workload=CELL, k=1, hlo="", chips=1,
                                samples_per_step=8192, peaks=peaks)
    read = lambda: {name: run._load(ROOT, "layer_metrics", name).compute(ctx)
                    for name in READERS}
    return ctx, read


def test_scope_readers_on_the_recorded_trace(traced_cell):
    ctx, read = traced_cell
    meta = 'metadata={op_name="jit(step)/%s/dot_general" stack_frame_id=1}'
    mixer = "jvp(apex.forward)/GraniteHybrid/layer_0/mamba/apex.ssm"
    ctx.hlo = "\n".join([
        "ENTRY %main.1 (p: bf16[1024,1024]) -> bf16[1024,1024] {",
        "  %copy-done = bf16[8]{0} copy-done(%copy-start)",
        "  %convolution_tanh_fusion.2 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % (mixer + "/in_proj"),
        "  %convolution_tanh_fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % ("transpose(" + mixer + "/apex.ssm.scan)"),
        "  ROOT %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, "
        + meta % "apex.optimizer", "}"])
    got = read()
    assert got["ssm_ms_per_step"] == pytest.approx(158231e-6 / 6)
    assert 0 < got["ssm_scan_ms_per_step"] < got["ssm_ms_per_step"]
    assert got["ssm_scan_ms_per_step"] == pytest.approx(
        got["ssm_ms_per_step"] / 2, rel=0.3)
    least_ms = 1e3 * hybrid_flops.ssd_train_bytes(CONFIG, 8192) / 819e9
    assert got["ssm_scan_roofline_pct"] == pytest.approx(
        100 * least_ms / got["ssm_scan_ms_per_step"])
    # the same time by the innermost scope, for PERF.md's breakdown
    by_scope = json.load(open(os.path.join(
        os.path.dirname(scope_reduce.__file__), "out", CELL, "scopes.json")))[
            "ms_per_step_by_innermost_scope"]
    assert set(by_scope) == {"apex.ssm", "apex.ssm.scan", "apex.optimizer",
                             "none"}
    assert by_scope["apex.ssm"] + by_scope["apex.ssm.scan"] == pytest.approx(
        got["ssm_ms_per_step"])
    assert by_scope["apex.ssm.scan"] == pytest.approx(
        got["ssm_scan_ms_per_step"])


def test_a_program_without_the_scopes_reads_nothing_and_raises_nothing(
        traced_cell):
    """The parent commit's program, or any other cell's."""
    ctx, read = traced_cell
    ctx.hlo = ('  %convolution_tanh_fusion.2 = bf16[8]{0} fusion(%p), '
               'kind=kOutput, metadata={op_name="jit(step)/jvp(apex.forward)/'
               'block_0/attention/dot_general"}\n')
    assert read() == dict.fromkeys(READERS)
    ctx.hlo = None
    assert read() == dict.fromkeys(READERS)
