"""Roofline attribution engine + regression differ (ISSUE 6).

Contracts tier-1 pins here:

* **cost harvest correctness** — ``harvest_costs`` matmul/conv FLOPs
  match hand-computed counts exactly on the jaxpr path, and the XLA
  ``cost_analysis`` path (when the API exists) agrees with the walk;
* **old-jax fallback parity** — with the XLA API unavailable the
  harvest degrades to the jaxpr totals, same regions, same matmul
  count;
* **region attribution** — FLOPs group under the ``prof.capture``
  scope names, through NESTED scopes and through the backward pass
  (``transpose(jvp(...))`` wrappers peel to the forward region);
* **zero retraces** — harvesting never touches a training step's own
  jit cache (``prof.assert_trace_count`` pin);
* **MFU ledger** — boundedness classification against the ridge point,
  modeled times normalized onto the measured step, gap attribution
  read from a timeline analysis;
* **schema + differ** — timeline ``--json`` carries ``schema_version``,
  future majors are rejected with a clear error, and ``prof.regress``
  exits 0 on a self-diff and non-zero on a synthetically degraded
  summary (the acceptance criterion verbatim).
"""

import copy
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.prof import assert_trace_count, capture, roofline, timeline
from apex_tpu.prof import regress


# -- cost harvest -------------------------------------------------------------

def _matmul_fn():
    def f(x, w):
        return x @ w
    return f, (jnp.zeros((8, 16), jnp.float32),
               jnp.zeros((16, 32), jnp.float32))


def test_harvest_matmul_flops_exact_on_jaxpr_path():
    f, args = _matmul_fn()
    h = roofline.harvest_costs(f, *args, xla=False)
    assert h.source == "jaxpr"
    assert h.matmul_flops == 2 * 8 * 16 * 32
    assert h.flops == h.jaxpr_flops == h.matmul_flops
    # bytes: both operands read + output written, all fp32
    assert h.jaxpr_bytes == (8 * 16 + 16 * 32 + 8 * 32) * 4


def test_harvest_conv_flops_hand_computed():
    def f(x, k):
        return jax.lax.conv_general_dilated(
            x, k, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    x = jnp.zeros((2, 8, 8, 3), jnp.float32)
    k = jnp.zeros((3, 3, 3, 4), jnp.float32)
    h = roofline.harvest_costs(f, x, k, xla=False)
    out_elems = 2 * 6 * 6 * 4
    assert h.matmul_flops == 2 * out_elems * (3 * 3) * 3


def test_harvest_xla_path_agrees_with_walk():
    f, args = _matmul_fn()
    h = roofline.harvest_costs(f, *args, xla=True)
    if h.source == "jaxpr":
        pytest.skip("no XLA cost_analysis API on this jax")
    assert h.source in ("xla_lowered", "xla_compiled")
    # XLA charges the same 2mnk for a plain dot
    assert h.flops == pytest.approx(h.jaxpr_flops, rel=0.25)
    # the matmul split ALWAYS comes from the walk (stable numerator)
    assert h.matmul_flops == 2 * 8 * 16 * 32


def test_harvest_old_jax_fallback_parity(monkeypatch):
    """With the XLA cost API gone (old jax), the harvest must degrade
    to the jaxpr totals — same matmul count, same regions."""
    f, args = _matmul_fn()
    ref = roofline.harvest_costs(f, *args, xla=True)
    monkeypatch.setattr(roofline, "_xla_cost", lambda *a, **k: None)
    h = roofline.harvest_costs(f, *args, xla=True)
    assert h.source == "jaxpr"
    assert h.flops == h.jaxpr_flops == ref.jaxpr_flops
    assert h.matmul_flops == ref.matmul_flops
    assert h.by_region == ref.by_region


def _scoped_model():
    def f(x, w1, w2):
        with capture.scope("blockA"):
            with capture.scope("mm"):
                h = x @ w1
        with capture.scope("blockB"):
            return jnp.tanh(h) @ w2
    return f, (jnp.zeros((4, 8), jnp.float32),
               jnp.zeros((8, 8), jnp.float32),
               jnp.zeros((8, 2), jnp.float32))


def test_region_attribution_nested_scopes():
    f, args = _scoped_model()
    h = roofline.harvest_costs(f, *args, xla=False)
    assert set(h.by_region) == {"blockA", "blockB"}
    assert h.by_region["blockA"]["matmul_flops"] == 2 * 4 * 8 * 8
    assert h.by_region["blockB"]["matmul_flops"] == 2 * 4 * 8 * 2
    # depth=2 splits blockA into its nested scope
    h2 = roofline.harvest_costs(f, *args, xla=False, region_depth=2)
    assert "blockA/mm" in h2.by_region
    # every harvested flop is attributed to some region
    assert h.coverage_pct == pytest.approx(100.0)


def test_region_attribution_survives_backward_pass():
    """fwd and bwd ops of one region land in the SAME row: the
    transpose(jvp(...)) wrappers peel back to the forward scope."""
    f, args = _scoped_model()

    def train(x, w1, w2):
        return jnp.sum(f(x, w1, w2))

    g = jax.grad(train, argnums=(1, 2))
    h = roofline.harvest_costs(g, *args, xla=False)
    assert set(h.by_region) <= {"blockA", "blockB", "<unattributed>"}
    # bwd adds dgrad+wgrad: blockA's matmul flops are >= 2x forward
    assert h.by_region["blockA"]["matmul_flops"] >= 2 * (2 * 4 * 8 * 8)


def test_region_path_helper():
    assert capture.region_path("blockA/mm") == "blockA"
    assert capture.region_path("blockA/mm", depth=2) == "blockA/mm"
    assert capture.region_path("transpose(jvp(blockA))/mm") == "blockA"
    # pure call machinery yields no user region; a jit(<fn>) wrapper
    # peels to the function's own name (the best available label)
    assert capture.region_path("pjit/scan") == "<unattributed>"
    assert capture.region_path("jit(step)") == "step"
    assert capture.region_path("") == "<unattributed>"
    # review regression pin: bare machinery names drop by EXACT match —
    # user regions that merely START with one must survive
    for name in ("branch2a", "body_net", "scanner", "jitter", "condhead"):
        assert capture.region_path(f"{name}/mm") == name
    assert capture.region_path("custom_vjp_call") == "<unattributed>"
    # conv backward machinery peels like custom_*: dgrad/wgrad land on
    # the forward conv's ledger row instead of splitting off (ISSUE 18)
    assert capture.region_path(
        "transpose(jvp(stage1))/conv_general_dilated_transpose_lhs"
    ) == "stage1"
    assert capture.region_path(
        "stage1/conv_general_dilated_transpose_rhs", depth=2) == "stage1"
    assert capture.region_path(
        "conv_general_dilated_transpose_lhs/mm") == "mm"
    assert capture.region_path("conv_general_dilated") == "<unattributed>"


def test_harvest_never_retraces_the_training_step():
    """The acceptance trace-count pin: harvesting uses its own jit
    instance, so the step's cache neither grows nor is perturbed."""
    def step_fn(state, b):
        return state + jnp.sum(b), jnp.sum(b)

    step = jax.jit(step_fn)
    b = jnp.ones((4, 4), jnp.float32)
    with assert_trace_count(step, 1):
        s, _ = step(jnp.float32(0.0), b)
    with assert_trace_count(step, 0):
        roofline.harvest_costs(step_fn, jnp.float32(0.0), b)
        roofline.harvest_costs(step, jnp.float32(0.0), b, xla=False)
        s, _ = step(s, b)


# -- MFU ledger ---------------------------------------------------------------

def _toy_harvest():
    # two regions: one past the ridge (compute), one far below (memory)
    return roofline.CostHarvest(
        flops=2e9, bytes=2e7, source="jaxpr", matmul_flops=1.9e9,
        jaxpr_flops=2e9, jaxpr_bytes=2e7,
        by_region={
            "dense": {"flops": 1.9e9, "bytes": 4e6,
                      "matmul_flops": 1.9e9, "ops": 3},
            "norm": {"flops": 1e8, "bytes": 1.6e7,
                     "matmul_flops": 0.0, "ops": 7},
        })


def test_mfu_ledger_classification_and_normalization():
    peaks = {"flops": 100e12, "hbm_gb_s": 1000.0, "source": "test"}
    led = roofline.mfu_ledger(_toy_harvest(), step_time_s=1e-3,
                              peaks=peaks)
    assert led["schema_version"] == timeline.SCHEMA_VERSION
    by = {r["region"]: r for r in led["regions"]}
    # ridge = 100e12 / 1e12 = 100 flop/byte
    assert by["dense"]["bound"] == "compute"     # 1.9e9/4e6 = 475 > 100
    assert by["norm"]["bound"] == "memory"       # 1e8/1.6e7 = 6.25 < 100
    # modeled times normalized onto the measured step
    assert sum(r["modeled_ms"] for r in led["regions"]) \
        == pytest.approx(1.0, rel=0.01)
    t = led["total"]
    assert t["step_ms"] == 1.0
    assert t["achieved_tflops"] == pytest.approx(2.0, rel=0.01)
    assert t["mfu_pct"] == pytest.approx(100 * 1.9e9 / 1e-3 / 100e12,
                                         rel=0.01)
    assert led["coverage_pct"] == pytest.approx(100.0)


def test_mfu_ledger_top_truncation_and_json_clean():
    led = roofline.mfu_ledger(_toy_harvest(), step_time_s=1e-3,
                              peaks={"flops": 1e12, "hbm_gb_s": 100.0},
                              top=1)
    assert len(led["regions"]) == 1 and led["regions_dropped"] == 1
    json.dumps(led)                      # BENCH_EXTRA-safe
    assert "roofline ledger" in roofline.format_ledger(led)


def test_mfu_ledger_gap_attribution_from_timeline():
    events = [
        {"t": 0.0, "kind": "run", "meta": {}},
        {"t": 0.3, "kind": "retrace", "program": "hot", "step": 0,
         "n_traces": 1, "first": True, "new_sig": True, "sig": "s",
         "dur": 0.3},
        {"t": 0.3, "kind": "window", "step": 0, "k": 4, "n_valid": 4,
         "dur": 0.3, "gap": 0.0, "program": "hot"},
        {"t": 0.5, "kind": "loader_wait", "dur": 0.05, "qdepth": 0},
        {"t": 0.6, "kind": "window", "step": 4, "k": 4, "n_valid": 4,
         "dur": 0.1, "gap": 0.2, "program": "hot"},
        {"t": 0.9, "kind": "window", "step": 8, "k": 4, "n_valid": 4,
         "dur": 0.1, "gap": 0.2, "program": "hot"},
    ]
    ta = timeline.analyze(events)
    assert ta["retraces"]["compile_s"] == 0.3
    led = roofline.mfu_ledger(_toy_harvest(), timeline=ta,
                              peaks={"flops": 1e12, "hbm_gb_s": 100.0},
                              best_window_step_s=0.02)
    gap = led["gap"]
    assert gap["compile_pct"] is not None and gap["compile_pct"] > 0
    assert gap["dispatch_gap_pct"] == ta["attribution"]["dispatch_gap_pct"]
    assert gap["host_other_pct"] is not None
    # steady step from the stream (elapsed/steps), best window given
    assert 0 <= gap["steady_vs_best_pct"] <= 100
    # step time fell back to the stream's elapsed/steps
    assert led["total"]["step_ms"] == pytest.approx(
        ta["elapsed_s"] / ta["steps"] * 1e3, rel=0.01)


def test_load_peaks_reads_bench_extra(tmp_path):
    p = tmp_path / "BENCH_EXTRA.json"
    p.write_text(json.dumps({
        "measured_matmul_tflops": 127.4, "peak_bf16_tflops": 197.0,
        "resnet50": {"prof_measured": {"by_category": [
            {"category": "loop fusion", "gb_per_s": 881.0}]}}}))
    pk = roofline.load_peaks(str(p))
    assert pk["flops"] == pytest.approx(127.4e12)
    assert pk["hbm_gb_s"] == 881.0
    assert pk["bw_source"] == "measured_loop_fusion"
    # a directory works too; a missing file is an error, not a default
    assert roofline.load_peaks(str(tmp_path))["flops"] \
        == pytest.approx(127.4e12)
    with pytest.raises(ValueError, match="no usable peaks artifact"):
        roofline.load_peaks(str(tmp_path / "nope.json"))


def test_load_peaks_device_table_and_unknown_device():
    """No artifact: the published peaks of the NAMED device; a device
    that is not in the table (the CPU this suite runs on) raises
    instead of borrowing the v5e's numbers."""
    pk = roofline.load_peaks(device_kind="TPU v5 lite")
    assert pk["flops"] == 197e12 and pk["hbm_gb_s"] == 819.0
    with pytest.raises(ValueError, match="unknown device"):
        roofline.load_peaks()
    with pytest.raises(ValueError, match="unknown device"):
        roofline.device_peaks("TPU v99")


def test_roofline_cli_json(tmp_path, capsys, monkeypatch):
    mod = tmp_path / "roofline_cli_target.py"
    # big enough that GFLOP rounding (3 decimals) keeps the signal
    mod.write_text(
        "import jax.numpy as jnp\n"
        "def entry():\n"
        "    def f(x, w):\n"
        "        return x @ w\n"
        "    return f, (jnp.zeros((256, 512)), jnp.zeros((512, 512)))\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    rc = roofline.main(["--fn", "roofline_cli_target:entry", "--no-xla",
                        "--step-ms", "1.0", "--json",
                        "--device-kind", "TPU v5 lite"])
    assert rc == 0
    led = json.loads(capsys.readouterr().out)
    assert led["total"]["matmul_flops_g"] == pytest.approx(
        2 * 256 * 512 * 512 / 1e9, rel=0.01)
    assert led["schema_version"] == timeline.SCHEMA_VERSION


# -- schema versioning --------------------------------------------------------

def test_timeline_json_carries_schema_version():
    a = timeline.analyze([{"t": 0.0, "kind": "run", "meta": {}}])
    assert a["schema_version"] == timeline.SCHEMA_VERSION
    timeline.check_schema_version(a)          # current: accepted
    timeline.check_schema_version({})         # absent: accepted (old)
    timeline.check_schema_version({"schema_version": "0.9"})  # older major


def test_future_schema_major_rejected_with_clear_error():
    with pytest.raises(ValueError, match="FUTURE major"):
        timeline.check_schema_version({"schema_version": "99.0"},
                                      where="base.json")
    with pytest.raises(ValueError, match="unparseable"):
        timeline.check_schema_version({"schema_version": "banana"})


# -- prof.regress -------------------------------------------------------------

def _analysis():
    events = [
        {"t": 0.0, "kind": "run", "meta": {"example": "t"}},
        {"t": 0.05, "kind": "retrace", "program": "hot", "step": 0,
         "n_traces": 1, "first": True, "new_sig": True, "sig": "s",
         "dur": 0.04},
        {"t": 0.1, "kind": "window", "step": 0, "k": 4, "n_valid": 4,
         "dur": 0.05, "gap": 0.0, "program": "hot"},
        {"t": 0.2, "kind": "window", "step": 4, "k": 4, "n_valid": 4,
         "dur": 0.05, "gap": 0.01, "program": "hot"},
        {"t": 0.3, "kind": "window", "step": 8, "k": 4, "n_valid": 4,
         "dur": 0.05, "gap": 0.01, "program": "hot"},
    ]
    return timeline.analyze(events)


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_regress_self_diff_exits_zero(tmp_path, capsys):
    a = _analysis()
    rc = regress.main([_write(tmp_path, "a.json", a),
                       _write(tmp_path, "b.json", a)])
    assert rc == 0
    assert "0 regression(s)" in capsys.readouterr().out


def test_regress_degraded_exits_nonzero_and_names_metrics(tmp_path,
                                                          capsys):
    a = _analysis()
    bad = copy.deepcopy(a)
    bad["steps_per_s"] /= 2.0
    bad["step_time"]["p50_ms"] *= 3.0
    bad["retraces"]["retraces"] = 2
    rc = regress.main([_write(tmp_path, "a.json", a),
                       _write(tmp_path, "b.json", bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "steps_per_s" in out and "p50_ms" in out \
        and "retraces.retraces" in out


def test_regress_json_is_schema_versioned(tmp_path, capsys):
    """ISSUE 10 satellite: --json output is machine-readable for CI —
    schema-versioned like timeline --json, regressions as structured
    entries, and round-trips check_schema_version."""
    a = _analysis()
    bad = copy.deepcopy(a)
    bad["steps_per_s"] /= 2.0
    rc = regress.main([_write(tmp_path, "a.json", a),
                       _write(tmp_path, "b.json", bad), "--json"])
    assert rc == 1
    diff = json.loads(capsys.readouterr().out)
    assert diff["schema_version"] == timeline.SCHEMA_VERSION
    timeline.check_schema_version(diff, "regress --json")
    metrics = {e["metric"] for e in diff["regressions"]}
    assert "steps_per_s" in metrics
    entry = next(e for e in diff["regressions"]
                 if e["metric"] == "steps_per_s")
    assert set(entry) >= {"metric", "base", "cur", "ratio", "tol_pct",
                          "direction"}


def test_regress_rejects_future_schema_major(tmp_path, capsys):
    a = _analysis()
    fut = dict(a, schema_version="99.0")
    rc = regress.main([_write(tmp_path, "a.json", a),
                       _write(tmp_path, "b.json", fut)])
    assert rc == 2
    assert "FUTURE major" in capsys.readouterr().err


def test_regress_tolerance_override(tmp_path):
    a = _analysis()
    slower = copy.deepcopy(a)
    slower["steps_per_s"] *= 0.93          # 7% down: inside default 10%
    base, cur = (_write(tmp_path, "a.json", a),
                 _write(tmp_path, "b.json", slower))
    assert regress.main([base, cur]) == 0
    assert regress.main([base, cur, "--tol", "steps_per_s=2"]) == 1
    # loosening the other way passes a big hit
    slower2 = copy.deepcopy(a)
    slower2["steps_per_s"] *= 0.5
    cur2 = _write(tmp_path, "c.json", slower2)
    assert regress.main([base, cur2]) == 1
    assert regress.main([base, cur2, "--tol", "steps_per_s=60"]) == 0


def test_regress_bench_summary_inputs(tmp_path):
    base = {"resnet50": {"ms_per_step_o2": 50.0,
                         "images_per_sec_o2": 2560.0},
            "telemetry": {"overhead_ratio": 1.07}}
    cur = copy.deepcopy(base)
    cur["resnet50"]["ms_per_step_o2"] = 61.0
    rc = regress.main([_write(tmp_path, "a.json", base),
                       _write(tmp_path, "b.json", cur)])
    assert rc == 1
    # identical bench summaries self-diff clean
    assert regress.main([_write(tmp_path, "c.json", base),
                         _write(tmp_path, "d.json", base)]) == 0


def test_regress_pct_point_slack_absorbs_noise(tmp_path):
    """A 0.0 -> 0.3 stall-percentage wobble is noise, not a failure;
    an integer counter going 0 -> 1 still fails."""
    base = {"attribution": {"loader_stall_pct": 0.0},
            "retraces": {"retraces": 0}}
    noisy = {"attribution": {"loader_stall_pct": 0.3},
             "retraces": {"retraces": 0}}
    assert regress.main([_write(tmp_path, "a.json", base),
                         _write(tmp_path, "b.json", noisy)]) == 0
    worse = {"attribution": {"loader_stall_pct": 0.0},
             "retraces": {"retraces": 1}}
    assert regress.main([_write(tmp_path, "a2.json", base),
                         _write(tmp_path, "b2.json", worse)]) == 1


def test_regress_diff_summaries_direction_table():
    d = regress.diff_summaries(
        {"x_ms": 10.0, "y_per_s": 100.0, "mystery": 1.0},
        {"x_ms": 10.5, "y_per_s": 200.0, "mystery": 99.0})
    assert d["regressions"] == []
    assert [e["metric"] for e in d["improvements"]] == ["y_per_s"]
    assert d["skipped"] == 1               # unclassifiable never fails


# -- bench integration shape --------------------------------------------------

def test_bench_harvest_cross_check_shape():
    """The bench gate's contract in miniature: a harvested matmul count
    within 10% of a hand formula passes; the jaxpr walk on a BERT-like
    block reproduces 6*N*B*S for a dense tower."""
    B, S, H = 2, 8, 16

    def f(x, w1, w2):
        # two dense layers + their backward = 6 * (H*H * 2) * B*S flops
        h = jnp.tanh(x @ w1)
        return jnp.sum(h @ w2)

    g = jax.grad(f, argnums=(1, 2))
    x = jnp.zeros((B * S, H), jnp.float32)
    w = jnp.zeros((H, H), jnp.float32)
    h = roofline.harvest_costs(g, x, w, w, xla=False)
    # 5 dots of 2*(B*S)*H*H each: 2 fwd, w1/w2 wgrads, ONE dgrad (x is
    # an input, so layer 1 needs no dgrad) — the per-layer 6N rule
    # minus the first layer's missing dgrad
    analytic = 5 * (2 * H * H) * B * S
    assert h.matmul_flops == pytest.approx(analytic, rel=0.10)
