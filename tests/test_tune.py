"""apex_tpu.tune — autotuner registry/harness/cache lifecycle (ISSUE 14).

CPU-runnable by design: the tuner's measurement runs the REAL Pallas
kernels in interpret mode (the fused_bn_act/xentropy tier-parity
pattern), the cache lifecycle is pure host JSON, and dispatch consults
are trace-time dict lookups.  Covered here:

* config roundtrip + process-restart survival (reload from disk only);
* stale-entry invalidation when a kernel bumps its registered version;
* corrupt/partial cache files fall back to defaults loudly-ONCE;
* deterministic tuner runs on CPU (interpret mode, seeded candidate
  order, injected deterministic timer);
* ledger-driven candidate prioritization (memory- vs compute-bound
  verdicts reorder the search);
* every registered kernel dispatches through the cache with outputs
  bitwise-identical to its default config (tolerance for flash
  attention's reordered online softmax — its oracle contract);
* tune telemetry events + the tuned_kernel_pct gauge;
* the python -m apex_tpu.tune CLI (tune one kernel / show table /
  refuses to measure off-TPU without --interpret).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.telemetry as telemetry
from apex_tpu.tune import dispatch, measure, registry, space, store
from apex_tpu.tune.__main__ import main as tune_main

registry.load_builtin()


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Isolated config cache: fresh file path, cleared memo/stats, the
    env override pointing dispatch at it."""
    path = str(tmp_path / "tune_configs.json")
    monkeypatch.setenv("APEX_TPU_TUNE_CACHE", path)
    store._STATE["memo_path"] = None
    store._STATE["memo"] = None
    store._STATE["warned"] = set()
    dispatch.reset_stats()
    yield path
    store._STATE["memo_path"] = None
    store._STATE["memo"] = None
    dispatch.reset_stats()


def _fresh_reload(path):
    """Simulate a process restart: drop every in-memory trace and read
    the persisted file back."""
    store._STATE["memo_path"] = None
    store._STATE["memo"] = None
    return store.load(path, reload=True)


# -- space helpers (the hoisted VMEM math) ------------------------------------

def test_space_is_the_one_home_for_vmem_math():
    import importlib
    # (the package __init__ re-exports the FUNCTION under this name)
    fln = importlib.import_module(
        "apex_tpu.normalization.fused_layer_norm")

    # the kernel aliases ARE the shared constants
    assert fln._VMEM_BUDGET_BYTES == space.VMEM_BUDGET_BYTES
    assert fln._SUBLANE_ROWS == space.SUBLANE_ROWS
    # and the kernel's row picker delegates to the same function
    for n1, n2, bpe in ((32768, 768, 22), (32768, 4096, 22),
                       (4, 768, 22), (32768, 16384, 28)):
        assert fln._pick_rows(n1, n2, bpe) == space.pick_rows(n1, n2, bpe)
    # width gate equivalence (fused_layer_norm's bwd footprint)
    assert fln._kernel_max_width(4) == space.max_width(3 * 4 + 16)


def test_space_row_block_candidates_dedupe_clamped_blocks():
    # at width 4096 fp32-bwd footprint the budget admits ~100 rows, so
    # 128/256/512/1024 all clamp to the same effective block — only one
    # survives alongside the genuinely distinct small blocks
    cands = space.row_block_candidates(32768, 4096, 28)
    assert sorted(set(cands)) == sorted(cands)
    effs = {space.pick_rows(32768, 4096, 28, row_block=b) for b in cands}
    assert len(effs) == len(cands)


def test_pow2_bucket():
    assert [space.pow2_bucket(n) for n in (1, 2, 3, 64, 65, 1024)] \
        == [1, 2, 4, 64, 128, 1024]


# -- cache lifecycle ----------------------------------------------------------

def test_config_roundtrip_survives_restart(tune_cache):
    key = store.put("fused_layer_norm", 1, "r64_w128_i4",
                    {"row_block": 32}, meta={"best_ms": 0.5},
                    path=tune_cache)
    assert key == "cpu|fused_layer_norm|v1|r64_w128_i4"
    assert store.lookup("fused_layer_norm", 1, "r64_w128_i4",
                        path=tune_cache) == {"row_block": 32}
    # "restart": only the persisted file survives
    _fresh_reload(tune_cache)
    assert store.lookup("fused_layer_norm", 1, "r64_w128_i4",
                        path=tune_cache) == {"row_block": 32}
    ents = store.entries(tune_cache)
    assert len(ents) == 1 and ents[0]["meta"]["best_ms"] == 0.5


def test_version_bump_invalidates_stale_entries(tune_cache):
    store.put("fused_layer_norm", 1, "r64_w128_i4", {"row_block": 32},
              path=tune_cache)
    # the bumped kernel never sees the v1 entry
    assert store.lookup("fused_layer_norm", 2, "r64_w128_i4",
                        path=tune_cache) is None
    # and the garbage collector drops it from disk
    assert store.prune_stale({"fused_layer_norm": 2},
                             path=tune_cache) == 1
    _fresh_reload(tune_cache)
    assert store.lookup("fused_layer_norm", 1, "r64_w128_i4",
                        path=tune_cache) is None
    assert store.entries(tune_cache) == []


def test_corrupt_cache_falls_back_loudly_once(tune_cache, capsys):
    with open(tune_cache, "w") as f:
        f.write('{"schema": 1, "entries": {TRUNCATED')
    assert store.lookup("fused_layer_norm", 1, "b", path=tune_cache) is None
    assert store.lookup("xentropy", 1, "b", path=tune_cache) is None
    err = capsys.readouterr().err
    # loudly: the fallback is announced; once: a single line for both
    assert err.count("falling back to built-in default configs") == 1
    assert "corrupt" in err
    # a later put repairs the file
    store.put("xentropy", 1, "r32_h128", {"row_block": 64},
              path=tune_cache)
    _fresh_reload(tune_cache)
    assert store.lookup("xentropy", 1, "r32_h128",
                        path=tune_cache) == {"row_block": 64}


def test_partial_entries_are_skipped_not_fatal(tune_cache, capsys):
    with open(tune_cache, "w") as f:
        json.dump({"schema": 1, "entries": {
            "cpu|xentropy|v1|r32_h128": {"kernel": "xentropy"},  # no config
            "cpu|fused_layer_norm|v1|b": {
                "kernel": "fused_layer_norm", "version": 1, "bucket": "b",
                "device_kind": "cpu", "config": {"row_block": 16}},
        }}, f)
    assert store.lookup("xentropy", 1, "r32_h128", path=tune_cache) is None
    assert store.lookup("fused_layer_norm", 1, "b",
                        path=tune_cache) == {"row_block": 16}
    assert "partial" in capsys.readouterr().err


def test_future_schema_is_not_misread(tune_cache, capsys):
    with open(tune_cache, "w") as f:
        json.dump({"schema": 99, "entries": {
            "cpu|xentropy|v1|b": {"config": {"row_block": 8}}}}, f)
    assert store.lookup("xentropy", 1, "b", path=tune_cache) is None
    assert "newer" in capsys.readouterr().err


# -- deterministic tuner runs on CPU ------------------------------------------

def _fake_timer(model):
    """Deterministic injected timer: seconds from a pure function of
    the config (no device clock involved)."""
    def timer(cfg, run):
        run()
        return model(cfg)
    return timer


def test_tuner_is_deterministic_on_cpu(tune_cache):
    # n1=1024 keeps every row-block candidate a DISTINCT effective
    # block (at tiny n1 the effective-dedupe collapses the big blocks
    # onto the default — covered separately below)
    shape = {"n1": 1024, "n2": 128, "dtype": "float32"}
    model = lambda cfg: 1e-3 * (1 + abs(cfg["row_block"] - 64))

    runs = []
    for _ in range(2):
        _fresh_reload(tune_cache)
        res = measure.tune_kernel("fused_layer_norm", shape, seed=7,
                                  interpret=True,
                                  measure=_fake_timer(model),
                                  path=tune_cache)
        runs.append(res)
    a, b = runs
    # same winner, same candidate visit order, same measurements
    assert a.config == b.config == {"row_block": 64}
    assert a.order == b.order
    assert a.best_ms == b.best_ms
    assert a.source == "interpret"
    # a different seed may reorder, but the min is order-independent
    c = measure.tune_kernel("fused_layer_norm", shape, seed=8,
                            interpret=True, measure=_fake_timer(model),
                            path=tune_cache)
    assert c.config == {"row_block": 64}


def test_tuner_refuses_to_measure_off_tpu_without_interpret():
    if jax.default_backend() == "tpu":
        pytest.skip("on-chip run: the refusal is the CPU contract")
    with pytest.raises(RuntimeError, match="only runs on TPU"):
        measure.tune_kernel("fused_layer_norm",
                            {"n1": 8, "n2": 128}, store_result=False)


def test_tuned_never_slower_than_default_by_construction(tune_cache):
    # the default config is always a candidate, so best <= default even
    # under an adversarial timer that makes everything else slower
    model = lambda cfg: 1e-3 * (100.0 if cfg["row_block"] != 256 else 1.0)
    res = measure.tune_kernel("fused_layer_norm",
                              {"n1": 64, "n2": 128}, interpret=True,
                              measure=_fake_timer(model), path=tune_cache)
    assert res.config == res.default_config == {"row_block": 256}
    assert res.tuned_over_default == 1.0


def test_oracle_rejects_wrong_outputs(tune_cache):
    from apex_tpu.tune.registry import KernelSpec, TuneCase

    def build(shape, interpret):
        def run(cfg):
            # a "kernel" whose non-default config computes WRONG values
            base = jnp.arange(8, dtype=jnp.float32)
            return base * (1.0 if cfg["blk"] == 1 else 1.5)
        return TuneCase(run=run)

    spec = KernelSpec(
        name="_test_wrong", version=1, params=("blk",), kind="memory",
        exact=True, defaults=lambda s: {"blk": 1},
        candidates=lambda s, b: [{"blk": 2}, {"blk": 3}],
        constraint=lambda s, c: True, build=build,
        bucket=lambda s: "b", small_shape={}, example_shape={})
    model = lambda cfg: 1e-6 * cfg["blk"]   # wrong configs look faster
    res = measure.tune_kernel(spec, {}, interpret=True,
                              measure=_fake_timer(model), path=tune_cache)
    assert res.rejected_oracle == 2
    assert res.config == {"blk": 1}         # the wrong ones cannot win


def test_constraint_rejects_before_timing(tune_cache):
    from apex_tpu.tune.registry import KernelSpec, TuneCase

    timed = []

    def build(shape, interpret):
        def run(cfg):
            return jnp.zeros(4)
        return TuneCase(run=run)

    spec = KernelSpec(
        name="_test_constraint", version=1, params=("blk",),
        kind="memory", exact=True, defaults=lambda s: {"blk": 8},
        candidates=lambda s, b: [{"blk": 16}, {"blk": 4096}],
        constraint=lambda s, c: c["blk"] <= 64, build=build,
        bucket=lambda s: "b", small_shape={}, example_shape={})

    def timer(cfg, run):
        timed.append(dict(cfg))
        return 1e-3
    res = measure.tune_kernel(spec, {}, interpret=True, measure=timer,
                              path=tune_cache)
    assert res.rejected_constraint == 1
    assert {"blk": 4096} not in timed       # never timed, never compiled


def test_bound_from_ledger_reorders_candidates():
    spec = registry.get_spec("flash_attention")
    ledger_mem = {"regions": [
        {"region": "encoder/attention", "bound": "memory",
         "modeled_ms": 10.0},
        {"region": "mlp", "bound": "compute", "modeled_ms": 50.0}]}
    ledger_cmp = {"regions": [
        {"region": "encoder/attention", "bound": "compute",
         "modeled_ms": 10.0}]}
    assert measure.bound_from_ledger(ledger_mem, spec) == "memory"
    assert measure.bound_from_ledger(ledger_cmp, spec) == "compute"
    # no attention-ish region -> None (the spec's own kind decides)
    assert measure.bound_from_ledger({"regions": [
        {"region": "optimizer", "bound": "memory"}]}, spec) is None

    shape = dict(spec.small_shape)
    mem = spec.candidates(shape, "memory")
    mem.sort(key=lambda c: spec.priority(shape, c, "memory"))
    cmp_ = spec.candidates(shape, "compute")
    cmp_.sort(key=lambda c: spec.priority(shape, c, "compute"))
    area = lambda c: c["block_q"] * c["block_k"]
    assert area(mem[0]) == min(area(c) for c in mem)
    assert area(cmp_[0]) == max(area(c) for c in cmp_)


# -- dispatch integration: every registered kernel consults the cache ---------

def test_layer_norm_dispatch_is_bitwise_with_tuned_config(tune_cache):
    from apex_tpu.normalization.fused_layer_norm import (TUNE_VERSION,
                                                         fused_layer_norm,
                                                         tune_bucket)
    x = jnp.linspace(-2, 2, 64 * 128, dtype=jnp.float32).reshape(64, 128)
    w = jnp.linspace(0.5, 1.5, 128, dtype=jnp.float32)
    b = jnp.linspace(-0.1, 0.1, 128, dtype=jnp.float32)
    base = fused_layer_norm(x, (128,), w, b, interpret=True)
    assert dispatch.dispatch_stats()["by_kernel"][
        "fused_layer_norm"]["misses"] >= 1

    store.put("fused_layer_norm", TUNE_VERSION, tune_bucket(64, 128, 4),
              {"row_block": 16}, path=tune_cache)
    tuned = fused_layer_norm(x, (128,), w, b, interpret=True)
    stats = dispatch.dispatch_stats()["by_kernel"]["fused_layer_norm"]
    assert stats["hits"] >= 1 and stats["tuned"]
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tuned))


def test_quantized_matmul_dispatch_is_bitwise_with_tuned_config(tune_cache):
    from apex_tpu.quant.kernels import (TUNE_VERSION, quantized_matmul,
                                        tune_bucket)
    x = jnp.linspace(-1, 1, 64 * 128, dtype=jnp.float32).reshape(64, 128)
    w = jnp.linspace(-0.5, 0.5, 128 * 128,
                     dtype=jnp.float32).reshape(128, 128)
    base = quantized_matmul(x, w, x_scale=0.01, interpret=True)
    store.put("quantized_matmul", TUNE_VERSION,
              tune_bucket(64, 128, 128, 4),
              {"block_m": 8, "block_n": 128}, path=tune_cache)
    tuned = quantized_matmul(x, w, x_scale=0.01, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tuned))
    assert dispatch.dispatch_stats()["by_kernel"][
        "quantized_matmul"]["tuned"]


def test_flash_dispatch_consults_and_matches_default(tune_cache):
    from apex_tpu.ops.flash_attention import (TUNE_VERSION,
                                              flash_attention,
                                              tune_bucket)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 256, 2, 64), jnp.float32)
    k = jnp.asarray(rs.randn(1, 256, 2, 64), jnp.float32)
    v = jnp.asarray(rs.randn(1, 256, 2, 64), jnp.float32)
    base = flash_attention(q, k, v, causal=True, interpret=True)
    store.put("flash_attention", TUNE_VERSION,
              tune_bucket(256, 256, 64, True, False, False),
              {"block_q": 128, "block_k": 128}, path=tune_cache)
    tuned = flash_attention(q, k, v, causal=True, interpret=True)
    stats = dispatch.dispatch_stats()["by_kernel"]["flash_attention"]
    assert stats["hits"] >= 1
    # flash's oracle contract: tolerance, not bitwise (online softmax
    # reorders with the KV block)
    np.testing.assert_allclose(np.asarray(base), np.asarray(tuned),
                               rtol=2e-5, atol=2e-6)


def test_explicit_blocks_and_bad_entries_bypass_the_cache(tune_cache):
    from apex_tpu.normalization.fused_layer_norm import (TUNE_VERSION,
                                                         fused_layer_norm,
                                                         tune_bucket)
    x = jnp.ones((64, 128), jnp.float32)
    # unknown keys / non-int values are rejected as a miss, not passed
    # through to pallas_call
    store.put("fused_layer_norm", TUNE_VERSION, tune_bucket(64, 128, 4),
              {"row_block": 16, "exotic_knob": 3}, path=tune_cache)
    fused_layer_norm(x, (128,), interpret=True)
    assert not dispatch.dispatch_stats()["by_kernel"][
        "fused_layer_norm"]["tuned"]
    dispatch.reset_stats()
    # an explicit row_block never consults at all
    fused_layer_norm(x, (128,), row_block=32, interpret=True)
    assert "fused_layer_norm" not in dispatch.dispatch_stats()["by_kernel"]


def test_partial_config_entry_is_a_miss_not_a_crash(tune_cache):
    """A half-written entry (only block_q) must fall back to defaults —
    the kernels index the config unconditionally, so the params filter
    rejects MISSING keys too (review finding: KeyError at dispatch)."""
    from apex_tpu.ops.flash_attention import (TUNE_VERSION,
                                              flash_attention,
                                              tune_bucket)
    store.put("flash_attention", TUNE_VERSION,
              tune_bucket(256, 256, 64, True, False, False),
              {"block_q": 128}, path=tune_cache)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 256, 2, 64), jnp.float32)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    assert out.shape == (1, 256, 2, 64)
    assert not dispatch.dispatch_stats()["by_kernel"][
        "flash_attention"]["tuned"]


def test_hostile_row_block_is_rounded_legal(tune_cache):
    """An out-of-band cache value (hand-edited 100, hostile 3) must
    reach pallas_call as a legal sublane-multiple block (review
    finding: pick_rows only rounded the budget cap, not the knob)."""
    assert space.pick_rows(4096, 1024, 12, row_block=100) == 96
    assert space.pick_rows(4096, 1024, 12, row_block=3) == 8
    from apex_tpu.normalization.fused_layer_norm import (TUNE_VERSION,
                                                         fused_layer_norm,
                                                         tune_bucket)
    store.put("fused_layer_norm", TUNE_VERSION, tune_bucket(64, 128, 4),
              {"row_block": 100}, path=tune_cache)
    x = jnp.linspace(-2, 2, 64 * 128, dtype=jnp.float32).reshape(64, 128)
    tuned = fused_layer_norm(x, (128,), interpret=True)
    assert dispatch.dispatch_stats()["by_kernel"][
        "fused_layer_norm"]["tuned"]
    dispatch.reset_stats()
    base = fused_layer_norm(x, (128,), row_block=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tuned))


def test_bool_config_values_are_rejected(tune_cache):
    """JSON `true` is an int subclass — it must not pass the positive-
    int gate and reach _pick_block as 1 (review finding)."""
    from apex_tpu.quant.kernels import TUNE_VERSION, tune_bucket
    store.put("quantized_matmul", TUNE_VERSION, tune_bucket(64, 128, 128, 4),
              {"block_m": True, "block_n": 256}, path=tune_cache)
    assert dispatch.kernel_config(
        "quantized_matmul", TUNE_VERSION, tune_bucket(64, 128, 128, 4),
        params=("block_m", "block_n")) is None


def test_effective_dedupe_never_times_the_default_twice(tune_cache):
    """At n1=64 every row_block >= 64 clamps onto the same effective
    block as the default — only ONE of them may be measured (review
    finding: a clamped twin of the default could be persisted as a
    noise 'win')."""
    spec = registry.get_spec("fused_layer_norm")
    shape = {"n1": 64, "n2": 128, "dtype": "float32"}
    model = lambda cfg: 1e-3
    res = measure.tune_kernel(spec, shape, interpret=True,
                              measure=_fake_timer(model), path=tune_cache)
    keys = [repr(spec.effective(shape, c)) for c in res.order]
    assert len(keys) == len(set(keys))
    # the default's effective block appears exactly once (the default)
    assert keys.count(repr(spec.effective(shape,
                                          res.default_config))) == 1


def test_max_candidates_counts_as_truncated_not_constraint(tune_cache):
    model = lambda cfg: 1e-3 * cfg["row_block"]
    res = measure.tune_kernel("fused_layer_norm",
                              {"n1": 64, "n2": 128}, interpret=True,
                              max_candidates=2,
                              measure=_fake_timer(model), path=tune_cache)
    assert res.truncated > 0
    assert res.rejected_constraint == 0


def test_xentropy_tuned_rows_helper(tune_cache):
    from apex_tpu.contrib import xentropy as xe
    assert xe._tuned_rows(32, 128) is None
    store.put("xentropy", xe.TUNE_VERSION, xe.tune_bucket(32, 128),
              {"row_block": 64}, path=tune_cache)
    assert xe._tuned_rows(32, 128) == 64
    # the budget clamp still binds a hostile value
    assert xe._row_block(32, 128, 4096) <= 512


def test_xentropy_version_2_leaves_version_1_entries_unread(tune_cache):
    """ISSUE 36: the kernel under differentiation writes ``[R, H]`` blocks
    as well as reading them, so a ``row_block`` tuned for the old pair
    (version 1) is not consulted."""
    from apex_tpu.contrib import xentropy as xe
    assert xe.TUNE_VERSION == registry.get_spec("xentropy").version == 2
    store.put("xentropy", 1, xe.tune_bucket(32, 128), {"row_block": 64},
              path=tune_cache)
    assert xe._tuned_rows(32, 128) is None


# -- telemetry ----------------------------------------------------------------

def test_tune_events_and_tuned_kernel_pct_gauge(tune_cache, tmp_path):
    stream = tmp_path / "tune_stream.jsonl"
    rec = telemetry.start(str(stream))
    try:
        model = lambda cfg: 1e-3 * cfg["row_block"]
        measure.tune_kernel("fused_layer_norm", {"n1": 64, "n2": 128},
                            interpret=True, measure=_fake_timer(model),
                            path=tune_cache)
        from apex_tpu.normalization.fused_layer_norm import \
            fused_layer_norm
        fused_layer_norm(jnp.ones((64, 128), jnp.float32), (128,),
                         interpret=True)
        gauge = rec.metrics.gauge("tuned_kernel_pct").value
        assert gauge == 100.0
    finally:
        rec.close()
    kinds = {}
    with open(stream) as f:
        events = [json.loads(line) for line in f]
    tune_events = [e for e in events if e["kind"] == "tune"]
    phases = {e["phase"] for e in tune_events}
    assert {"result", "dispatch"} <= phases
    result = next(e for e in tune_events if e["phase"] == "result")
    assert result["kernel"] == "fused_layer_norm"
    assert result["best_ms"] <= result["default_ms"]
    assert result["stored"] is True
    hit = next(e for e in tune_events if e["phase"] == "dispatch")
    assert hit["hit"] is True and hit["config"]


# -- CLI ----------------------------------------------------------------------

def test_cli_tune_show_and_offline_refusal(tune_cache, capsys):
    rc = tune_main(["kernel", "fused_layer_norm", "--interpret",
                    "--cache", tune_cache, "--iters", "1", "--reps", "1",
                    "--shape", "n1=64,n2=128"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "persisted to" in out and "tuned" in out

    rc = tune_main(["show", "--cache", tune_cache])
    out = capsys.readouterr().out
    assert rc == 0 and "fused_layer_norm" in out and "r64_w128_i4" in out

    rc = tune_main(["show", "--cache", tune_cache, "--json"])
    rows = json.loads(capsys.readouterr().out)
    assert rows and rows[0]["kernel"] == "fused_layer_norm"

    if jax.default_backend() != "tpu":
        rc = tune_main(["kernel", "fused_layer_norm", "--cache",
                        tune_cache])
        assert rc == 2
        assert "only runs on TPU" in capsys.readouterr().err


def test_cli_ledger_rejects_shape(tune_cache, tmp_path, capsys):
    ledger = tmp_path / "ledger.json"
    ledger.write_text(json.dumps({"regions": []}))
    rc = tune_main(["ledger", str(ledger), "--interpret",
                    "--cache", tune_cache, "--shape", "rows=64"])
    assert rc == 2
    assert "--shape applies to `kernel NAME`" in capsys.readouterr().err


def test_cli_prune_drops_stale_versions(tune_cache, capsys):
    from apex_tpu.normalization.fused_layer_norm import TUNE_VERSION
    store.put("fused_layer_norm", TUNE_VERSION + 1, "b1",
              {"row_block": 16}, path=tune_cache)      # stale (future)
    store.put("fused_layer_norm", TUNE_VERSION, "b2",
              {"row_block": 16}, path=tune_cache)      # current
    rc = tune_main(["prune", "--cache", tune_cache])
    assert rc == 0
    assert "pruned 1" in capsys.readouterr().out
    _fresh_reload(tune_cache)
    assert [e["bucket"] for e in store.entries(tune_cache)] == ["b2"]


def test_cli_ledger_driven(tune_cache, tmp_path, capsys):
    ledger = tmp_path / "ledger.json"
    ledger.write_text(json.dumps({"regions": [
        {"region": "attention", "bound": "compute", "modeled_ms": 5.0},
        {"region": "layer_norm", "bound": "memory", "modeled_ms": 2.0}]}))
    # tune only the two cheapest kernels through the ledger path to keep
    # the CPU run fast: restrict via monkeypatched registry listing
    specs = [registry.get_spec("fused_layer_norm"),
             registry.get_spec("xentropy")]
    results = measure.tune_from_ledger(
        json.loads(ledger.read_text()), specs=specs, interpret=True,
        iters=1, reps=1, path=tune_cache)
    assert {r.kernel for r in results} == {"fused_layer_norm", "xentropy"}
    ln = next(r for r in results if r.kernel == "fused_layer_norm")
    assert ln.bound == "memory"          # the ledger verdict, not kind
    assert len(store.entries(tune_cache)) == 2
