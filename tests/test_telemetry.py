"""Run-telemetry engine (ISSUE 5): event stream, metrics registry,
instrumentation wiring, and the offline timeline analyzer.

The contracts tier-1 pins here:

* **strict no-op when disabled** — with no recorder installed the
  instrumented loop produces BITWISE-identical parameters and traces
  exactly once (instrumentation causes zero retraces — the acceptance
  criterion's trace-count pin);
* **zero extra syncs** — device-side values enter the stream only
  through the one-dispatch-behind ``WindowMetrics.fetch`` the loop
  already pays;
* **single-snapshot loader attribution** — the ``loader`` event carries
  the same ``LoaderStats.as_dict()`` dict ``format_loader_line``
  prints, so the analyzer's stall number and the example's printed
  number cannot diverge (runs under the native/no-native tier matrix,
  like the bucket engine: the events are pure host Python, so tier-2
  must behave identically);
* the analyzer reconstructs step counts, loss-scale skip steps, retrace
  counts, and per-collective byte totals from the stream alone.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import data as apex_data
from apex_tpu import runtime, telemetry, training
from apex_tpu.prof import assert_trace_count, timeline
from apex_tpu.training import make_train_step

NDEV = 8


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Never leak an active recorder across tests."""
    telemetry.set_recorder(None)
    yield
    telemetry.set_recorder(None)


@pytest.fixture(params=["native-default", "no-native"])
def native_tier(request, monkeypatch):
    """The loader telemetry path is pure host Python; the tier-2
    (no-native) install must emit identical event shapes."""
    if request.param == "no-native":
        monkeypatch.setenv("APEX_TPU_DISABLE_NATIVE", "1")
    return request.param


def _loss_fn(p, batch):
    x, y = batch
    return jnp.mean((x @ p["w"] - y) ** 2)


def _params():
    return {"w": jnp.ones((4, 2), jnp.float32)}


def _batches(n, seed=0, bad_step=None):
    rng = np.random.RandomState(seed)
    out = [(rng.randn(8, 4).astype(np.float32),
            rng.randn(8, 2).astype(np.float32)) for _ in range(n)]
    if bad_step is not None:
        x, y = out[bad_step]
        out[bad_step] = (x, np.full_like(y, np.inf))
    return out


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _kinds(events):
    return [e["kind"] for e in events]


# -- metrics registry ---------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = telemetry.MetricsRegistry()
    reg.counter("n").inc()
    reg.counter("n").inc(3)
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    for v in range(100):
        h.observe(float(v))
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 4
    assert snap["gauges"]["g"] == 2.5
    hs = snap["histograms"]["h"]
    assert hs["count"] == 100 and hs["min"] == 0.0 and hs["max"] == 99.0
    assert 40.0 <= hs["p50"] <= 60.0
    assert hs["p99"] >= hs["p90"] >= hs["p50"]


def test_registry_reservoir_bounded_and_deterministic():
    a = telemetry.Histogram(reservoir=64, seed=7)
    b = telemetry.Histogram(reservoir=64, seed=7)
    for v in range(10_000):
        a.observe(v)
        b.observe(v)
    assert len(a._res) == 64
    assert a.percentiles() == b.percentiles()     # same seed, same answer
    p50 = a.percentiles((50.0,))[0]
    assert 2_000 <= p50 <= 8_000                  # uniform-ish sample


def test_registry_disabled_is_noop():
    reg = telemetry.MetricsRegistry(enabled=False)
    reg.counter("n").inc()
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(3.0)
    snap = reg.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


# -- recorder core ------------------------------------------------------------

def test_recorder_jsonl_stream_and_summary(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with telemetry.Recorder(path, meta={"example": "test"}) as rec:
        rec.event("window", step=0, dur=0.25)
        with rec.span("opt_step", step=0):
            pass
        rec.metrics.counter("steps_dispatched").inc(4)
    ev = _events(path)
    assert _kinds(ev) == ["run", "window", "opt_step", "summary"]
    assert ev[0]["meta"] == {"example": "test"}
    assert all(e["t"] >= 0 for e in ev)
    assert ev[2]["dur"] >= 0
    summary = ev[-1]
    assert summary["events"]["window"] == 1
    assert summary["metrics"]["counters"]["steps_dispatched"] == 4


def test_recorder_close_idempotent_and_drops_late_events(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.Recorder(path)
    rec.close()
    rec.close()
    rec.event("window", step=0)       # dropped, not an error
    assert _kinds(_events(path)) == ["run", "summary"]


def test_start_installs_and_close_clears_active(tmp_path):
    rec = telemetry.start(str(tmp_path / "r.jsonl"))
    assert telemetry.get_recorder() is rec
    rec.close()
    assert telemetry.get_recorder() is None


def test_recorder_tolerates_exotic_values(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with telemetry.Recorder(path) as rec:
        rec.event("marker", arr=np.arange(3), scalar=jnp.float32(1.5),
                  obj=object())
    ev = _events(path)          # every line parsed back as valid JSON
    assert ev[1]["arr"] == [0, 1, 2]
    assert ev[1]["scalar"] == 1.5


# -- StepPipeline / DeferredMetrics instrumentation ---------------------------

def _run_pipeline(k, batches, rec=None, fetch=True):
    init_fn, step_fn = make_train_step(
        _loss_fn, training.sgd(lr=0.1), opt_level="O2",
        loss_scale="dynamic", scale_window=4)
    pipe = runtime.StepPipeline(step_fn, k=k, telemetry=rec)
    state = init_fn(_params())
    with assert_trace_count(pipe.loop, 1):
        state, reader = pipe.run(
            state, runtime.window_batches(iter(batches), k),
            on_metrics=(lambda wm: wm.fetch()) if fetch else None)
    if not fetch:
        reader.last()
    return state


def test_pipeline_emits_window_and_metrics_events(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.Recorder(path)
    _run_pipeline(4, _batches(8), rec=rec)
    rec.close()
    ev = _events(path)
    windows = [e for e in ev if e["kind"] == "window"]
    metrics = [e for e in ev if e["kind"] == "metrics"]
    assert [w["step"] for w in windows] == [0, 4]
    assert all(w["k"] == 4 and w["n_valid"] == 4 and w["dur"] >= 0
               and w["gap"] >= 0 for w in windows)
    assert windows[0]["program"] == "hot"
    assert {m["step"] for m in metrics} == {0, 4}
    m0 = metrics[0]
    assert len(m0["loss"]) == 4 and len(m0["loss_scale"]) == 4
    # the hot program compiled exactly once, recorded as first=True
    retraces = [e for e in ev if e["kind"] == "retrace"]
    assert len(retraces) == 1 and retraces[0]["first"] is True
    assert "float32" in retraces[0]["sig"]


def test_instrumentation_zero_retraces_and_bitwise_identical(tmp_path):
    """The acceptance pin: enabling telemetry changes neither the trace
    count (asserted inside _run_pipeline) nor a single parameter bit."""
    batches = _batches(12, bad_step=5)       # include an overflow skip
    off = _run_pipeline(4, batches, rec=None)
    rec = telemetry.Recorder(str(tmp_path / "run.jsonl"))
    on = _run_pipeline(4, batches, rec=rec)
    rec.close()
    for a, b in zip(jax.tree_util.tree_leaves(off.params),
                    jax.tree_util.tree_leaves(on.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(off.scaler.loss_scale) == float(on.scaler.loss_scale)


def test_scale_skip_and_growth_events(tmp_path):
    """An overflow mid-run lands a ``scale skip`` event at the global
    step index; a small scale_window lands ``grow`` events after clean
    windows — both derived from the one-dispatch-behind fetches."""
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.Recorder(path)
    _run_pipeline(4, _batches(12, bad_step=5), rec=rec)
    rec.close()
    ev = _events(path)
    skips = [e for e in ev if e["kind"] == "scale"
             and e["event"] == "skip"]
    assert [e["step"] for e in skips] == [5]
    grows = [e for e in ev if e["kind"] == "scale"
             and e["event"] == "grow"]
    assert grows, "scale_window=4 over 12 steps must grow at least once"
    summary = ev[-1]
    assert summary["metrics"]["counters"]["loss_scale_skips"] == 1


def test_double_fetch_does_not_double_scale_events(tmp_path):
    """The warmup pattern fetches the same window twice (drain + print);
    the recorder's high-water guard must not re-derive its events."""
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.Recorder(path)
    init_fn, step_fn = make_train_step(
        _loss_fn, training.sgd(lr=0.1), opt_level="O2",
        loss_scale="dynamic")
    pipe = runtime.StepPipeline(step_fn, k=4, telemetry=rec)
    reader = runtime.DeferredMetrics(telemetry=rec)
    state = init_fn(_params())
    for window, n in runtime.window_batches(
            iter(_batches(8, bad_step=2)), 4):
        state, metrics = pipe.step_window(state, window, n)
        prev = reader.push(metrics, n)
        if prev is not None:
            prev.fetch()
            prev.fetch()                       # the double-fetch
    reader.last()
    rec.close()
    skips = [e for e in _events(path) if e["kind"] == "scale"
             and e["event"] == "skip"]
    assert [e["step"] for e in skips] == [2]


def test_deferred_metrics_flush_returns_each_window_once():
    reader = runtime.DeferredMetrics()
    seen = []
    for i in range(3):
        prev = reader.push({"loss": jnp.float32(i)}, 4)
        if prev is not None:
            seen.append(prev.step)
    seen += [wm.step for wm in reader.flush()]
    assert seen == [0, 4, 8]
    assert reader.flush() == []               # idempotent until next push
    prev = reader.push({"loss": jnp.float32(3)}, 4)
    assert prev.step == 8
    assert [wm.step for wm in reader.flush()] == [12]


# -- loader instrumentation ---------------------------------------------------

def test_loader_events_and_single_snapshot(tmp_path, native_tier):
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.start(path)
    batches = [(np.full((2, 3), i, np.float32),) for i in range(6)]
    loader = apex_data.PrefetchLoader(iter(batches), depth=2, workers=2)
    n = sum(1 for _ in loader)
    assert n == 6
    rec.close()
    ev = _events(path)
    waits = [e for e in ev if e["kind"] == "loader_wait"]
    stages = [e for e in ev if e["kind"] == "stage"]
    loaders = [e for e in ev if e["kind"] == "loader"]
    assert len(waits) == 6 and all(w["dur"] >= 0 for w in waits)
    assert sorted(s["seq"] for s in stages) == list(range(6))
    assert len(loaders) == 1 and loaders[0]["phase"] == "exhausted"
    # the event's snapshot IS as_dict(): same keys, including the
    # derived stall pct the examples print via format_loader_line
    snap = loaders[0]["stats"]
    assert set(snap) == set(loader.stats.as_dict())
    line = apex_data.format_loader_line(snap)
    assert line.startswith(f"loader: stall {snap['loader_stall_pct']:.2f}%")


def test_loader_close_emits_final_snapshot(tmp_path, native_tier):
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.start(path)
    batches = [(np.zeros((2,), np.float32),) for _ in range(16)]
    loader = apex_data.PrefetchLoader(iter(batches), depth=1)
    it = iter(loader)
    next(it)
    loader.close()                  # abandoned mid-stream
    rec.close()
    loaders = [e for e in _events(path) if e["kind"] == "loader"]
    assert [e["phase"] for e in loaders] == ["close"]
    assert loaders[0]["stats"]["batches"] >= 1


def test_as_dict_snapshot_consistent_fields():
    stats = apex_data.LoaderStats()
    stats._start()
    stats._add("consumer_wait_s", 0.5)
    stats._delivered(2)
    d = stats.as_dict()
    s = stats.snapshot()                 # the alias: same read, same keys
    assert set(d) == set(s)
    for k in ("batches", "staged", "produce_s", "consumer_wait_s",
              "mean_queue_depth"):
        assert d[k] == s[k]
    assert d["batches"] == 1 and d["consumer_wait_s"] == 0.5


# -- collective byte events ---------------------------------------------------

def test_reduce_gradients_records_psum_bytes(tmp_path):
    from jax import shard_map
    from apex_tpu.parallel.distributed import reduce_gradients

    mesh = Mesh(np.array(jax.devices("cpu")[:NDEV]), ("data",))
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.start(path)
    grads = jnp.arange(NDEV * 4, dtype=jnp.float32).reshape(NDEV, 4)
    f = shard_map(lambda g: reduce_gradients({"w": g}, "data")["w"],
                  mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    jax.block_until_ready(f(grads))
    rec.close()
    colls = [e for e in _events(path) if e["kind"] == "collective"]
    assert colls, "trace-time psum bytes must be recorded"
    c = colls[0]
    assert c["op"] == "psum" and c["axis"] == ["data"]
    assert c["bytes"] == 4 * 4 and c["n"] == 1   # [4] f32 per-shard leaf
    assert c["dtype"] == "float32"


def test_zero1_records_collective_pair(tmp_path):
    from jax import shard_map
    from apex_tpu.parallel.zero import zero1, zero1_partition_spec

    mesh = Mesh(np.array(jax.devices("cpu")[:NDEV]), ("data",))
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.start(path)
    tx = zero1(training.adam(1e-2), "data", num_shards=NDEV)
    params = {"w": jnp.ones((NDEV * 2,), jnp.float32)}
    state = tx.init(params)
    sspec = zero1_partition_spec(state, "data")

    def step(params, state, grads):
        return tx.update(grads, state, params)

    f = shard_map(step, mesh=mesh, in_specs=(P(), sspec, P()),
                  out_specs=(P(), sspec))
    grads = {"w": jnp.ones((NDEV * 2,), jnp.float32)}
    jax.block_until_ready(f(params, state, grads)[0]["w"])
    rec.close()
    ops = {e["op"] for e in _events(path) if e["kind"] == "collective"}
    assert {"psum_scatter", "all_gather"} <= ops


# -- chrome export + timeline analyzer ----------------------------------------

def test_chrome_trace_export(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.Recorder(path)
    _run_pipeline(4, _batches(8), rec=rec)
    rec.close()
    out = str(tmp_path / "trace.json")
    n = telemetry.to_chrome_trace(path, out)
    assert n > 0
    with open(out) as f:
        trace = json.load(f)
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert "M" in phases and "X" in phases
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] > 0 for e in xs)


def test_timeline_analyze_end_to_end(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.start(path)
    init_fn, step_fn = make_train_step(
        _loss_fn, training.sgd(lr=0.1), opt_level="O2",
        loss_scale="dynamic")
    pipe = runtime.StepPipeline(step_fn, k=4)
    state = init_fn(_params())
    state, reader = pipe.run(
        state, runtime.stage_windows(iter(_batches(12, bad_step=6)), 4),
        on_metrics=lambda wm: wm.fetch())
    rec.close()
    a = timeline.analyze(timeline.load_events(path))
    assert a["steps"] == 12 and a["windows"] == 3
    assert a["retraces"]["retraces"] == 0
    assert a["loss_scale"]["skip_steps"] == [6]
    att = a["attribution"]
    assert 0.0 <= att["dispatch_gap_pct"] <= 100.0
    assert att["loader_stall_pct"] == a["loader"]["loader_stall_pct"]
    st = a["step_time"]
    assert st["samples"] == 8 and st["p50_ms"] is not None
    assert st["p99_ms"] >= st["p50_ms"]
    report = timeline.format_report(a)
    assert "skips at steps [6]" in report
    assert "loader stall" in report


def test_timeline_cli_main(tmp_path, capsys):
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.Recorder(path)
    _run_pipeline(2, _batches(4), rec=rec)
    rec.close()
    chrome = str(tmp_path / "trace.json")
    assert timeline.main([path, "--chrome", chrome]) == 0
    out = capsys.readouterr().out
    assert "telemetry timeline" in out and "steps: 4" in out
    assert os.path.exists(chrome)
    assert timeline.main([path, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["steps"] == 4


def test_timeline_collective_totals():
    """Analyzer collective math from a synthetic stream: the hot and
    tail compiles each re-record the same per-step collectives (divide
    by observed compiles), but two genuinely distinct same-signature
    reduce calls inside one step must SURVIVE the division."""
    base = [
        {"t": 0.0, "kind": "run", "meta": {}},
        {"t": 0.04, "kind": "retrace", "program": "hot", "step": 0,
         "n_traces": 1, "first": True, "new_sig": True, "sig": "s"},
        {"t": 0.1, "kind": "window", "step": 0, "k": 4, "n_valid": 4,
         "dur": 0.05, "gap": 0.0, "program": "hot"},
        {"t": 0.14, "kind": "retrace", "program": "tail", "step": 4,
         "n_traces": 1, "first": True, "new_sig": True, "sig": "s"},
        {"t": 0.2, "kind": "window", "step": 4, "k": 4, "n_valid": 2,
         "dur": 0.05, "gap": 0.01, "program": "tail"},
    ]
    coll = {"kind": "collective", "op": "psum", "axis": ["data"],
            "bytes": 1000, "n": 2, "dtype": "float32"}
    # one reduce per step, recorded by both compiles -> divides to 1
    a = timeline.analyze(base + [dict(coll, t=0.05), dict(coll, t=0.15)])
    assert a["steps"] == 6
    assert a["collectives"]["per_step_bytes"] == 1000
    assert a["collectives"]["total_gb"] == round(1000 * 6 / 1e9, 4)
    assert a["retraces"] == {"compiles": 2, "respecializations": 0,
                             "retraces": 0, "by_signature": [],
                             "compile_s": 0.0}
    # TWO identical reduces per step (e.g. twin G/D trees), two compiles
    # -> four events divide to multiplicity 2, not 1
    a2 = timeline.analyze(base + [dict(coll, t=t)
                                  for t in (0.05, 0.06, 0.15, 0.16)])
    assert a2["collectives"]["per_step_bytes"] == 2000


def test_timeline_respecialization_not_a_retrace():
    """The known-benign call-1 re-specialization (same signature, cache
    grew) is reported separately from true retraces (new signature)."""
    events = [
        {"t": 0.0, "kind": "run", "meta": {}},
        {"t": 0.1, "kind": "window", "step": 0, "k": 1, "n_valid": 1,
         "dur": 0.05, "gap": 0.0, "program": "hot"},
        {"t": 0.05, "kind": "retrace", "program": "hot", "step": 0,
         "n_traces": 1, "first": True, "new_sig": True, "sig": "a"},
        {"t": 0.15, "kind": "retrace", "program": "hot", "step": 1,
         "n_traces": 2, "first": False, "new_sig": False, "sig": "a"},
        {"t": 0.25, "kind": "retrace", "program": "hot", "step": 2,
         "n_traces": 3, "first": False, "new_sig": True, "sig": "b"},
    ]
    rt = timeline.analyze(events)["retraces"]
    assert rt["compiles"] == 1
    assert rt["respecializations"] == 1
    assert rt["retraces"] == 1 and rt["by_signature"] == ["b"]


def test_timeline_tolerates_torn_tail_line(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        f.write('{"t": 0.0, "kind": "run", "meta": {}}\n')
        f.write('{"t": 0.1, "kind": "window", "step": 0, "k": 1, '
                '"n_valid": 1, "dur": 0.01, "gap": 0.0}\n')
        f.write('{"t": 0.2, "kind": "wind')      # killed mid-write
    a = timeline.analyze(timeline.load_events(path))
    assert a["steps"] == 1


def test_timeline_collectives_attribute_per_axis():
    """ISSUE 12 satellite: per-collective byte totals split per mesh
    axis (dp vs fsdp vs joint) instead of one undifferentiated pool."""
    events = [
        {"t": 0.0, "kind": "run", "meta": {}},
        {"t": 0.04, "kind": "retrace", "program": "hot", "step": 0,
         "n_traces": 1, "first": True, "new_sig": True, "sig": "s"},
        {"t": 0.1, "kind": "window", "step": 0, "k": 4, "n_valid": 4,
         "dur": 0.05, "gap": 0.0, "program": "hot"},
        {"t": 0.05, "kind": "collective", "op": "all_gather",
         "axis": "fsdp", "bytes": 4000, "n": 1, "dtype": "float32"},
        {"t": 0.06, "kind": "collective", "op": "reduce_scatter",
         "axis": "fsdp", "bytes": 4000, "n": 1, "dtype": "float32"},
        {"t": 0.07, "kind": "collective", "op": "psum",
         "axis": "dp", "bytes": 500, "n": 1, "dtype": "float32"},
        {"t": 0.08, "kind": "collective", "op": "psum",
         "axis": ["dp", "fsdp"], "bytes": 64, "n": 1,
         "dtype": "float32"},
    ]
    a = timeline.analyze(events)
    by_axis = a["collectives"]["by_axis"]
    assert set(by_axis) == {"fsdp", "dp", "dp+fsdp"}
    assert by_axis["fsdp"]["bytes_per_step"] == 8000
    assert by_axis["fsdp"]["ops"] == ["all_gather", "reduce_scatter"]
    assert by_axis["dp"]["bytes_per_step"] == 500
    assert by_axis["dp+fsdp"]["bytes_per_step"] == 64
    assert (a["collectives"]["per_step_bytes"]
            == sum(v["bytes_per_step"] for v in by_axis.values()))
