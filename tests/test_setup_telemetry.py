"""Set-up on the telemetry stream (ISSUE 35): the ``jax.monitoring`` listeners
live exactly as long as a started recorder, ``cache.warmup`` reports its three
stages, the ``run`` event says how old the process was, and with no recorder
the program compiled is the one-liner's."""

import io
import json

import jax
import jax.numpy as jnp
import pytest
from jax._src import compilation_cache, monitoring

from apex_tpu import cache, runtime, telemetry, training
from apex_tpu.tune import store as tune_store

_CACHE_CONFIG = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_entry_size_bytes",
                 "jax_persistent_cache_min_compile_time_secs")


def _listeners():
    return (len(monitoring.get_event_listeners()),
            len(monitoring.get_event_duration_listeners()))


def _events(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def _of(events, kind, **fields):
    return [e for e in events if e["kind"] == kind
            and all(e.get(k) == v for k, v in fields.items())]


@pytest.fixture
def compile_cache(tmp_path):
    """``cache.enable`` on a directory of the test's own; what it moved is put
    back afterwards."""
    config = {name: getattr(jax.config, name) for name in _CACHE_CONFIG}
    enabled, tune_dir = cache._STATE["dir"], tune_store._STATE["dir"]
    yield cache.enable(str(tmp_path / "xla_cache"))
    for name, value in config.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    cache._STATE["dir"] = enabled
    tune_store.set_default_dir(tune_dir)


@jax.jit
def _three_inner_jits(x):
    return jnp.sum(jnp.tanh(jnp.matmul(x, x)))


def test_listeners_live_from_start_to_close(tmp_path):
    before = _listeners()
    bare = telemetry.Recorder(io.StringIO())
    assert _listeners() == before, "a bare Recorder registers nothing"
    bare.close()
    rec = telemetry.start(str(tmp_path / "run.jsonl"))
    assert _listeners() == (before[0] + 1, before[1] + 1)
    rec.close()
    assert _listeners() == before
    rec.close()                                     # idempotent
    assert _listeners() == before


def test_close_survives_listeners_cleared_by_someone_else(tmp_path):
    kept = (monitoring.get_event_listeners(),
            monitoring.get_event_duration_listeners())
    rec = telemetry.start(str(tmp_path / "run.jsonl"))
    try:
        monitoring.clear_event_listeners()
        rec.close()
        assert _listeners() == (0, 0)
    finally:
        for listener in kept[0]:
            monitoring.register_event_listener(listener)
        for listener in kept[1]:
            monitoring.register_event_duration_secs_listener(listener)


def test_warmup_reports_three_stages_then_a_cache_hit(tmp_path, compile_cache):
    path = str(tmp_path / "run.jsonl")
    x = jnp.ones((8, 8))
    with telemetry.start(path) as rec:
        cache.warmup(_three_inner_jits, x)
        jax.clear_caches()
        cache.warmup(_three_inner_jits, x)
        counters = rec.metrics.snapshot()["counters"]
    events = _events(path)
    first, second = _of(events, "warmup")
    assert (first["program"], first["cache"]) == ("_three_inner_jits", "miss")
    assert second["cache"] == "hit"
    for w in (first, second):
        assert abs(w["trace_s"] + w["lower_s"] + w["compile_s"]
                   - w["dur"]) < 1e-3
        assert min(w["trace_s"], w["lower_s"], w["compile_s"]) > 0
    # the step program's own events lie inside its warm-up, one a stage
    name = "jit(_three_inner_jits)"
    for w, state in ((first, "miss"), (second, "hit")):
        inside = [e for e in _of(events, "compile", fun_name=name)
                  if w["t"] - w["dur"] <= e["t"] - e["dur"] and e["t"] <= w["t"]]
        assert [e["stage"] for e in inside] == ["lower", "backend"]
        assert inside[1]["cache"] == state
        assert ("read_s" in inside[1]) == (state == "hit")
        assert inside[1]["dur"] <= w["compile_s"] + 1e-3
        assert abs(inside[0]["dur"] - w["lower_s"]) < 1e-5
    assert _of(events, "compile", fun_name=name, cache="hit")[0]["read_s"] > 0
    backend = _of(events, "compile", stage="backend")
    assert counters["programs_compiled"] == len(backend)
    assert counters["compile_cache_hits"] == len(
        [e for e in backend if e["cache"] == "hit"]) >= 1
    assert counters["compile_cache_misses"] == len(
        [e for e in backend if e["cache"] == "miss"]) >= 1
    assert events[-1]["metrics"]["counters"] == counters   # in the summary


def test_inner_jits_are_one_program_not_four(tmp_path):
    path = str(tmp_path / "run.jsonl")
    x = jnp.ones((8, 8))                # made before the recorder listens
    jax.clear_caches()
    with telemetry.start(path):
        cache.warmup(_three_inner_jits, x)
    compiles = _of(_events(path), "compile")
    assert [(e["stage"], e["fun_name"]) for e in compiles] == [
        ("lower", "jit(_three_inner_jits)"),
        ("backend", "jit(_three_inner_jits)")]
    assert all(e["dur"] > 0 and e["t"] >= e["dur"] for e in compiles)


def test_backend_event_says_off_without_a_persistent_cache(tmp_path):
    path = str(tmp_path / "run.jsonl")
    x = jnp.ones((8, 8))
    jax.clear_caches()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # forgets that the cache was in use
    try:
        with telemetry.start(path):
            cache.warmup(_three_inner_jits, x)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    events = _events(path)
    assert [e["cache"] for e in _of(events, "compile", stage="backend")] == [
        "off"]
    assert _of(events, "warmup")[0]["cache"] == "off"


def test_no_recorder_same_program_and_no_listener(compile_cache):
    """The disabled path: ``cache.enable``, ``cache.warmup`` and
    ``pipe.warmup`` register nothing, and the executable is the one the
    parent's one-liner ``jitted.lower(...).compile()`` gives."""
    assert telemetry.get_recorder() is None
    before = _listeners()
    assert cache.enable(compile_cache) == compile_cache
    x = jnp.ones((8, 8))
    warmed = cache.warmup(_three_inner_jits, x)
    one_liner = _three_inner_jits.lower(*cache.abstractify((x,))).compile()
    assert warmed.as_text() == one_liner.as_text()
    assert float(warmed(x)) == float(one_liner(x))

    init_fn, step_fn = training.make_train_step(
        lambda p, b: jnp.mean((b @ p["w"]) ** 2), training.sgd(0.1),
        opt_level="O0")
    pipe = runtime.StepPipeline(step_fn, 2)
    pipe.warmup(init_fn({"w": jnp.ones((8, 4))}), jnp.ones((2, 16, 8)))
    assert pipe.compiled() is not None
    assert _listeners() == before


def test_warmup_keeps_the_one_liners_frame():
    """Lowering recurses deeply above this frame, and on the chip a few more
    words in it moved ResNet-50's lowering from 1.2 s to 17 to 36 s (PERF.md
    section 6, PR 35): the frame holds what the parent's one-liner held."""
    def one_liner(jitted, *args):
        return jitted.lower(*cache.abstractify(args)).compile()

    ours, theirs = cache.warmup.__code__, one_liner.__code__
    assert ours.co_varnames == ("jitted", "args")
    assert (ours.co_nlocals, ours.co_stacksize) == (
        theirs.co_nlocals, theirs.co_stacksize)


def test_a_bare_recorder_gets_the_length_alone():
    buf = io.StringIO()
    rec = telemetry.Recorder(buf)
    was = telemetry.set_recorder(rec)
    try:
        cache.warmup(_three_inner_jits, jnp.ones((4, 4)))
    finally:
        telemetry.set_recorder(was)
        rec.close()
    warm, = _of([json.loads(l) for l in buf.getvalue().splitlines()], "warmup")
    assert warm["dur"] > 0 and warm["cache"] == "off"
    assert "lower_s" not in warm and "trace_s" not in warm


def test_pipeline_warmup_names_its_programs(tmp_path):
    path = str(tmp_path / "run.jsonl")
    init_fn, step_fn = training.make_train_step(
        lambda p, b: jnp.mean((b @ p["w"]) ** 2), training.sgd(0.1),
        opt_level="O0")
    state, window = init_fn({"w": jnp.ones((8, 4))}), jnp.ones((2, 16, 8))
    with telemetry.start(path):
        runtime.StepPipeline(step_fn, 2).warmup(state, window, tail=True)
    assert [w["program"] for w in _of(_events(path), "warmup")] == [
        "hot", "tail"]


def test_run_event_carries_the_process_age(tmp_path):
    with telemetry.Recorder(io.StringIO()) as rec:
        age = rec.process_age_s
    assert age is not None and 0 < age < 24 * 3600
    path = str(tmp_path / "run.jsonl")
    telemetry.start(path).close()
    run = _events(path)[0]
    assert run["kind"] == "run" and abs(run["process_age_s"] - age) < 60
    assert run["anchor_unix"] > 0


def test_no_proc_no_field(tmp_path, monkeypatch):
    from apex_tpu.telemetry import events

    monkeypatch.setattr(events, "_process_age_s", lambda: None)
    buf = io.StringIO()
    telemetry.Recorder(buf).close()
    assert "process_age_s" not in json.loads(buf.getvalue().splitlines()[0])
