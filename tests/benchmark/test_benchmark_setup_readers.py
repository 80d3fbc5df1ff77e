"""The eight readers that split ``setup_s`` by the program's own telemetry
events (``run.process_age_s``, ``compile``, ``warmup``), on a stream written
by hand; a stream without those events reads nothing and raises nothing."""

import json
import os
import types

import pytest

from benchmark import run

MANIFEST = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}


def _compile(t, stage, dur, name, cache=None, read_s=None):
    event = {"t": t, "kind": "compile", "stage": stage, "dur": dur,
             "fun_name": name}
    if cache is not None:
        event["cache"] = cache
    if read_s is not None:
        event["read_s"] = read_s
    return event


#: a launch on a half-warm cache: three programs in ``build`` (one read back),
#: the step program a miss inside its warm-up (which begins at t = 2.5), and
#: one late program in the first dispatches, read back
EVENTS = [
    {"t": 0.0, "kind": "run", "run_id": "r", "meta": {}, "process_index": 0,
     "process_count": 1, "anchor_unix": 1.7e9, "segment": 0,
     "process_age_s": 14.25},
    _compile(0.5, "lower", 0.25, "jit(init)"),
    _compile(1.0, "backend", 0.5, "jit(init)", "miss"),
    _compile(1.25, "lower", 0.125, "jit(_normal)"),
    _compile(1.5, "backend", 0.25, "jit(_normal)", "hit", 0.125),
    _compile(2.0, "lower", 0.125, "jit(window)"),
    _compile(2.25, "backend", 0.125, "jit(window)", "miss"),
    _compile(5.5, "lower", 1.0, "jit(loop)"),
    _compile(8.5, "backend", 2.75, "jit(loop)", "miss"),
    {"t": 8.5, "kind": "warmup", "program": "hot", "trace_s": 2.0,
     "lower_s": 1.0, "compile_s": 3.0, "dur": 6.0, "cache": "miss"},
    _compile(9.0, "backend", 0.25, "jit(late)", "hit", 0.0625),
    {"t": 9.5, "kind": "window", "step": 0, "k": 1, "n_valid": 1,
     "dur": 0.004, "gap": 0.0, "program": "hot"},
    {"t": 9.6, "kind": "window", "step": 1, "k": 1, "n_valid": 1,
     "dur": 0.002, "gap": 0.09, "program": "hot"},
]
#: what the parent's program writes: no age, no ``compile``, no ``warmup``
OLD_EVENTS = [
    {"t": 0.0, "kind": "run", "run_id": "r", "meta": {}, "process_index": 0,
     "process_count": 1, "anchor_unix": 1.7e9, "segment": 0},
    EVENTS[-2], EVENTS[-1],
    {"t": 9.7, "kind": "summary", "events": {"run": 1, "window": 2},
     "metrics": {"counters": {}, "gauges": {}, "histograms": {}}},
]
EXPECTED = {
    "start_s": ("s", "program_span", 14.25),
    "warmup_trace_s": ("s", "program_span", 2.0),
    "warmup_lower_s": ("s", "program_span", 1.0),
    "warmup_compile_s": ("s", "program_span", 3.0),
    "build_compile_s": ("s", "program_span", 1.375),
    "build_programs": ("count", "program_counter", 3),
    "cache_misses": ("count", "program_counter", 3),
    "cache_read_s": ("s", "program_span", 0.1875),
}


def _read(name, events):
    reader = run._load(run.ROOT, "layer_metrics", name)
    return reader.compute(types.SimpleNamespace(events=events))


def test_the_stream_is_the_one_described():
    compiles = [e for e in EVENTS if e["kind"] == "compile"]
    warmup, = [e for e in EVENTS if e["kind"] == "warmup"]
    assert len(compiles) == 9
    assert len([e for e in compiles
                if e["t"] > warmup["t"] - warmup["dur"]]) == 3
    assert len([e for e in compiles if e["t"] > warmup["t"]]) == 1
    assert len([e for e in EVENTS if e["kind"] == "window"]) == 2


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_setup_reader(name):
    unit, source, value = EXPECTED[name]
    entry = PER_LAYER[name]                 # by name, wherever it stands
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "build", "moves": "setup_s"}
    assert _read(name, EVENTS) == value
    assert _read(name, OLD_EVENTS) is None


def test_every_cell_reports_the_eight():
    for cell in MANIFEST["workloads"]:
        reported = {m["name"] for m in run.resolve(cell["name"]).per_layer}
        assert set(EXPECTED) <= reported, cell["name"]


def test_a_warm_and_a_cold_stream_read_as_the_issue_says():
    """Warm: no miss and some read; an emptied cache: misses and no read."""
    warm = [dict(e, cache="hit", read_s=0.5) if e.get("stage") == "backend"
            else e for e in EVENTS]
    cold = [{k: v for k, v in dict(e, cache="miss").items() if k != "read_s"}
            if e.get("stage") == "backend" else e for e in EVENTS]
    assert (_read("cache_misses", warm), _read("cache_read_s", warm)) == (0, 2.5)
    assert (_read("cache_misses", cold), _read("cache_read_s", cold)) == (5, 0)
    # a bare recorder's stream: a warm-up and no ``compile`` event
    bare = [e for e in EVENTS if e["kind"] != "compile"]
    assert _read("build_compile_s", bare) is None
    assert _read("build_programs", bare) is None
    assert _read("warmup_lower_s", bare) == 1.0
