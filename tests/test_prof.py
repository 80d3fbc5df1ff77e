"""prof package tests: analytic FLOP counts against hand-computed values,
scan multiplicity, capture markers, summary output."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import prof
from apex_tpu.prof import profile_function


def test_matmul_flops_exact():
    a = jnp.ones((64, 32))
    b = jnp.ones((32, 128))
    p = profile_function(lambda x, y: x @ y, a, b, xla_cost=False)
    dots = [r for r in p.records if r.op == "dot_general"]
    assert len(dots) == 1
    assert dots[0].flops == 2 * 64 * 32 * 128
    # bytes: read a + read b + write out, fp32
    assert dots[0].bytes == 4 * (64 * 32 + 32 * 128 + 64 * 128)
    assert dots[0].intensity > 1


def test_conv_flops():
    x = jnp.ones((2, 8, 8, 3))
    k = jnp.ones((3, 3, 3, 16))
    f = lambda a, b: jax.lax.conv_general_dilated(
        a, b, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    p = profile_function(f, x, k, xla_cost=False)
    convs = [r for r in p.records if r.op == "conv_general_dilated"]
    assert len(convs) == 1
    out_elems = 2 * 8 * 8 * 16
    assert convs[0].flops == 2 * out_elems * 3 * 3 * 3


def test_elementwise_and_reduction():
    x = jnp.ones((100,))
    p = profile_function(lambda a: jnp.sum(jnp.exp(a) + a), x,
                        xla_cost=False)
    ops = {r.op: r for r in p.records}
    assert ops["exp"].flops == 100
    assert ops["add"].flops == 100
    assert ops["reduce_sum"].flops == 100


def test_scan_multiplicity():
    x = jnp.ones((4, 8))

    def f(a):
        def body(c, _):
            return c @ jnp.ones((8, 8)), None
        out, _ = jax.lax.scan(body, a, None, length=10)
        return out

    p = profile_function(f, x, xla_cost=False)
    dots = [r for r in p.records if r.op == "dot_general"]
    assert dots and dots[0].count == 10
    assert p.total_flops >= 10 * 2 * 4 * 8 * 8


def test_profile_through_jit_and_grad():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    w = jnp.ones((16, 4))
    x = jnp.ones((8, 16))
    p = profile_function(jax.grad(loss), w, x, xla_cost=False)
    # forward + transpose matmuls present
    assert sum(1 for r in p.records if r.op == "dot_general") >= 2
    assert p.total_flops > 0


def test_summary_and_by_op():
    a = jnp.ones((32, 32))
    p = profile_function(lambda x: jnp.sum(x @ x), a, xla_cost=False)
    s = p.summary()
    assert "dot_general" in s and "TOTAL" in s and "MXU" in s
    assert p.by_op()["dot_general"] == 2 * 32 ** 3


def test_xla_cost_analysis_attached():
    a = jnp.ones((64, 64))
    p = profile_function(lambda x: x @ x, a, xla_cost=True)
    if p.xla_cost:  # backend-dependent; when present, sanity-check
        flops = p.xla_cost.get("flops")
        if flops:
            assert flops > 0


def test_capture_markers_and_scope():
    prof.MARKERS.clear()
    prof.init()

    @prof.annotate("my_matmul")
    def f(a):
        return a @ a

    out = jax.jit(f)(jnp.ones((8, 8)))
    assert out.shape == (8, 8)
    assert prof.MARKERS and prof.MARKERS[0]["op"] == "my_matmul"
    assert prof.MARKERS[0]["args"][0]["shape"] == (8, 8)

    with prof.scope("outer"):
        _ = jnp.ones((2,)) + 1


def test_dump_markers(tmp_path):
    prof.MARKERS.clear()
    prof.init()

    @prof.annotate()
    def g(a, flag=True):
        return a * 2

    g(jnp.ones((3,)), flag=False)
    path = tmp_path / "markers.jsonl"
    prof.dump_markers(str(path))
    import json
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines[0]["op"] == "g"
    assert lines[0]["kwargs"]["flag"]["value"] is False


def test_capture_scope_annotate_nesting():
    """ISSUE 5 satellite: nested annotate/scope/annotate must (a) nest
    the named scopes into HLO metadata (the NVTX-range analog the
    profiler trace shows) and (b) record one marker per annotated call
    in call order."""
    prof.MARKERS.clear()
    prof.init()
    try:
        @prof.annotate("inner_op")
        def inner(a):
            return a * 2

        @prof.annotate("outer_op")
        def outer(a):
            with prof.scope("mid"):
                return inner(a) + 1

        hlo = jax.jit(outer).lower(jnp.ones((4,))).compile().as_text()
        assert "outer_op/mid/inner_op" in hlo, \
            "named scopes must nest into HLO op metadata"
        assert [m["op"] for m in prof.MARKERS] == ["outer_op", "inner_op"]
        assert prof.MARKERS[0]["args"][0]["shape"] == (4,)
    finally:
        prof.init(enable_markers=False)


def test_dump_markers_roundtrip():
    """The dumped JSONL parses back into exactly the MARKERS content
    (tuples arrive as lists — the JSON-normalized forms must match)."""
    import json
    import tempfile

    prof.MARKERS.clear()
    prof.init()
    try:
        @prof.annotate("round")
        def f(a, mode="x"):
            return a

        f(jnp.ones((2, 3)), mode="y")
        f(7, mode=None)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "markers.jsonl")
            prof.dump_markers(path)
            with open(path) as fh:
                back = [json.loads(line) for line in fh]
        want = [json.loads(json.dumps(m)) for m in prof.MARKERS]
        assert back == want
        assert back[0]["op"] == "round"
        assert back[0]["kwargs"]["mode"]["value"] == "y"
        assert back[1]["args"][0]["value"] == 7
    finally:
        prof.init(enable_markers=False)


def test_annotate_emits_marker_into_telemetry_stream(tmp_path):
    """ISSUE 5: with a telemetry recorder active, each annotate call
    also lands a timestamped ``marker`` event in the run's stream (the
    traceMarker dicts become tail-able run events)."""
    import json

    from apex_tpu import telemetry

    prof.MARKERS.clear()
    prof.init()
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.start(path)
    try:
        @prof.annotate("tele_op")
        def f(a):
            return a + 1

        f(jnp.ones((2,)))
    finally:
        rec.close()
        prof.init(enable_markers=False)
    with open(path) as fh:
        events = [json.loads(line) for line in fh]
    markers = [e for e in events if e["kind"] == "marker"]
    assert len(markers) == 1
    assert markers[0]["op"] == "tele_op"
    assert markers[0]["args"][0]["shape"] == [2]
    assert markers[0]["t"] >= 0
    # and the in-memory MARKERS list still got its copy (dump_markers
    # and the stream describe the same call)
    assert prof.MARKERS[0]["op"] == "tele_op"


# -- measured-trace parse stage (VERDICT r2 #6) -------------------------------

@pytest.mark.slow
def test_parse_trace_roundtrip(tmp_path):
    """Capture a REAL device trace, parse it back, and join measured
    durations onto the static analysis (reference pyprof parse stage,
    ``parse/nvvp.py`` + ``prof/prof.py:39-56``)."""
    from apex_tpu import prof as P

    @jax.jit
    def f(x):
        return jnp.sum(jnp.tanh(x @ x))

    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()              # compile outside the trace window
    with P.trace(str(tmp_path)):
        for _ in range(3):
            r = f(x)
        r.block_until_ready()

    tp = P.parse_trace(str(tmp_path))
    assert tp.records, "no measured kernel records parsed"
    by_op = tp.by_op()
    assert any(k.startswith("dot") for k in by_op), by_op.keys()
    dot_key = next(k for k in by_op if k.startswith("dot"))
    assert by_op[dot_key]["count"] >= 3          # one per traced iteration
    assert by_op[dot_key]["total_us"] > 0
    # step segmentation: one run_id per executed iteration
    assert len(tp.steps()) >= 3
    assert tp.summary()

    static = P.profile_function(f, x, xla_cost=False)
    report = P.attach_measured(static, tp)
    # the joined report shows measured microseconds on the matmul row
    dot_line = next(l for l in report.splitlines()
                    if l.startswith("dot_general"))
    assert "-" not in dot_line.split()[3], report


def test_parse_trace_missing_dir_raises(tmp_path):
    from apex_tpu import prof as P
    with pytest.raises(FileNotFoundError):
        P.parse_trace(str(tmp_path / "nope"))


def test_parse_trace_tpu_device_event_format(tmp_path):
    """TPU traces carry hlo_category/model_flops device events (no hlo_op
    arg); the parse stage must ingest them (discovered on a live v5e
    trace — reference kernel-record parity for real chips)."""
    import json
    import gzip

    from apex_tpu import prof as P

    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    events = [
        {"ph": "X", "pid": 3, "tid": 3, "ts": 10.0, "dur": 100.0,
         "name": "convert_reduce_fusion.7",
         "args": {"hlo_category": "convolution fusion",
                  "model_flops": "2000000", "bytes_accessed": "4096"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 120.0, "dur": 50.0,
         "name": "multiply_subtract_fusion.2",
         "args": {"hlo_category": "loop fusion",
                  "model_flops": "1000", "bytes_accessed": "2048"}},
        {"ph": "M", "name": "process_name"},          # metadata: ignored
        {"ph": "X", "ts": 1.0, "dur": 1.0, "name": "no_args_event"},
    ]
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    tp = P.parse_trace(str(tmp_path))
    assert len(tp.records) == 2
    by_op = tp.by_op()
    assert by_op["convert_reduce_fusion"]["total_us"] == 100.0
    cats = tp.by_category()
    assert cats["convolution fusion"]["count"] == 1
    assert abs(cats["convolution fusion"]["tflops_per_sec"] - 0.02) < 1e-9
    assert "hlo_category" in tp.summary()


# -- CLI entry points (VERDICT r2 next #7) ------------------------------------

def _make_synthetic_trace(tmp_path):
    import gzip
    import json

    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    events = [
        {"ph": "X", "pid": 3, "tid": 3, "ts": 10.0, "dur": 100.0,
         "name": "fusion.7",
         "args": {"hlo_category": "convolution fusion",
                  "model_flops": "2000000", "bytes_accessed": "4096"}},
    ]
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)


@pytest.mark.slow
def test_parse_cli_subprocess(tmp_path):
    """``python -m apex_tpu.prof.parse <logdir>`` is a runnable tool
    (reference ``python -m apex.pyprof.parse net.sql``, parse/parse.py:25)."""
    import subprocess
    import sys

    _make_synthetic_trace(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.prof.parse", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "fusion" in out.stdout and "TOTAL measured" in out.stdout

    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.prof.parse", str(tmp_path),
         "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    import json
    rec = json.loads(out.stdout.splitlines()[0])
    assert rec["base_op"] == "fusion" and rec["duration_us"] == 100.0


@pytest.mark.slow
def test_analysis_cli_subprocess(tmp_path):
    """``python -m apex_tpu.prof.analysis --fn ... --shape ...`` emits the
    tabular flops/bytes report (reference ``python -m apex.pyprof.prof``,
    prof/prof.py:171), joined with a trace dir and a markers file."""
    import json
    import subprocess
    import sys

    _make_synthetic_trace(tmp_path)
    markers = tmp_path / "markers.jsonl"
    markers.write_text(json.dumps(
        {"op": "dense", "args": [{"shape": [8, 16], "dtype": "float32"}],
         "kwargs": {"causal": {"value": True}}}) + "\n")
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.prof.analysis",
         "--fn", "jax.numpy:tanh", "--shape", "8,128",
         "--no-xla-cost", "--trace", str(tmp_path),
         "--markers", str(markers)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert "tanh" in out.stdout          # static table has the op
    assert "TOTAL" in out.stdout
    assert "marker op" in out.stdout and "dense" in out.stdout
    assert "causal=True" in out.stdout


# -- trace-count assertions (runtime complement to jaxlint J004) --------------

def test_assert_trace_count_basic():
    f = jax.jit(lambda x: x * 2)
    with prof.assert_trace_count(f, 1):          # first call compiles
        for _ in range(3):
            f(jnp.ones(3))
    with prof.assert_trace_count(f, 0):          # steady state
        f(jnp.ones(3))
    assert prof.trace_count(f) == 1


def test_assert_trace_count_catches_retrace():
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(3))
    with pytest.raises(AssertionError, match="J004"):
        with prof.assert_trace_count(f, 0):
            f(jnp.ones(5))                       # new shape: retrace
    g = jax.jit(lambda x, n: x * n, static_argnums=(1,))
    with pytest.raises(AssertionError, match="J004"):
        with prof.assert_trace_count(g, 1):
            for i in range(3):
                g(jnp.ones(3), i)                # static arg varies: retrace


def test_assert_trace_count_exact_catches_missing_compile():
    f = jax.jit(lambda x: x - 1)
    with pytest.raises(AssertionError, match="not invoked"):
        with prof.assert_trace_count(f, 1):
            pass                                 # never called
    with prof.assert_trace_count(f, 1, exact=False):
        pass                                     # at-most mode: ok


def test_trace_count_rejects_plain_function():
    with pytest.raises(TypeError, match="tracing cache"):
        prof.trace_count(lambda x: x)


def test_amp_o2_step_compiles_once_never_retraces():
    """The headline contract: a representative amp O2 train step traces
    exactly once, then every same-shaped step reuses the trace.  This is
    the runtime ground truth behind jaxlint J004 — a Python scalar or a
    weak-type literal sneaking into the carried state would retrace
    every step and fail here before it shows up as a 10x dispatch-floor
    regression in bench.py."""
    from apex_tpu import training
    from apex_tpu.training import make_train_step

    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(6, 4) * 0.3, jnp.float32),
              "b": jnp.zeros((4,), jnp.float32)}
    x = jnp.asarray(rng.randn(16, 6), jnp.float32)
    y = jnp.asarray(rng.randn(16, 4) * 0.1, jnp.float32)

    def loss_fn(p, batch):
        xb, yb = batch
        out = xb @ p["w"].astype(xb.dtype) + p["b"].astype(xb.dtype)
        return jnp.mean((out.astype(jnp.float32) - yb) ** 2)

    init_fn, step_fn = make_train_step(loss_fn, training.adam(1e-2),
                                       opt_level="O2", loss_scale="dynamic")
    state = init_fn(params)
    step = jax.jit(step_fn)
    with prof.assert_trace_count(step, 1):       # one compile...
        for _ in range(5):
            state, metrics = step(state, (x, y))
    with prof.assert_trace_count(step, 0):       # ...zero retraces after
        state, metrics = step(state, (x, y))
    assert np.isfinite(float(metrics["loss"]))
