"""One process, one cell, once: ``--workload <name> --seed <n> --seconds <s> --trace <0|1>``.

Refuses to run off the TPU (no CPU fallback), builds the cell through its
family adapter, warms up, measures the examples' steady loop for
``--seconds``, checks the outputs against the plain reference after the
window, and prints one JSON object as the last line of its output.

This file holds no cell, configuration, family or metric name.  It finds the
cell in ``BENCHMARK.json``, the configuration by the ``file`` given there,
the traffic mix in ``traffic/<name>.json``, the family in
``families/<family>.py`` and every metric in ``end_to_end/<name>.py`` or
``layer_metrics/<name>.py``.
"""

import time

_T0 = time.perf_counter()           # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __package__ in (None, ""):       # started as a file: import from the checkout
    sys.path[0] = ROOT

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: key of BENCHMARK.json -> directory of that kind's readers
_READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
#: the traced stretch: it starts after this many dispatches of the window
#: and lasts until it holds this many steps and this many seconds
_TRACE_AFTER_DISPATCHES = 3
_TRACE_MIN_STEPS = 12
_TRACE_MIN_SECONDS = 2.0


def _read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _load(root, kind, name):
    """The module ``<root>/benchmark/<kind>/<name>.py``, loaded by its path so
    that a file dropped in is found with no registry to edit."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"BENCHMARK.json names {kind}/{name}, but there is "
                         f"no {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(workload, root=ROOT):
    """Everything ``BENCHMARK.json`` says about one cell, with its files read
    and its modules loaded."""
    manifest = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = _read_json(os.path.join(root, entry["file"]))
    applies = lambda m: workload in m.get("workloads", [workload])
    metrics = {kind: [dict(m, reader=_load(root, where, m["name"]))
                      for m in manifest[kind] if applies(m)]
               for kind, where in _READERS.items()}
    return types.SimpleNamespace(
        name=workload, chips=cell["chips"], config=config,
        traffic=_read_json(os.path.join(
            root, "benchmark", "traffic", cell["traffic"] + ".json")),
        family=_load(root, "families", config["family"]),
        end_to_end=metrics["end_to_end"], per_layer=metrics["per_layer"],
        peaks=_read_json(os.path.join(root, "benchmark", "peaks.json")))


def _devices(plan, allow_cpu):
    """The cell's chips, or exit: never a CPU fallback on a measurement."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if allow_cpu:
        if len(devices) < plan.chips:
            raise SystemExit(f"{plan.name} needs {plan.chips} devices, JAX "
                             f"found {len(devices)}")
        return devices[:plan.chips], None
    found = (f"platform={dev.platform!r} device_kind={dev.device_kind!r} "
             f"count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found {found}; "
                         f"nothing was run")
    if dev.device_kind not in plan.peaks["by_device_kind"]:
        raise SystemExit(f"benchmark: no published peaks for {found} in "
                         f"benchmark/peaks.json; nothing was run")
    if len(devices) != plan.chips:
        raise SystemExit(f"benchmark: {plan.name} needs {plan.chips} chip(s), "
                         f"JAX found {found}; nothing was run")
    return devices, plan.peaks["by_device_kind"][dev.device_kind]


def _peak_bytes(devices):
    """Peak memory of the fullest chip.  The v5e's allocator counts live
    arrays (``peak_bytes_in_use``) and what running programs reserve for
    their temporaries (``peak_bytes_reserved``) apart; the arguments of a
    step are live while it runs, so the peak is their sum."""
    stats = [d.memory_stats() or {} for d in devices]
    print(f"memory_stats: {json.dumps(stats[0])}", flush=True)
    return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)


class _Tracer:
    """Profiles a short steady stretch of the window with ``jax.profiler``."""

    def __init__(self, out_dir):
        self.dir = os.path.join(out_dir, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        self.started = self.stopped = None
        self.steps_at_start = 0

    def tick(self, dispatches, steps, now):
        """Starts or stops the trace when it is time to; true if it did,
        which holds the loop up."""
        import jax

        if self.started is None:
            if dispatches >= _TRACE_AFTER_DISPATCHES:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0     # host spans are enough
                jax.profiler.start_trace(self.dir, profiler_options=options)
                self.started, self.steps_at_start = time.perf_counter(), steps
                return True
        elif (steps - self.steps_at_start >= _TRACE_MIN_STEPS
              and now - self.started >= _TRACE_MIN_SECONDS):
            return self.stop()
        return False

    def stop(self):
        import jax

        if self.started is None or self.stopped is not None:
            return False
        jax.profiler.stop_trace()
        self.stopped = time.perf_counter()
        return True

    def xplane(self):
        for base, _, files in os.walk(self.dir):
            for name in files:
                if name.endswith(".xplane.pb"):
                    return os.path.join(base, name)
        return None


def _measure(cell, seconds, tracer):
    """The examples' steady loop without the prints: dispatch, push, fetch
    one dispatch behind.  Returns the fetched metrics, the time from the
    start of the window to the completion of each dispatch as the host saw
    it, the dispatches the tracer held up, and the last metrics on the
    device."""
    import jax
    from apex_tpu import runtime

    span = (jax.profiler.TraceAnnotation if tracer is not None
            else lambda name: contextlib.nullcontext())
    pipe, window, k = cell.pipe, cell.window, cell.k
    state, reader = cell.state, runtime.DeferredMetrics()
    fetched, done_at, held_up, dispatches = [], [], set(), 0
    t_begin = time.perf_counter()
    while True:
        with span("bench.dispatch"):
            state, metrics = pipe.step_window(state, window, k)
        dispatches += 1
        prev = reader.push(metrics, k)
        if prev is not None:
            with span("bench.fetch"):
                fetched.append(prev.fetch())
            done_at.append(time.perf_counter() - t_begin)
        now = time.perf_counter()
        if tracer is not None and tracer.tick(dispatches, dispatches * k, now):
            held_up.add(len(done_at))
        if now - t_begin >= seconds:
            break
    if tracer is not None and tracer.stop():
        held_up.add(len(done_at))
    with span("bench.fetch"):
        fetched.append(reader.last())
    done_at.append(time.perf_counter() - t_begin)
    cell.state = state
    return fetched, done_at, held_up, reader.newest().metrics


def _replicas_agree(arrays):
    """Replicated outputs must really be equal on every device."""
    import numpy as np

    for arr in arrays:
        shards = [np.asarray(s.data) for s in arr.addressable_shards]
        if any(not np.array_equal(s, shards[0]) for s in shards[1:]):
            return False
    return True


def run_cell(workload, seed, seconds, trace, *, allow_cpu=False, root=ROOT,
             t0=None):
    """Run one cell and return the result line as a dict.  ``allow_cpu`` is
    for the benchmark's own tests and is not reachable from the command."""
    t0 = time.perf_counter() if t0 is None else t0
    plan = resolve(workload, root)

    import jax
    import numpy as np

    devices, peaks = _devices(plan, allow_cpu)
    from apex_tpu import cache, telemetry

    print(f"compile cache: {cache.enable()}", flush=True)
    out_dir = os.path.join(root, "benchmark", "out", workload)
    os.makedirs(out_dir, exist_ok=True)

    compiles = []
    listener = lambda name, *a, **kw: (
        compiles.append(time.perf_counter()) if name == _COMPILE_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    recorder = None
    tracer = _Tracer(out_dir) if trace else None
    spans = {}

    @contextlib.contextmanager
    def timed(name):
        t = time.perf_counter()
        yield
        spans[name] = time.perf_counter() - t

    try:
        if trace:
            recorder = telemetry.start(
                os.path.join(out_dir, "telemetry.jsonl"), watchdog=False)
        with timed("build"):
            cell = plan.family.build(plan.config, plan.traffic, devices, seed)
        with timed("warmup"):
            cell.pipe.warmup(cell.state, cell.window)
        with timed("first_dispatch"):
            cell.first_dispatch()
        setup_s = time.perf_counter() - t0
        compiled_in_setup = len(compiles)
        print(f"set-up: {setup_s:.1f} s = " + " + ".join(
            f"{name} {s:.1f}" for name, s in spans.items())
            + f" + start and imports; {compiled_in_setup} backend compile(s) "
            f"or cache hits", flush=True)

        fetched, done_at, held_up, last_metrics = _measure(cell, seconds, tracer)
        compiles_in_window = len(compiles) - compiled_in_setup
        peak_bytes = _peak_bytes(devices)       # before the check can set it
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        if tracer is not None:
            tracer.stop()
        if recorder is not None:
            recorder.close()

    step_metrics = {name: np.concatenate([np.ravel(f[name]) for f in fetched])
                    for name in fetched[0]}
    steps = len(step_metrics["loss"])
    bad = ~np.isfinite(step_metrics["loss"]) | step_metrics["overflow"].astype(bool)
    # seconds from one dispatch's completion to the next (the first from the
    # start of the window), without those the tracer held up
    intervals = np.delete(np.diff([0.0] + done_at), sorted(held_up))
    window_s = done_at[-1]
    print(f"window: {steps} steps in {window_s:.3f} s, median "
          f"{1e3 * np.median(intervals) / cell.k:.3f} ms/step; loss "
          f"{step_metrics['loss'][0]:.4f} -> {step_metrics['loss'][-1]:.4f}; "
          f"loss scale {step_metrics['loss_scale'][-1]:.0f}; "
          f"{compiles_in_window} compile(s) in the window", flush=True)

    with timed("check"):
        check = cell.check()
    agree = _replicas_agree([last_metrics["loss"], max(
        jax.tree_util.tree_leaves(cell.state.params), key=lambda a: a.size)])
    print(f"check: {json.dumps(check)}; replicas agree: {agree} "
          f"({spans['check']:.1f} s)", flush=True)

    ctx = types.SimpleNamespace(
        workload=workload, chips=len(devices), k=cell.k, steps=steps,
        window_s=window_s, intervals=intervals, setup_s=setup_s, spans=spans,
        samples_per_step=cell.samples_per_step,
        flops_per_step=cell.flops_per_step, peaks=peaks,
        peak_bytes=peak_bytes, compiles_in_window=compiles_in_window,
        step_metrics=step_metrics, hlo=None, events=None, trace=None)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(check["correct"] and agree
                              and np.isfinite(step_metrics["loss"]).all()),
              "attempted": steps, "failed": int(bad.sum())}
    if trace:
        from benchmark import trace_reduce

        ctx.hlo = cell.pipe.compiled().as_text()
        with open(recorder.path, encoding="utf-8") as f:
            ctx.events = [json.loads(line) for line in f if line.strip()]
        ctx.trace = trace_reduce.reduce(tracer.xplane(), k=cell.k)
        device.update(busy_s=ctx.trace["busy_s"], window_s=ctx.trace["window_s"])
        result["breakdown"] = trace_reduce.breakdown(ctx.trace)
        with open(os.path.join(out_dir, "trace_reduced.json"), "w",
                  encoding="utf-8") as f:
            json.dump(ctx.trace, f, indent=1)
    values = {}
    for m in (plan.per_layer if trace else plan.end_to_end):
        value = m["reader"].compute(ctx)
        if value is not None:
            values[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result.update(metrics=values, device=device)
    return result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = p.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace), t0=_T0)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
