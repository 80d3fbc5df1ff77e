#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU host, no network, nothing but this checkout:

1. **environment** — refuses to run unless ``jax.devices()[0].platform`` is
   ``"tpu"`` and its ``device_kind`` is in the published-peaks table
   (``apex_tpu.prof.roofline.DEVICE_PEAKS``); prints the device, the package
   versions, the compile-cache directory and the native-runtime tier.
2. **main path** — ResNet-50 (full width, 1000 classes) at amp O2 with
   dynamic loss scaling, SGD momentum, batch 128 per chip at 224², synthetic
   data, built by ``examples/imagenet/main_amp.py``'s own ``build()`` with
   its default flags: ``make_train_step`` -> ``runtime.StepPipeline`` wrapped
   in ``shard_map`` over a ``("data",)`` mesh of all local devices ->
   ``pipe.warmup`` -> one drain window + 3 windows of 16 steps -> deferred
   metric read.  On more than one device ``--sync_bn`` is added (BN
   statistics psum'd over the mesh).  Asserts finite losses, a loss scale
   that did not collapse, the step count, ZERO compiles after warm-up,
   the fused-loss kernel's custom calls in the compiled step (the one
   Mosaic kernel the step holds: its conv and BatchNorm sites run XLA's
   own code), memory in use on every device and, on several devices, an
   all-reduce in the compiled step and replicas that agree.
3. **kernel sweep** — every kernel in ``apex_tpu.tune.registry.all_specs()``
   compiled by Mosaic (``interpret=False``, ``impl="pallas"`` forced by
   the tuner's builders) at its ``example_shape`` (xentropy also at the
   ResNet-50 step's shape), forward and backward, against the jnp
   reference its module carries, within the spec's tolerance.

Any failure is an exception: non-zero exit, no result line.  On success the
last line of stdout is ``{"ok": true, "device": {...}}``.  The seconds it
prints are smoke timings (one run, compile state as stated), NOT benchmark
numbers.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

STEPS_PER_CALL = 16
WINDOWS = 3                 # timed windows after the drain window
BATCH_PER_CHIP = 128
IMAGE_SIZE = 224


def say(msg):
    print(msg, flush=True)


def environment():
    import importlib.metadata as md

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: needs a TPU, but JAX found platform="
            f"{dev.platform!r} (device_kind={dev.device_kind!r}, "
            f"{len(devices)} device(s)); nothing was run\n")
        sys.exit(2)

    from apex_tpu import cache
    from apex_tpu.prof import roofline

    peaks = roofline.device_peaks(dev.device_kind)     # unknown -> raises
    say(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={len(devices)}")
    say(f"versions: jax={jax.__version__} jaxlib={md.version('jaxlib')} "
        f"libtpu={md.version('libtpu')} python="
        f"{sys.version.split()[0]}")
    say(f"published peaks: {peaks['flops'] / 1e12:.0f} TFLOP/s bf16, "
        f"{peaks['hbm_gb_s']:.0f} GB/s ({peaks['source']})")
    cache_dir = cache.enable()
    origin = ("$" + cache.ENV_VAR if os.environ.get(cache.ENV_VAR)
              else "checkout default")
    say(f"compile cache: {cache_dir} ({origin}, "
        f"{len(os.listdir(cache_dir))} entries at start)")
    return devices


def main_path(devices):
    import jax
    import numpy as np

    sys.path.insert(0, os.path.join(HERE, "examples", "imagenet"))
    import main_amp as imagenet

    from apex_tpu import native, prof, runtime

    n_dev = len(devices)
    argv = ["--synthetic", "-a", "resnet50",
            "-b", str(BATCH_PER_CHIP * n_dev),
            "--image-size", str(IMAGE_SIZE), "--opt-level", "O2",
            "--loss-scale", "dynamic",
            "--steps-per-call", str(STEPS_PER_CALL)]
    if n_dev > 1:
        argv.append("--sync_bn")
    say(f"main path: examples/imagenet/main_amp.py {' '.join(argv)}")
    args = imagenet.parse(argv)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: compiles.append(dur)
        if name == "/jax/core/compile/backend_compile_duration" else None)

    t0 = time.perf_counter()
    run = imagenet.build(args)
    window = imagenet.synthetic_window(args, run)
    pipe, state = run.pipe, run.state
    assert run.n_dev == n_dev and run.spc == STEPS_PER_CALL
    t_built = time.perf_counter()
    say(f"native runtime available: {native.available}")

    pipe.warmup(state, window)
    t_ready = time.perf_counter()
    say(f"set-up (smoke timing): build {t_built - t0:.1f} s + trace/"
        f"compile {t_ready - t_built:.1f} s = {t_ready - t0:.1f} s "
        f"({len(compiles)} backend compiles)")

    # What is IN the executable the windows below dispatch to (read from
    # the compiled module, so nothing is lowered a second time).
    hlo = pipe.compiled().as_text()
    n_custom = hlo.count('custom_call_target="tpu_custom_call"')
    n_allreduce = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    say(f"compiled step: {n_custom} tpu_custom_call(s), {n_allreduce} "
        f"all-reduce(s)")
    assert n_custom > 0, (
        "no tpu_custom_call in the compiled step although the fused "
        "xentropy loss, a Mosaic kernel, is default-ON")
    if n_dev > 1:
        assert n_allreduce > 0, "no all-reduce in a multi-device step"

    # -- stepping: one drain window, then the timed ones ------------------
    n_before = len(compiles)
    reader = runtime.DeferredMetrics()
    fetched = []
    t_steady = None
    with prof.assert_trace_count(pipe.loop, 0):
        for i in range(1 + WINDOWS):
            state, metrics = pipe.step_window(state, window, STEPS_PER_CALL)
            prev = reader.push(metrics, STEPS_PER_CALL)
            if prev is not None:
                fetched.append(prev.fetch())    # one dispatch behind
            if i == 0:
                # first dispatch through the warmed executable (H2D of
                # the state): drained so the clock starts after it
                jax.block_until_ready(metrics)
                t_steady = time.perf_counter()
        fetched.append(reader.last())           # drains the pipeline
    t_done = time.perf_counter()
    n_after = len(compiles) - n_before
    assert n_after == 0, f"{n_after} compile(s) after warm-up"

    steps = (1 + WINDOWS) * STEPS_PER_CALL
    assert reader.steps_pushed == steps
    loss = np.concatenate([np.ravel(m["loss"]) for m in fetched])
    scale = np.concatenate([np.ravel(m["loss_scale"]) for m in fetched])
    skipped = int(np.concatenate(
        [np.ravel(m["overflow"]) for m in fetched]).sum())
    assert loss.shape == (steps,), loss.shape
    assert np.isfinite(loss).all(), f"non-finite loss: {loss}"
    assert scale[-1] >= 2.0 ** 8 and skipped <= steps // 4, (
        f"loss scale collapsed: final {scale[-1]}, {skipped} skipped steps")
    timed = WINDOWS * STEPS_PER_CALL
    dt = t_done - t_steady
    say(f"stepped {steps} steps = 1 drain + {WINDOWS} windows x "
        f"{STEPS_PER_CALL}; loss {loss[0]:.4f} -> {loss[-1]:.4f}, loss "
        f"scale {scale[0]:.0f} -> {scale[-1]:.0f}, {skipped} skipped; "
        f"0 compiles, 0 retraces after warm-up")
    say(f"stepping (smoke timing, not a benchmark): {timed} steps in "
        f"{dt:.2f} s = {dt / timed * 1e3:.1f} ms/step, "
        f"{args.batch_size * timed / dt:.0f} img/s over {n_dev} device(s)")

    for d in devices:
        used = d.memory_stats()["bytes_in_use"]
        say(f"memory: device {d.id} bytes_in_use={used} "
            f"({used / 2 ** 30:.2f} GiB)")
        assert used > 0, f"device {d.id} holds nothing"
    if n_dev > 1:
        # replicated outputs must really be equal on every device
        for name, arr in (("loss", metrics["loss"]),
                          ("head kernel", state.params["head"]["kernel"])):
            shards = [np.asarray(s.data) for s in arr.addressable_shards]
            assert len(shards) == n_dev
            for s in shards[1:]:
                np.testing.assert_array_equal(s, shards[0], err_msg=name)
        say(f"replicas agree: loss and head kernel equal on all {n_dev} "
            f"devices")


def _sweep_shapes(spec):
    shapes = [dict(spec.example_shape)]
    if spec.name == "xentropy":
        shapes.append({"rows": BATCH_PER_CHIP, "vocab": 1000})
    return shapes


def kernel_sweep():
    from apex_tpu.tune import registry
    from apex_tpu.tune.measure import check_against_reference

    for spec in registry.all_specs():
        for shape in _sweep_shapes(spec):
            t0 = time.perf_counter()
            err = check_against_reference(spec, shape)
            say(f"kernel {spec.name} {json.dumps(shape, sort_keys=True)}: "
                f"Mosaic ok, max scaled error {err:.2e} vs jnp reference "
                f"({time.perf_counter() - t0:.1f} s incl. compile)")


def main():
    t0 = time.perf_counter()
    devices = environment()
    main_path(devices)
    kernel_sweep()
    dev = devices[0]
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.0f} s")
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
