"""ImageNet training with apex_tpu amp — the TPU port of the reference
entry point (``examples/imagenet/main_amp.py``): same CLI surface
(--arch/--opt-level/--keep-batchnorm-fp32/--loss-scale/--sync_bn/-b/--lr...),
TPU-native mechanics (one jitted SPMD train step over a device mesh instead
of hooks + NCCL; bf16 instead of fp16).

The training loop runs on :class:`apex_tpu.runtime.StepPipeline`:
``--steps-per-call K`` chains K steps into ONE compiled program, batch
windows are staged on device through the prefetcher (H2D of window N+1
overlaps the device loop of window N — the reference ``data_prefetcher``'s
stream overlap, at window granularity), and metric prints read one
dispatch behind so the hot loop never drains the pipeline on a scalar.

Data: pass an ImageNet directory laid out as class subfolders of npy/JPEG
files, or use --synthetic (default when no dir is given) for generated
data.  Real-data runs go through the multi-worker input engine
(``--workers`` threads each assembling whole K-step windows — decode +
fused crop/flip/normalize (native C++, ``--augment``) + stack — with
async double-buffered device staging and per-host sharded loading on
multi-process runs); the loop prints a ``loader: stall X%`` line so an
input-bound run is an attributed number, not a steady-vs-best-window
mystery.  --synthetic pre-stages a pool generated by the native
counter-based generator (no Python RNG on the producer).  JPEG decode
itself is PIL — functional, but not a DALI-class engine (the reference
uses DALI for full-rate ImageNet) — so .npy or --synthetic are the
benchmarked paths.

Run (single chip or full pod — same command, SPMD handles both):
    python main_amp.py --synthetic -b 128 --opt-level O2 [--sync_bn]
"""

import os as _os
import sys as _sys

try:
    import apex_tpu  # noqa: F401
except ModuleNotFoundError:  # running from a source checkout
    _sys.path.insert(0, _os.path.abspath(_os.path.join(
        _os.path.dirname(__file__), *[_os.pardir] * 2)))

import argparse
import functools
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import cache as apex_cache
from apex_tpu import data as apex_data
from apex_tpu import runtime, training
from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
from apex_tpu.data import normalize_images, synthetic_imagenet
from apex_tpu.models import (ResNet18, ResNet34, ResNet50, ResNet101,
                             ResNet152)
from apex_tpu.training import make_train_step

ARCHS = {"resnet18": ResNet18, "resnet34": ResNet34, "resnet50": ResNet50,
         "resnet101": ResNet101, "resnet152": ResNet152}


def parse(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu ImageNet Training")
    p.add_argument("data", nargs="?", default=None, help="path to dataset")
    p.add_argument("--arch", "-a", default="resnet18", choices=sorted(ARCHS))
    p.add_argument("--epochs", default=90, type=int)
    p.add_argument("-b", "--batch-size", default=256, type=int,
                   help="GLOBAL batch size (split over the mesh)")
    p.add_argument("--lr", "--learning-rate", default=0.1, type=float)
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--weight-decay", "--wd", default=1e-4, type=float)
    p.add_argument("--print-freq", "-p", default=10, type=int)
    p.add_argument("--prof", default=-1, type=int,
                   help="stop after N iterations (profiling); on "
                        "synthetic runs with a device loop, best-window "
                        "timing then adds 6 extra calls (3 windows x 2 "
                        "calls, reusing one synthetic batch) beyond this "
                        "budget")
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--sync_bn", action="store_true")
    p.add_argument("--opt-level", type=str, default="O0")
    p.add_argument("--bucketed", action="store_true",
                   help="flat-bucket optimizer state (ISSUE 4): moments "
                        "carried as a few large per-dtype buffers instead "
                        "of two per parameter leaf")
    p.add_argument("--keep-batchnorm-fp32", type=str, default=None)
    p.add_argument("--loss-scale", type=str, default=None)
    p.add_argument("--fused-loss", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="contrib.xentropy fused softmax-cross-entropy "
                        "(one pass, saves only max_log_sum_exp) instead "
                        "of log_softmax + gather; --no-fused-loss keeps "
                        "the reference composition")
    p.add_argument("--compilation-cache", default=None, metavar="DIR",
                   help="where the persistent XLA compilation cache "
                        "goes when JAX_COMPILATION_CACHE_DIR is not set "
                        "(the variable always wins; default "
                        "<checkout>/.jax_cache — apex_tpu.cache."
                        "resolve_dir).  Compiles are disk hits on the "
                        "second process start; the kernel autotuner's "
                        "per-device config cache (apex_tpu.tune) rides "
                        "in the same directory")
    p.add_argument("--aot-warmup", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="AOT-compile the device loop for the declared "
                        "(K, shape) signature before step 0 "
                        "(StepPipeline.warmup: lower().compile() on "
                        "abstract shapes) — steady state starts at "
                        "step 1 and the --prof best-window probes pay "
                        "no cold compiles")
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--workers", default=4, type=int,
                   help="input-engine worker threads: each assembles "
                        "whole K-step windows (decode/augment/stack) "
                        "ahead of the device loop "
                        "(apex_tpu.data.PrefetchLoader)")
    p.add_argument("--loader-depth", default=2, type=int,
                   help="staged device windows held ahead of the "
                        "consumer (double-buffered H2D staging)")
    p.add_argument("--augment", action="store_true",
                   help="train-time random crop + horizontal flip, "
                        "fused with normalize into ONE native C++ pass "
                        "(loads images at image-size + 32 and crops "
                        "back down; real-data runs only)")
    p.add_argument("--image-size", default=224, type=int)
    p.add_argument("--steps-per-epoch", default=100, type=int)
    p.add_argument("--steps-per-call", default=1, type=int,
                   help="chain N train steps into ONE compiled program "
                   "(apex_tpu.runtime.StepPipeline) — the TPU device-loop "
                   "shape; host dispatch and metric fetches then cost "
                   "once per N steps.  Real-data runs stage stacked "
                   "windows through the prefetcher (H2D overlaps the "
                   "device loop); a ragged final window is padded and "
                   "mask-gated on device, no retrace.")
    p.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                   help="async sharded checkpointing "
                        "(apex_tpu.checkpoint.CheckpointManager): "
                        "snapshot every --checkpoint-every steps at a "
                        "window boundary, serialize+fsync on a "
                        "background writer, atomic publish, keep the "
                        "3 newest")
    p.add_argument("--checkpoint-every", default=100, type=int,
                   help="save cadence in STEPS (floored to window "
                        "boundaries by the StepPipeline hook)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest VALID checkpoint under "
                        "--checkpoint-dir (corrupt/mid-write steps are "
                        "skipped): params/optimizer/scaler state, step "
                        "counter, loader cursor, and telemetry run-id "
                        "all round-trip — kill-and-resume is "
                        "bit-identical to an uninterrupted run")
    p.add_argument("--drain", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="graceful preemption drain (ON by default): "
                        "SIGTERM/SIGINT finish the in-flight window, "
                        "write a final checkpoint (with "
                        "--checkpoint-dir), flush the recorder summary "
                        "and watchdog health line, then exit cleanly; "
                        "a second signal escalates to a hard stop")
    p.add_argument("--telemetry", default=_os.environ.get(
                       "APEX_TPU_TELEMETRY") or None, metavar="PATH",
                   help="record the run-telemetry event stream (JSONL) "
                   "to PATH: window dispatch/gap, loader stage/stall, "
                   "loss-scale skips, retraces — analyze offline with "
                   "python -m apex_tpu.prof.timeline PATH.  Defaults "
                   "from APEX_TPU_TELEMETRY")
    p.add_argument("--metrics-port", type=int, metavar="PORT",
                   default=(int(_os.environ["APEX_TPU_METRICS_PORT"])
                            if _os.environ.get("APEX_TPU_METRICS_PORT")
                            else None),
                   help="serve live Prometheus metrics (steps/s, loader "
                   "stall, loss scale, checkpoint backlog, HBM gauges, "
                   "watchdog health) on http://:PORT/metrics while the "
                   "run is live (0 = ephemeral port; defaults from "
                   "APEX_TPU_METRICS_PORT)")
    p.add_argument("--metrics-textfile", metavar="PATH",
                   default=_os.environ.get("APEX_TPU_METRICS_TEXTFILE")
                   or None,
                   help="render the same metrics to an atomically-"
                   "replaced Prometheus textfile for node-exporter "
                   "style scraping (defaults from "
                   "APEX_TPU_METRICS_TEXTFILE)")
    p.add_argument("--watchdog", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="run-health rule engine (non-finite loss, "
                   "loss-scale collapse, loader-stall spikes, step-time "
                   "anomalies, retrace storms) folding the telemetry "
                   "events online; debounced alerts land in the stream "
                   "and a one-line health: summary prints at exit.  ON "
                   "by default when --telemetry is set "
                   "(--no-watchdog disables); --watchdog alone folds "
                   "without writing a stream")
    return p.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    print("opt_level =", args.opt_level)
    rec = None
    # Watchdog defaults ON whenever a telemetry stream is recorded
    # (ISSUE 6); --watchdog alone attaches the rule engine to a
    # devnull-backed recorder — health folding without a stream file.
    use_watchdog = (args.watchdog if args.watchdog is not None
                    else bool(args.telemetry))
    if (args.telemetry or use_watchdog or args.metrics_port is not None
            or args.metrics_textfile):
        # Installed as the ACTIVE recorder before the pipeline / loader
        # are built, so StepPipeline, PrefetchLoader, and the deferred
        # metric reads all pick it up with zero extra wiring.
        from apex_tpu import telemetry
        rec = telemetry.start(args.telemetry or _os.devnull,
                              watchdog=use_watchdog, example="imagenet",
                              export_port=args.metrics_port,
                              export_textfile=args.metrics_textfile,
                              arch=args.arch, opt_level=args.opt_level,
                              batch_size=args.batch_size,
                              steps_per_call=args.steps_per_call)
        if rec.exporter is not None:
            print(f"metrics export: {rec.exporter.describe()}")
    try:
        # close() in finally: a run that dies mid-training still gets its
        # summary event, a flushed stream, AND the watchdog's last alerts
        # — exactly the runs a telemetry record is most useful for.
        _train(args)
    finally:
        if rec is not None:
            wd = rec.watchdog
            # publish the conv dispatch dark counters BEFORE close so
            # the stream's summary snapshot carries them (ISSUE 20)
            try:
                from apex_tpu.ops import publish_conv_counters
                publish_conv_counters(rec.metrics)
            except Exception:                    # pragma: no cover
                pass
            rec.close()
            if args.telemetry:
                print(f"telemetry: {args.telemetry} "
                      f"(python -m apex_tpu.prof.timeline to analyze)")
            if wd is not None:
                print(f"health: {wd.format_line()}"
                      f"{_health_extras(rec)}")
    print("done")


def _health_extras(rec) -> str:
    """Peak-HBM (the memory ledger's gauge, set by
    ``StepPipeline.memory_stats`` at the end of the loop) and the live
    scrape target, appended to the exit ``health:`` line (ISSUE 10)."""
    extras = ""
    peak = rec.metrics.gauge("peak_hbm_bytes").value
    if peak:
        extras += f"  peak-hbm {peak / 1e6:.1f}MB"
    # conv dispatch dark counters (ISSUE 20): published into the
    # registry right before close, surfaced here so a run whose convs
    # silently fell back to XLA says so on its last line
    fb = rec.metrics.counter("conv_fallback_sites").value
    pl = rec.metrics.counter("conv_pallas_sites").value
    if fb or pl:
        extras += f"  conv-sites {pl or 0}p/{fb or 0}xla"
    if rec.exporter is not None:
        extras += f"  export {rec.exporter.describe()}"
    return extras


def build_model(args, n_dev):
    """The ResNet the example trains.  BN epilogues go through the norm
    (ISSUE 7): the contrib GroupBN NHWC module carries the apex
    bn_relu/bn_add_relu contract, so the ResNet norm-factory hook sends
    every bn->relu->(+residual) chain through bn_relu_residual (jnp
    that XLA fuses).  --sync_bn maps to bn_group=n_dev (stats psum'd
    across the mesh, same contract as SyncBatchNorm)."""
    dtype = (jnp.bfloat16 if args.opt_level in ("O1", "O2", "O3")
             else jnp.float32)
    norm_cls = functools.partial(
        BatchNorm2d_NHWC,
        bn_group=n_dev if args.sync_bn else 1,
        axis_name="data" if args.sync_bn else None,
        world_size=n_dev if args.sync_bn else None)
    return ARCHS[args.arch](num_classes=1000, dtype=dtype,
                            norm_cls=norm_cls)


def build(args):
    """Model, train state and device loop exactly as the training loop
    below drives them: ``make_train_step`` -> ``runtime.StepPipeline``
    wrapped in ``shard_map`` over a ``("data",)`` mesh of all local
    devices.  Returned as a namespace so ``chip_smoke.py`` exercises the
    SAME objects the example trains with, not an imitation."""
    if args.deterministic:
        jax.config.update("jax_default_matmul_precision", "highest")
    # Before the first compile: every jit in this process (including the
    # device loop below) then lands in the on-disk cache.
    apex_cache.enable(args.compilation_cache)

    devices = jax.devices()
    n_dev = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    if args.batch_size % n_dev:
        raise SystemExit(f"global batch {args.batch_size} must divide over "
                         f"{n_dev} devices")
    # Reference lr scaling: lr * global_batch/256 (main_amp.py --lr help).
    lr = args.lr * args.batch_size / 256.0

    model = build_model(args, n_dev)
    # jitted: ONE compile instead of one per initializer op (eager flax
    # init dispatches hundreds of tiny programs); same values.  Init
    # runs on the training model directly — collectives are guarded off
    # under is_initializing() — so the batch_stats pytree is
    # structure-stable from step 0.
    variables = jax.jit(lambda key, x: model.init(key, x, train=True))(
        jax.random.PRNGKey(0),
        jnp.ones((2, args.image_size, args.image_size, 3), jnp.float32))

    if args.fused_loss:
        # Fused label-smoothing softmax-xentropy (contrib port): one
        # pass, saves only max_log_sum_exp for the backward.  smoothing
        # 0 makes it exactly the NLL below; padding_idx=-1 because every
        # label 0..999 is a real class.
        from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    def loss_fn(p, ms, batch):
        xb, yb = batch
        logits, updated = model.apply(
            {"params": p, "batch_stats": ms}, xb, train=True,
            mutable=["batch_stats"])
        if args.fused_loss:
            losses = softmax_cross_entropy_loss(
                logits.astype(jnp.float32), yb, smoothing=0.0,
                padding_idx=-1)
            loss = jnp.mean(losses)
        else:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))
        return loss, updated["batch_stats"]

    loss_scale = args.loss_scale
    if loss_scale is not None and loss_scale != "dynamic":
        loss_scale = float(loss_scale)
    keep_bn = args.keep_batchnorm_fp32
    if isinstance(keep_bn, str):
        keep_bn = keep_bn == "True"

    tx = training.sgd(lr=lr, momentum=args.momentum,
                      weight_decay=args.weight_decay,
                      bucketed=args.bucketed)
    init_fn, step_fn = make_train_step(
        loss_fn, tx, opt_level=args.opt_level, loss_scale=loss_scale,
        keep_batchnorm_fp32=keep_bn, axis_name="data",
        has_model_state=True)
    state = init_fn(variables["params"], variables["batch_stats"])

    # Elastic fault-tolerant runtime (ISSUE 9): async sharded
    # checkpointing + deterministic resume.  The restore runs against
    # the freshly-initialized state as the template (same arch +
    # opt_level rule as the v1 recipe) and round-trips the step counter,
    # the loader cursor, and the telemetry run-id.
    mgr = None
    start_step = 0
    resumed_loader_sd = None
    if args.checkpoint_dir:
        from apex_tpu import checkpoint as apex_checkpoint
        mgr = apex_checkpoint.CheckpointManager(
            args.checkpoint_dir,
            every_steps=max(1, args.checkpoint_every))
        if args.resume:
            restored = mgr.restore(like=state)
            if restored is not None:
                state = restored.state
                start_step = restored.step
                resumed_loader_sd = restored.loader_state
                from apex_tpu import telemetry as _tel
                rec = _tel.get_recorder()
                if rec is not None:
                    rec.run_id = mgr.run_id
                    rec.event("resume", run_id=mgr.run_id,
                              step=start_step)
                print(f"resumed at step {start_step} "
                      f"(run {mgr.run_id}) from {args.checkpoint_dir}")

    spc = max(1, args.steps_per_call)
    if spc > 1 and args.prof > 0 and args.prof % spc:
        # The device loop advances spc steps per call; honor --prof at
        # call granularity rather than silently overrunning it.
        rounded = ((args.prof + spc - 1) // spc) * spc
        print(f"note: --prof {args.prof} rounded up to {rounded} "
              f"(multiple of --steps-per-call {spc})")
        args.prof = rounded
    if spc > 1 and args.print_freq % spc:
        # Same granularity rule for printing: the cadence below floors
        # print_freq to whole calls, so a print_freq < spc would silently
        # print (and pay the metric fetch) on EVERY call (ADVICE r4).
        rounded = ((args.print_freq + spc - 1) // spc) * spc
        print(f"note: --print-freq {args.print_freq} rounded up to "
              f"{rounded} (multiple of --steps-per-call {spc})")
        args.print_freq = rounded

    synthetic = args.synthetic or args.data is None
    # The device loop: spc steps per program over a [spc, batch, ...]
    # window.  The window's leading (step) axis stays unsharded; the
    # per-step batch axis shards over the mesh; the tail-mask is
    # replicated.  Streaming (real-data) windows are fresh buffers and
    # get donated with the state; the synthetic pool window is reused
    # every call, so it must not be.
    pipe = runtime.StepPipeline(
        step_fn, spc,
        wrap=lambda fn: shard_map(
            fn, mesh=mesh,
            in_specs=(P(), (P(None, "data"), P(None, "data")), P()),
            out_specs=(P(), P())),
        donate_window=not synthetic)
    return types.SimpleNamespace(
        n_dev=n_dev, state=state, pipe=pipe, spc=spc, synthetic=synthetic,
        mgr=mgr, start_step=start_step,
        resumed_loader_sd=resumed_loader_sd,
        data_sh=NamedSharding(mesh, P(None, "data")))


def synthetic_window(args, run):
    """ONE stacked ``[spc, batch, ...]`` window, uploaded once and cycled
    device-side.  Streaming per-step synthetic batches would measure
    host->device bandwidth (77 MB/step at b128/224), not training — the
    reference's synthetic smoke does the same with a single static
    batch.  Real-data runs stage fresh windows through the threaded
    prefetcher instead."""
    pool_n = 8
    pool = []
    # synthetic_imagenet is the native counter-based generator (int32
    # labels included) — no Python-side RNG on this path.
    for imgs, labels in synthetic_imagenet(args.batch_size,
                                           args.image_size, steps=pool_n):
        pool.append((normalize_images(imgs), labels))
    return jax.device_put(
        jax.tree_util.tree_map(
            lambda *xs: np.stack(xs),
            *(pool[i % pool_n] for i in range(run.spc))),
        run.data_sh)


def _train(args):
    run = build(args)
    state, pipe, spc = run.state, run.pipe, run.spc
    synthetic, mgr, start_step = run.synthetic, run.mgr, run.start_step
    resumed_loader_sd, data_sh = run.resumed_loader_sd, run.data_sh
    if synthetic:
        stack = synthetic_window(args, run)
        total = max(0, args.steps_per_epoch * args.epochs - start_step)
        windows = ((stack, spc) for _ in range(0, total, spc))
    else:
        from apex_tpu.data import directory_imagenet, load_batch

        # decode=False keeps the source generator cheap (file lists
        # only); the heavy decode + fused augment/normalize runs in the
        # worker pool, one whole window per worker.  Multi-process runs
        # shard the sample stream per host.
        load_size = args.image_size + 32 if args.augment else args.image_size
        stream = directory_imagenet(
            args.data, args.batch_size, load_size,
            epochs=args.epochs, decode=False,
            host_shard=True if jax.process_count() > 1 else None)
        if start_step:
            # Deterministic resume: one batch per step, so the stream's
            # cursor fast-forwards by index math (epoch, shard slice,
            # per-epoch shuffle, and BatchFiles.seq — hence the augment
            # draws — all re-derive identically; no decode happens).
            # The checkpoint's saved loader state goes through
            # ``resume()`` so its recorded schedule (dataset size, seed,
            # shard layout) is VALIDATED against this stream — a resume
            # pointed at a different dataset/seed fails loudly instead
            # of silently training on the wrong batch schedule.
            if resumed_loader_sd and "cursor" in resumed_loader_sd \
                    and "seed" in resumed_loader_sd:
                stream.resume(resumed_loader_sd)
            else:
                stream.skip(start_step)

        def assemble(task):
            import zlib
            imgs, labels = load_batch(task)
            if args.augment:
                # Per-batch deterministic rng: transform runs on
                # whichever worker picks the window up, so thread
                # identity can't seed it.  Mix the batch's global
                # sequence number with its content hash — the same
                # leading file in a later epoch must still draw fresh
                # crops/flips.
                rng = np.random.RandomState(
                    (zlib.crc32("|".join(task.paths).encode())
                     ^ (task.seq * 2654435761)) & 0x7FFFFFFF)
                return (apex_data.augment_images(imgs, args.image_size,
                                                 rng), labels)
            return normalize_images(imgs), labels

        windows = runtime.stage_windows(
            stream, spc, transform=assemble, device=data_sh,
            workers=max(1, args.workers), depth=max(1, args.loader_depth))

    warmed = False
    if args.aot_warmup:
        # Warm start (ISSUE 7): AOT-compile the hot loop for the
        # declared (K, shape) signature BEFORE step 0 — nothing runs,
        # nothing is donated.  Steady state then starts at step 1 (no
        # step-0 compile, no call-1 donated-sharding re-specialization)
        # and the --prof best-window probes below dispatch straight to
        # the compiled executable.  A signature the loader never yields
        # (e.g. a different label dtype) is simply never looked up.
        if synthetic:
            pipe.warmup(state, stack)
        else:
            # int32 labels: device staging canonicalizes the loader's
            # int64 numpy labels (no-x64 default), so the staged
            # window's signature is int32 — declare what arrives,
            # INCLUDING the staging sharding (windows arrive committed
            # to data_sh; an unsharded warmup would compile a program
            # the real dispatch can't use and re-pay the compile).
            def _sds(shape, dt):
                return jax.ShapeDtypeStruct(shape, dt, sharding=data_sh)
            win_sds = (_sds((spc, args.batch_size, args.image_size,
                             args.image_size, 3), jnp.float32),
                       _sds((spc, args.batch_size), jnp.int32))
            pipe.warmup(state, win_sds, tail=True)
        warmed = True

    t0 = time.perf_counter()
    reader = runtime.DeferredMetrics()
    print_every = max(1, args.print_freq // spc)   # cadence in WINDOWS

    def emit(wm):
        """Print one window's iter line from its stacked metrics — ONE
        device->host transfer per print, one dispatch behind the loop."""
        vals = wm.fetch()
        last = wm.n_valid - 1
        loss = float(np.ravel(vals["loss"])[last])
        scale = float(np.ravel(vals["loss_scale"])[last])
        done = wm.step + wm.n_valid
        ips = args.batch_size * done / (time.perf_counter() - t0)
        print(f"iter {done - 1}  loss {loss:.4f}  "
              f"speed {ips:.1f} img/s  loss_scale {scale:.0f}")

    def _loader_state(gstep):
        """Loader resume state at the TRAINING-LOOP boundary: batches
        consumed == global steps done (one batch per step), never the
        stream's own cursor — the prefetch pipeline runs ahead of the
        loop, and in-flight batches must replay after a resume."""
        if synthetic:
            return {"cursor": int(gstep)}
        return stream.state_dict(consumed=gstep)

    # Preemption drain (ISSUE 9, ON by default): the first
    # SIGTERM/SIGINT finishes the in-flight window, writes the final
    # checkpoint, and exits through the finally-flushed recorder.
    stop = runtime.GracefulShutdown().install() if args.drain else None

    t1 = None
    warm = 0
    # Cold pipelines compile on calls 0 AND 1 (call 0 the initial trace,
    # call 1 a re-specialization because the donated state returns with
    # the mesh's NamedSharding — jit caches on input shardings); with
    # --aot-warmup both are gone, so only call 0 (first dispatch through
    # the warmed executable, H2D of the state) is drained and the steady
    # clock starts at step 1 (ISSUE 7).
    warm_calls = 1 if warmed else 2
    printed = -1        # window index of the last emitted print
    window = None
    for ci, (window, n_valid) in enumerate(windows):
        if args.prof >= 0 and reader.steps_pushed >= args.prof:
            break
        state, metrics = pipe.step_window(state, window, n_valid)
        prev = reader.push(metrics, n_valid)
        if ci < warm_calls:
            # Drain the warmup calls synchronously so the steady clock
            # starts after them (the reference's AverageMeter skips
            # warmup the same way).
            reader.newest().fetch()
            t1 = time.perf_counter()
            warm = reader.steps_pushed
        if prev is not None and (prev.step // spc) % print_every == 0:
            emit(prev)
            printed = prev.step // spc
        gstep = start_step + reader.steps_pushed
        if stop is not None and stop.draining:
            # Window boundary drain: final checkpoint written
            # synchronously (the writer queue is drained first), then a
            # clean break — the recorder summary + watchdog health line
            # flush through main()'s finally.
            if mgr is not None:
                mgr.save(gstep, state, block=True,
                         loader_state=_loader_state(gstep))
            print(f"drain: stopping at step {gstep} ({stop.reason})")
            break
        if mgr is not None:
            mgr.maybe_save(gstep, state,
                           loader_state=_loader_state(gstep))
    if hasattr(windows, "close"):
        # --prof break abandons the stream mid-epoch: release the
        # prefetch producer thread and its staged device windows now
        # rather than at GC time (no-op after normal exhaustion, and on
        # the synthetic generator).
        windows.close()
    n_done = reader.steps_pushed
    newest = reader.newest()
    if newest is not None and (newest.step // spc) % print_every == 0 \
            and newest.step // spc > printed:
        emit(newest)     # the fetch doubles as the end-of-loop drain
    else:
        # force completion before stopping the clock: the stacked metric
        # fetch drains the enqueued pipeline
        reader.last()
    if n_done > warm and t1 is not None:
        steady = (args.batch_size * (n_done - warm)
                  / (time.perf_counter() - t1))
        # "first N calls", not "N compile iters": under the device loop
        # the excluded window is N*spc steps but only the compiling (or
        # warm-dispatch) CALLS, not N*spc compile iterations (ADVICE r4).
        print(f"steady {steady:.1f} img/s over {n_done - warm} iters "
              f"(excl first {warm_calls} call"
              f"{'s' if warm_calls > 1 else ''})")
    if mgr is not None:
        # Final checkpoint at the exact stopping step (a drain already
        # wrote it; last_saved dedupes), BEFORE the best-window probes
        # below advance the state past the reported step count.
        gstep = start_step + reader.steps_pushed
        if mgr.last_saved != gstep:
            mgr.save(gstep, state, block=True,
                     loader_state=_loader_state(gstep))
        mgr.close()
        print(f"checkpoint: step {gstep} saved under "
              f"{args.checkpoint_dir}")
    # spc > 1 only: at one step per call the 2-call window is bounded by
    # the fixed metric-fetch round-trip, so the "best window" would
    # measure fetch latency, not training.
    if synthetic and n_done > warm and spc > 1 and window is not None:
        # Best-of-3 windows (the repo's min-of-reps policy, like the
        # DCGAN example): a host stall in one steady window has nothing
        # to do with training throughput.  Each window = 2 calls (2*spc
        # steps) synced by one metric fetch, so the fixed fetch
        # round-trip amortizes over the window.
        best = float("inf")
        for _ in range(3):
            t0w = time.perf_counter()
            for _ in range(2):
                state, metrics = pipe.step_window(state, window, spc)
            runtime.WindowMetrics(0, spc, metrics).fetch()
            best = min(best, time.perf_counter() - t0w)
        print(f"best-window {args.batch_size * 2 * spc / best:.1f} img/s "
              f"over {2 * spc}-iter windows")
    # Input-engine attribution (ISSUE 3): the share of the wall clock
    # the train loop spent WAITING on the loader (the line
    # tests/test_native_data.py parses back).
    if hasattr(windows, "stats"):
        from apex_tpu.prof import loader_ledger
        led = loader_ledger(windows.stats.as_dict())
        print(apex_data.format_loader_line(led)
              + f" (producer stall {led.get('producer_stall_pct', 0.0)}%)")
    else:
        print("loader: stall 0.00% (pre-staged synthetic pool)")
    # HBM memory ledger (ISSUE 10): with --aot-warmup the compiled
    # executable is already in hand (a pure host read); otherwise the
    # hot program is re-lowered once at exit.  Emits the `memory` event
    # + peak_hbm_bytes gauge the exit health: line and the watchdog's
    # memory_headroom rule read.
    try:
        mem = pipe.memory_stats()
        if mem is not None:
            print(f"memory: peak-hbm {mem['peak_bytes'] / 1e6:.1f}MB "
                  f"(args {mem['argument_bytes'] / 1e6:.1f} "
                  f"temps {mem['temp_bytes'] / 1e6:.1f})")
    except Exception as e:                       # pragma: no cover
        print(f"memory: ledger unavailable ({type(e).__name__}: {e})")
    # Autotuner coverage (ISSUE 14): which Pallas kernels dispatched a
    # per-device tuned config this run (populated when the tune cache —
    # beside --compilation-cache, or APEX_TPU_TUNE_CACHE — has entries
    # for this device kind; misses fall back to the built-in defaults).
    try:
        from apex_tpu.tune import dispatch_stats
        ts = dispatch_stats()
        if ts["by_kernel"]:
            tuned = sorted(k for k, v in ts["by_kernel"].items()
                           if v["tuned"])
            print(f"tune: {ts['tuned_kernel_pct']:.0f}% of consulted "
                  f"kernels tuned ({', '.join(tuned) or 'none'})")
    except Exception:                            # pragma: no cover
        pass
    if stop is not None:
        stop.uninstall()


if __name__ == "__main__":
    main()
