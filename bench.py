"""apex_tpu benchmark — prints ONE JSON line for the driver.

Headline metric (BASELINE.json): ResNet-50 images/sec/chip at amp O2
(bf16 compute, fp32 masters, fused SGD update) — one fully-jitted train
step per iteration, synthetic ImageNet-shaped data.  Secondary metrics
(in ``extra``): amp-O0 fp32 baseline, BERT-base FusedAdam train step
(exercises the Pallas FusedLayerNorm + xentropy kernels on chip,
BASELINE config 4), FusedAdam whole-model step vs an eager per-tensor
loop, a fused DCGAN joint-loss step, and — run IN THIS PROCESS, because
a chip belongs to one process at a time — the flagship example entry
points: ``examples/imagenet`` (the north-star "runs unmodified" claim)
and ``examples/dcgan`` (the imperative amp surface with three loss
scalers, BASELINE config 5).

Honesty contract:

* It runs on a TPU or not at all: ``main()`` refuses any other platform
  and any ``device_kind`` without published peaks, so a CPU wall can
  never be written under a device metric's name.
* JAX dispatch is asynchronous: every timing here forces execution with
  a real device->host scalar fetch that depends on the final step's
  full output chain.
* The emitted JSON self-validates: implied model TFLOP/s must be below
  the chip's bf16 peak or the bench fails loudly instead of reporting.
* The config that actually ran (backend, batch, image size, ms/step,
  MFU) is part of the JSON.

Not run on the current installation yet (see PERF.md); ROADMAP D1/S1
replace it with a small chip benchmark of named cells.
"""

import contextlib
import functools
import io
import json
import os
import re
import runpy
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import cache as _apex_cache
from apex_tpu.prof import roofline as _roofline_peaks

# Persistent compilation cache: the 8k-matmul calibration and the ResNet-50
# program each take minutes to compile; $JAX_COMPILATION_CACHE_DIR when
# set, <checkout>/.jax_cache otherwise (apex_tpu.cache.resolve_dir).
_apex_cache.enable()


def _chip_peak_flops():
    """Published bf16 peak of this device; an unknown ``device_kind``
    raises (one table: ``apex_tpu.prof.roofline.DEVICE_PEAKS``)."""
    return _roofline_peaks.device_peaks()["flops"]


_CALIB_FN = {}     # (n, iters) -> jitted chain + operands, compiled once


def _calibrate_peak(iters=48, reps=3, n=8192):
    """Measure the chip's *achievable* wall-clock bf16 matmul rate.

    Design (round-3 fix of VERDICT r2 weak #1):

    * The loop is a **provably serial chain** ``x <- bf16(x @ b)`` — each
      matmul consumes the previous result, so XLA can neither hoist a
      loop-invariant matmul (the r2 kernel's ``acc*0`` perturbation was
      foldable, which let small-shape runs report one matmul as ``iters``)
      nor CSE iterations.  ``b`` is scaled by 1/sqrt(n) so the chain is
      self-normalizing in bf16 (unit variance, no overflow) with zero
      non-matmul work in the body.
    * n=8192: a small shape measures per-program overhead, not the MXU.
    * iters=48: a ~1 s chain amortizes the per-call overhead and any
      host stall; a short chain lets one stall swing a pass.  The
      HEADLINE denominator is the MEDIAN of all passes (robust to a
      stalled outlier in either direction); the max still feeds the
      sanity gate (a workload beating the best the chip demonstrably did
      means the timing loop did not force execution).
    * Returns a LIST of per-pass rates; the caller runs this before and
      after the workloads, reports median + [min, max] band, and gates
      against the max.
    """
    key = (n, iters)
    if key not in _CALIB_FN:
        rs = np.random.RandomState(0)

        @jax.jit
        def run(x, b):
            def it(i, x):
                return (x @ b).astype(jnp.bfloat16)
            # Consume EVERY element of the final iterate: reading a single
            # entry would leave only one row of each iterate live (x_k[0,:]
            # depends only on x_{k-1}[0,:] @ b), inviting the same class of
            # slice-narrowing rewrite that broke the r2 kernel.
            return jnp.sum(jax.lax.fori_loop(0, iters, it, x)
                           .astype(jnp.float32))

        # Cache host copies + the jitted fn, NOT device arrays: the two
        # n x n operands (~256 MB at 8k) must not squat in HBM through the
        # timed workloads between the before/after calibration passes.
        x_host = rs.randn(n, n).astype(np.float32)
        b_host = (rs.randn(n, n) / np.sqrt(n)).astype(np.float32)
        _CALIB_FN[key] = (run, x_host, b_host)
    run, x_host, b_host = _CALIB_FN[key]
    x0 = jnp.asarray(x_host, jnp.bfloat16)       # transfers, untimed
    b = jnp.asarray(b_host, jnp.bfloat16)
    float(run(x0, b))                      # compile (first time) + warm
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(run(x0, b))  # jaxlint: disable=J001 -- timing fence: the calibration pass must block until the matmul completes
        dt = (time.perf_counter() - t0) / iters
        rates.append(2 * n ** 3 / dt)
    del x0, b                              # free HBM before the workloads
    return rates


# Wall-clock throughput is noisy (spread on this installation: not
# measured); a workload whose implied TFLOP/s lands
# above tol * max(measured calibration) means the timing loop did not force
# execution — fail loudly instead of reporting (VERDICT r2 next #3).
_GATE_TOL = 1.25

# Timing policy stamp: every wall timing in this file is min-of-reps
# (_best_pass / _time_steps reps=3).  Recorded in BENCH_EXTRA.json so the
# next round's regression guard only compares like-for-like (ADVICE r4).
_TIMING_POLICY = "min_of_3_passes"

# Example steady-vs-best-window gap (ISSUE 2): target the examples must
# hold on chip, and the looser self-validation gate that fails the bench
# loudly (single windows are noisy; the regression class this catches is
# 10x, not 1.2x).
_WINDOW_GAP_TARGET_PCT = 10.0
_WINDOW_GAP_GATE_PCT = 25.0

# Conv-path fusion + warm-start acceptance (ISSUE 7): per-example
# steady/best-window RATIO floors (the inverse view of the gap gate —
# "steady demonstrates at least this fraction of the chip's own best
# window"; with AOT warmup killing the step-0/1 compiles the steady
# clock has no excuse left), and a ResNet MFU floor so the fused conv
# epilogues must show up as device time, not just as code.  ISSUE 14
# switched the MFU floor from the static >26% to a RATCHET against the
# previous round's committed bench via prof.regress (name-inferred
# higher-is-better, the ratchet tolerance below + regress's 2-pt-point
# slack for pct metrics): each release must hold — and can only raise —
# the measured floor.  The static constant remains as the backstop when
# no comparable previous summary exists.
_STEADY_OVER_BEST_FLOORS = {"imagenet": 0.75, "dcgan": 0.75}
_RESNET_MFU_FLOOR_PCT = 26.0
_RESNET_MFU_RATCHET_TOL_PCT = 5.0

# DCGAN steady-rate floor (ISSUE 3 acceptance): >= 3x its r05 value
# (4.67 it/s, the imperative 10-dispatch/iter loop) — the pipelined
# default + pre-staged native synthetic pool must clear this on chip or
# the input/dispatch engines have regressed to the old steady floor.
_DCGAN_STEADY_GATE_IT_S = 3.0 * 4.67

# FusedAdam dispatch-overhead gates (ISSUE 4 acceptance): the bucketed
# step's wall/device ratio must stay <= 1.8 (r05 leafwise sat at 3.5x:
# pure per-leaf marshalling), and on the >=200-leaf deep tree the
# bucketed path must cut wall time >= 2x vs leafwise — dispatch-overhead
# regressions in the update half of the step fail the bench loudly.
_ADAM_WOD_GATE = 1.8
_ADAM_DEEP_SPEEDUP_GATE = 2.0

# Run-telemetry gates (ISSUE 5 acceptance): enabling the event stream
# must cost at most this factor of the disabled wall rate on the probe
# loop (the stream emits 2-3 events per WINDOW; the generous gate
# absorbs host noise — the regression class is "an event per step on
# the hot path" or a stray device sync, which shows up as 2x+); the
# disabled path must produce BITWISE-identical parameters (telemetry
# must never perturb numerics or dispatch); and the analyzer's
# loader-stall attribution must agree with the number the example
# prints (same LoaderStats.as_dict snapshot, so the tolerance only
# covers snapshot-time drift).
_TEL_OVERHEAD_GATE = 1.5
_TEL_STALL_TOL_PCT = 2.0

# ISSUE 9 (elastic runtime): the async checkpoint engine's stall
# contract — the train loop pays only the snapshot's D2H copy, the
# serialize+fsync rides the writer thread.  Gate: async stall per step
# <= 20% of the synchronous write's, measured on the SAME loop/state
# (the gate only arms when the sync stall is big enough to measure —
# below the floor the division is host-scheduler noise).
_CKPT_ASYNC_OVER_SYNC_GATE = 0.20
_CKPT_SYNC_FLOOR_MS = 1.0

# ISSUE 12 (mesh frontend): ZeRO-3 per-device param+optimizer-state
# bytes must scale ~1/shard_count on the probe mesh (8-way: ideal
# 0.125; the gate leaves room for the replicated scaler scalars and
# step counters), and the REAL 2-process CPU multi-host fixture
# (gloo collectives, per-host checkpoint shards, fleet merge of the
# two real streams) must pass end to end.
_MESH_Z3_RATIO_GATE = 0.16
_MESH_PROBE_DEVICES = 8


def _gate_implied(name, implied, peak, measured_max):
    if implied >= peak:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: {name} implies "
            f"{implied/1e12:.1f} TFLOP/s >= nameplate peak "
            f"{peak/1e12:.0f} TFLOP/s — the timing loop did not force "
            f"execution; refusing to report.")
    if measured_max and implied > _GATE_TOL * measured_max:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: {name} implies "
            f"{implied/1e12:.1f} TFLOP/s > {_GATE_TOL}x the measured "
            f"matmul ceiling {measured_max/1e12:.1f} TFLOP/s — "
            f"inconsistent with what this chip demonstrably achieves; "
            f"refusing to report.")


def _force(tree):
    """Force execution via one scalar device->host fetch.  The device
    executes enqueued programs in order, so fetching a single output of the
    LAST enqueued program drains the whole pipeline; touching every leaf
    (or ``block_until_ready`` on the whole tree) would walk hundreds of
    buffers inside the timed window."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if hasattr(l, "dtype")]
    return float(jnp.ravel(leaves[-1])[0].astype(jnp.float32))


def _best_pass(pass_fn, reps=3):
    """Min of ``reps`` calls to ``pass_fn() -> seconds_per_step`` — the
    shared timing policy (see _time_steps for why a single pass is not
    trusted)."""
    best = float("inf")
    for _ in range(reps):
        best = min(best, pass_fn())
    return best


def _time_steps(step, state, batch, iters, warmup=3, reps=3):
    """Returns (seconds/step, final state) — the state is returned so
    callers can keep driving the step (e.g. under a profiler trace) after
    the original buffers were consumed by ``donate_argnums``.

    Min over ``reps`` timed passes: a single pass can eat a host stall
    that has nothing to do with the step — the best pass is what the
    chip demonstrably does, the same policy as the flash timing and the
    calibration max."""
    for _ in range(warmup):
        state, m = step(state, batch)
    _force((m["loss"], state))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, batch)
        _force((m["loss"], state))  # full chain: metrics AND final state
        best = min(best, (time.perf_counter() - t0) / iters)
    return best, state


def _time_steps_device_loop(step_fn, state, batch, k=32, calls=2, reps=3):
    """Seconds/step with K steps chained into one program
    (:func:`apex_tpu.training.chain_steps`): the TPU device-loop rate,
    free of the per-call dispatch overhead (a fixed cost plus a cost per
    argument leaf, which the jitted-per-step numbers pay every step).
    The batch pool is the same batch broadcast K times; every step still
    runs the full train-step math on its own carry.

    ``donate_argnums=(0, 1)``: the loop donates BOTH the carried state
    and the consumed window (ISSUE 2 satellite — the [K, ...] stack is K
    full batches of HBM, ~2.4 GB at k=32/b128/224px, and un-donated it
    stays pinned for the whole call).  A donated window is consumed, so
    each call re-stages it with a tiny jitted broadcast program — the
    device-side analog of the runtime's fresh staged windows (an HBM
    write at memory bandwidth, ~3 ms for 2.4 GB, amortized over K
    steps)."""
    from apex_tpu.training import chain_steps

    chained = jax.jit(chain_steps(step_fn), donate_argnums=(0, 1))
    stage = jax.jit(lambda b: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (k,) + a.shape), b))
    for _ in range(2):                     # compile + resharding warmup
        state, m = chained(state, stage(batch))
    _force((m["loss"], state))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            state, m = chained(state, stage(batch))
        _force((m["loss"], state))
        best = min(best, (time.perf_counter() - t0) / (calls * k))
    return best


def _time_steps_pipeline(step_fn, state, batch, k=32, calls=2, reps=3):
    """Wall seconds/step of the USER-FACING training path
    (:class:`apex_tpu.runtime.StepPipeline`): K steps per host dispatch
    through the runtime engine itself — its Python overhead, window
    dispatch, and the deferred (one-dispatch-behind) metric read all
    included.  This is the number the ISSUE-2 acceptance compares
    against ``ms_per_step_o2_device_loop``: the dispatch gap the
    step-pipelining runtime closes for a real training loop.  The reused
    synthetic window is NOT donated (the examples' synthetic-pool
    shape); each rep is fenced by one stacked metric fetch."""
    from apex_tpu import runtime as rt

    pipe = rt.StepPipeline(step_fn, k, donate_window=False)
    window = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (k,) + a.shape), batch)
    for _ in range(2):                     # compile + resharding warmup
        state, m = pipe.step_window(state, window)
    _force((m["loss"], state))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            state, m = pipe.step_window(state, window)
        _force((m["loss"], state))   # drain: metrics AND final state
        best = min(best, (time.perf_counter() - t0) / (calls * k))
    return best


_PROF_TRACE_STEPS = 3   # shared with the bytes ledger below


def _prof_top_ops(step, state, batch, steps=_PROF_TRACE_STEPS, top=5):
    """Dogfood the profiler on a headline workload (VERDICT r2 next #3):
    capture a real XLA device trace around ``steps`` executions with
    :func:`apex_tpu.prof.capture.trace`, parse it with
    :func:`apex_tpu.prof.parse.parse_trace`, and return the top measured
    ops plus on-device totals.  On the TPU the trace is the device-event
    format (hlo_category per op); this is the parse stage proving itself
    on the same workload the bench reports.

    Round-4 lesson (VERDICT r3 missing #1 was a mis-read of this table):
    grouping by HLO *name* is misleading — XLA names a fusion after its
    root op, so a weight-gradient convolution whose epilogue is the SGD
    update shows up as ``multiply_subtract_fusion`` and a forward conv
    with a BN-stats epilogue as ``convert_reduce_fusion``.  The r3 table
    was read as "precision plumbing eats 72% of the step" when those
    fusions ARE the convolutions.  The ``by_category`` table (XLA's own
    hlo_category, which calls both of those "convolution fusion") is the
    truthful attribution and is now reported alongside."""
    import shutil
    import tempfile

    from apex_tpu.prof import capture
    from apex_tpu.prof import parse as prof_parse

    logdir = tempfile.mkdtemp(prefix="apex_bench_trace_")
    try:
        with capture.trace(logdir):
            s = state
            for _ in range(steps):
                s, m = step(s, batch)
            _force((m["loss"], s))
        tp = prof_parse.parse_trace(logdir)
        if not tp.records:
            return {"error": "trace produced no device events"}, None
        ops = sorted(tp.by_op().items(), key=lambda kv: -kv[1]["total_us"])
        by_cat = [
            {"category": k, "count": v["count"],
             "us_per_step": round(v["total_us"] / steps, 1),
             "pct": round(100 * v["total_us"] / tp.total_us, 1),
             "tflops": round(v["tflops_per_sec"], 1),
             "gb_per_s": round(v["bytes"] / (v["total_us"] * 1e-6) / 1e9, 0)
             if v["total_us"] else 0.0}
            for k, v in sorted(tp.by_category().items(),
                               key=lambda kv: -kv[1]["total_us"])[:6]]
        return {
            "steps_traced": steps,
            "device_us_per_step": round(tp.total_us / steps, 1),
            "top_ops": [
                {"op": name, "count": agg["count"],
                 "total_us": round(agg["total_us"], 1),
                 "mean_us": round(agg["mean_us"], 2)}
                for name, agg in ops[:top]],
            "by_category": by_cat,
        }, tp
    except Exception as e:               # never fail the bench on prof
        return {"error": f"{type(e).__name__}: {e}"}, None
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def _measure_precision_plumbing(steps=3):
    """Measure the O2 precision machinery IN ISOLATION on the real
    ResNet-50 parameter tree: bf16 compute-cast of all params (what
    ``compute_cast`` traces into the step), the unscale-with-overflow
    check, and the momentum-SGD master update with the skip mask.  This
    is everything `apex` implements in ``multi_tensor_scale_kernel.cu``
    and ``multi_tensor_sgd_kernel.cu`` — measured on-device as its own
    program, so its cost can be stated without untangling XLA's fusion
    attribution (the full-step profile fuses the update into the wgrad
    convolutions, where it is effectively free)."""
    import shutil
    import tempfile

    from apex_tpu.amp import policy as _policy
    from apex_tpu.models import ResNet50
    from apex_tpu.multi_tensor import multi_tensor_scale
    from apex_tpu.optimizers import functional as F
    from apex_tpu.prof import capture
    from apex_tpu.prof import parse as prof_parse

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 224, 224, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x, train=False)["params"]
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-4, jnp.float32), params)
    opt_state = F.sgd_init(params, momentum=0.9)

    @jax.jit
    def plumbing(params, grads, opt_state):
        # 1. compute-cast: fp32 masters -> bf16 model copy (keep-bn fp32)
        cast = _policy.convert_params(params, jnp.bfloat16,
                                      keep_norm_fp32=True)
        # 2. unscale + overflow flag (multi_tensor_scale contract)
        unscaled, overflow = multi_tensor_scale(grads, 1.0 / 1024.0)
        # 3. skip-masked momentum-SGD master update
        new_p, new_s = F.sgd_update(unscaled, opt_state, params, lr=0.1,
                                    momentum=0.9,
                                    apply_mask=jnp.logical_not(overflow))
        return cast, new_p, new_s

    out = plumbing(params, grads, opt_state)
    _force(out[1])
    logdir = tempfile.mkdtemp(prefix="apex_plumb_trace_")
    try:
        with capture.trace(logdir):
            for _ in range(steps):
                out = plumbing(params, grads, opt_state)
            _force(out[1])
        tp = prof_parse.parse_trace(logdir)
        if not tp.records:
            return None
        return round(tp.total_us / steps / 1e3, 3)    # ms per step
    except Exception:
        return None
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


# -- ResNet-50 (headline, BASELINE configs 1-2) -------------------------------

def _resnet_flops_per_step(batch, image_size):
    """Analytic ResNet-50 training FLOPs: ~4.09 GFLOP forward per 224x224
    image (multiply+add counted separately), x3 for fwd+bwd."""
    return 3 * 4.089e9 * (image_size / 224.0) ** 2 * batch


def _make_resnet_step(opt_level, batch, image_size=224, num_classes=1000,
                      fused=True):
    from apex_tpu import training
    from apex_tpu.models import ResNet50
    from apex_tpu.training import make_train_step

    dtype = jnp.bfloat16 if opt_level in ("O2", "O3") else jnp.float32
    if fused:
        # The shipping hot path (ISSUE 7): contrib GroupBN NHWC through
        # the ResNet norm-factory hook (bn->relu->(+residual) chains as
        # ONE Pallas bn_relu_residual epilogue each) + the NHWC
        # implicit-GEMM Pallas convs (ISSUE 18, per-site XLA fallback
        # for unservable shapes) + the contrib fused softmax-xentropy —
        # exactly what examples/imagenet runs with its default
        # --fused-bn/--fused-loss/--pallas-conv flags.
        import functools
        from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
        from apex_tpu.ops import PallasConv
        model = ResNet50(num_classes=num_classes, dtype=dtype,
                         norm_cls=functools.partial(BatchNorm2d_NHWC),
                         conv_cls=PallasConv)
    else:
        model = ResNet50(num_classes=num_classes, dtype=dtype)
    x = jnp.asarray(np.random.RandomState(0).rand(
        batch, image_size, image_size, 3), jnp.float32)
    y = jnp.asarray(np.arange(batch) % num_classes)
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    if fused:
        from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    def loss_fn(p, ms, b):
        xb, yb = b
        logits, updated = model.apply(
            {"params": p, "batch_stats": ms}, xb, train=True,
            mutable=["batch_stats"])
        if fused:
            loss = jnp.mean(softmax_cross_entropy_loss(
                logits.astype(jnp.float32), yb, smoothing=0.0,
                padding_idx=-1))
        else:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))
        return loss, updated["batch_stats"]

    tx = training.sgd(lr=0.1, momentum=0.9)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level=opt_level,
                                       has_model_state=True)
    state = init_fn(params, batch_stats)
    step = jax.jit(step_fn, donate_argnums=(0,))
    return step, state, (x, y), step_fn


# -- BERT-base FusedAdam (BASELINE config 4; Pallas layernorm + xentropy) -----

def _bert_flops_per_step(n_dense_params, batch, seq, hidden, vocab, layers):
    """Matmul-only analytic training FLOPs (VERDICT r2 next #3: do not
    charge matmul FLOPs to lookup params).

    * ``dense``: 6·N·B·S over **dense-kernel params only** — embedding
      tables (word/position/token-type) are gathers/adds, no MXU work.
    * ``head``: the tied-embedding projection ``feats @ emb.T`` IS a
      matmul (fwd 2·B·S·H·V, bwd dgrad+wgrad 4·B·S·H·V); counted here
      explicitly since its weight was excluded from ``dense``.
    * ``attn``: QK^T and PV, fwd+bwd, both mult+add counted.
    """
    dense = 6 * n_dense_params * batch * seq
    head = 6 * batch * seq * hidden * vocab
    attn = 3 * layers * 4 * seq * seq * hidden * batch
    return dense + head + attn


def _make_bert_step(batch=16, seq=128):
    from apex_tpu import training
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    from apex_tpu.models import bert_base
    from apex_tpu.training import make_train_step

    # attention_impl="flash": the Pallas flash-attention kernel on TPU
    # (falls back to the jnp blockwise path off-TPU).
    model = bert_base(dtype=jnp.bfloat16, num_classes=None,
                      attention_impl="flash")
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 30522, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, 30522, (batch, seq)))
    variables = model.init(jax.random.PRNGKey(0), ids)
    params = variables["params"]
    n_params = int(sum(np.prod(l.shape) for l in
                       jax.tree_util.tree_leaves(params)))
    n_emb = int(sum(
        np.prod(l.shape) for name in
        ("word_embeddings", "position_embeddings", "token_type_embeddings")
        for l in jax.tree_util.tree_leaves(params[name])))
    n_dense = n_params - n_emb       # matmul-participating params

    emb_kernel = params["word_embeddings"]["embedding"]
    vocab = int(emb_kernel.shape[0])

    def loss_fn(p, b):
        ids_b, labels_b = b
        feats = model.apply({"params": p}, ids_b)          # [b, s, h] fp32
        logits = feats @ p["word_embeddings"]["embedding"].T  # tied head
        losses = softmax_cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]),
            labels_b.reshape(-1), smoothing=0.1, padding_idx=-1)
        return jnp.mean(losses)

    tx = training.adam(lr=1e-4)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level="O2")
    state = init_fn(params)
    step = jax.jit(step_fn, donate_argnums=(0,))
    hidden = int(emb_kernel.shape[1])
    return (step, state, (ids, labels), n_params, n_dense, hidden, vocab,
            step_fn)


def _bert_mfu_bound(ledger, flops, measured_med, prof):
    """Additive no-overlap reference model for the BERT step: matmuls at
    the calibration-median rate PLUS the intrinsic Adam state sweep (30
    B/param) at the trace's loop-fusion bandwidth, as if the two never
    overlapped.

    NOT a hard ceiling: XLA fuses part of the update into wgrad-matmul
    epilogues and real matmuls can beat the calibration median, so a
    measured step may land under the additive total (an r5 run did:
    13.64 ms vs 14.18 additive).  Its value is the decomposition — how
    much of the step the non-matmul intrinsic traffic explains — not a
    gate.  Uses the device's published HBM bandwidth when the trace
    lacks a loop-fusion row.
    """
    if not (ledger and measured_med) or "error" in (ledger or {}):
        return None
    ideal_ms = flops / measured_med * 1e3
    opt_gb = ledger["intrinsic"].get("optimizer_gb")
    if not opt_gb:
        return None
    from apex_tpu.prof.parse import LOOP_FUSION_CATEGORY
    bw, bw_source = (_roofline_peaks.device_peaks()["hbm_gb_s"],
                     "published_hbm")
    for row in (prof or {}).get("by_category", []):
        if row.get("category") == LOOP_FUSION_CATEGORY \
                and row.get("gb_per_s"):
            bw, bw_source = row["gb_per_s"], "measured_" \
                + LOOP_FUSION_CATEGORY.replace(" ", "_")
            break
    floor_ms = opt_gb / bw * 1e3
    return {
        "ideal_matmul_ms": round(ideal_ms, 2),
        "optimizer_sweep_ms": round(floor_ms, 2),
        "optimizer_sweep_bw_gb_s": round(bw, 1),
        # drift guard (ADVICE r5): says whether the bandwidth above was
        # measured from the trace's loop-fusion row or is the hardcoded
        # 800 GB/s fallback — a renamed category can no longer silently
        # change the additive model without signal.
        "optimizer_sweep_bw_source": bw_source,
        "additive_model_mfu_pct": round(
            100 * ideal_ms / (ideal_ms + floor_ms), 1),
        "note": ("additive no-overlap model at the calibration median; "
                 "a measured step can beat it (epilogue fusion, "
                 "above-median matmuls) — reference point, not a ceiling"),
    }


# -- FusedAdam whole-model step vs eager per-tensor loop ----------------------

def _adam_fused_vs_eager(iters):
    """BASELINE metric 'FusedAdam step time vs eager': one jitted
    whole-model update (the multi-tensor capability) vs a per-tensor
    dispatch loop (the analog of an unfused eager optimizer)."""
    from apex_tpu.models import bert_base
    from apex_tpu.optimizers import functional as F

    model = bert_base(dtype=jnp.bfloat16, num_classes=None)
    ids = jnp.asarray(np.zeros((1, 16), np.int32))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-4, p.dtype), params)

    # fused: whole pytree in ONE program.  donate_argnums=(1, 2): the
    # consumed optimizer state + params alias the outputs (ISSUE 3
    # satellite — un-donated, the ~790-leaf update marshalled a full
    # copy of every master/momentum buffer per call, a pure dispatch
    # tax the reference's in-place multi_tensor_adam never pays).
    upd = functools.partial(F.adam_update, lr=1e-3)
    state = F.adam_init(params)
    fused = jax.jit(upd, donate_argnums=(1, 2))

    def run_fused(params, state):
        return fused(grads, state, params)

    def _fresh():
        # Donation consumes (params, state): every pass starts from
        # live copies, materialized before the clock starts.
        p, s = jax.tree_util.tree_map(jnp.copy, (params, state))
        _force(p)
        return p, s

    p, s = run_fused(*_fresh())
    _force(p)

    # min-of-reps (_best_pass): the ~600-leaf arg dispatch dominates this
    # number and is noisy pass-to-pass.
    def fused_pass():
        p, s = _fresh()
        t0 = time.perf_counter()
        for _ in range(iters):
            p, s = run_fused(p, s)
        _force(p)
        return (time.perf_counter() - t0) / iters

    t_fused = _best_pass(fused_pass)

    # eager: one dispatch per tensor (same math), jit per shape
    @jax.jit
    def one(g, p, m, v, t):
        b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-3
        gf = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * gf
        v = b2 * v + (1 - b2) * gf * gf
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return (p - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype), m, v

    leaves_p, treedef = jax.tree_util.tree_flatten(params)
    leaves_g = jax.tree_util.tree_leaves(grads)
    ms = [jnp.zeros(l.shape, jnp.float32) for l in leaves_p]
    vs = [jnp.zeros(l.shape, jnp.float32) for l in leaves_p]

    def run_eager(ps, ms, vs, t):
        out_p, out_m, out_v = [], [], []
        for g, pp, m, v in zip(leaves_g, ps, ms, vs):
            npp, nm, nv = one(g, pp, m, v, t)
            out_p.append(npp); out_m.append(nm); out_v.append(nv)
        return out_p, out_m, out_v

    ps2, ms2, vs2 = run_eager(leaves_p, ms, vs, 1.0)   # compile all shapes
    _force(ps2)

    def eager_pass():
        t0 = time.perf_counter()
        ps2, ms2, vs2 = leaves_p, ms, vs
        for i in range(iters):
            ps2, ms2, vs2 = run_eager(ps2, ms2, vs2, float(i + 1))
        _force(ps2)
        return (time.perf_counter() - t0) / iters

    t_eager = _best_pass(eager_pass)

    # -- the kernel itself, not the host dispatch around it:
    # (a) device time of ONE fused update, traced as its own program —
    #     the honest analog of the reference's multi_tensor_adam kernel
    #     time (roofline: ~2.6 GB of param+state traffic);
    # (b) K-chained wall time (lax.scan of K updates in one program), so
    #     the ~790-leaf dispatch tax amortizes like a real train loop.
    def _device_ms(run, fresh):
        if jax.default_backend() != "tpu":
            return None
        import shutil
        import tempfile

        from apex_tpu.prof import capture
        from apex_tpu.prof import parse as prof_parse

        logdir = tempfile.mkdtemp(prefix="apex_adam_trace_")
        try:
            with capture.trace(logdir):
                p, s = fresh()        # donation consumes the operands
                for _ in range(3):
                    p, s = run(p, s)
                _force(p)
            tp = prof_parse.parse_trace(logdir)
            if tp.records:
                return round(tp.total_us / 3 / 1e3, 3)
            return None
        except Exception:
            return None
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    t_dev_ms = _device_ms(run_fused, _fresh)

    K = 16

    @jax.jit
    def chained(p, s):
        def one_step(carry, _):
            p, s = carry
            return fused(grads, s, p), None
        (p, s), _ = jax.lax.scan(one_step, (p, s), None, length=K)
        return p, s

    p, s = chained(params, state)
    p, s = chained(p, s)          # resharding warmup (2 calls compile)
    _force(p)

    def chained_pass():
        t0 = time.perf_counter()
        p, s = params, state
        for _ in range(max(2, iters // K)):
            p, s = chained(p, s)
        _force(p)
        return (time.perf_counter() - t0) / (max(2, iters // K) * K)

    t_chained = _best_pass(chained_pass)

    # -- bucketed flat-bucket path (ISSUE 4): masters + optimizer state
    # live as a few large per-dtype buffers (the FusedOptimizer bucketed
    # contract), grads arrive as packed fp32 buckets (the amp unscale
    # output) — the jit call boundary passes O(buckets) arguments instead
    # of ~4 per leaf, which is exactly the wall-vs-device gap above.
    from apex_tpu.multi_tensor.buckets import BucketStore
    store = BucketStore(params)
    g_packed = store.pack_jit(grads, dtype=jnp.float32)
    state_b = F.adam_init(params, store=store)
    p_packed = store.pack_jit(params)
    fused_b = jax.jit(functools.partial(F.adam_update, lr=1e-3, store=store),
                      donate_argnums=(1, 2))

    def run_bucketed(p, s):
        return fused_b(g_packed, s, p)

    def _fresh_b():
        p, s = jax.tree_util.tree_map(jnp.copy, (p_packed, state_b))
        _force(p)
        return p, s

    p, s = run_bucketed(*_fresh_b())
    _force(p)

    def bucketed_pass():
        p, s = _fresh_b()
        t0 = time.perf_counter()
        for _ in range(iters):
            p, s = run_bucketed(p, s)
        _force(p)
        return (time.perf_counter() - t0) / iters

    t_bucketed = _best_pass(bucketed_pass)
    t_bucketed_dev_ms = _device_ms(run_bucketed, _fresh_b)

    return {
        "fused_s": t_fused, "eager_s": t_eager, "n_tensors": len(leaves_p),
        "device_ms": t_dev_ms, "chained_s": t_chained,
        "bucketed_s": t_bucketed, "bucketed_device_ms": t_bucketed_dev_ms,
        "n_buckets": store.n_buckets,
    }


def _adam_deep_pytree(iters, n_leaves=240):
    """ISSUE 4 satellite: FusedAdam over a DEEP (>=200-leaf) pytree,
    leafwise vs bucketed — wall ms/step AND first-compile seconds.  Deep
    trees are where the O(leaves) floors bite twice: ~4 jit arguments
    per leaf of per-call marshalling on the wall clock, and one update
    subgraph per leaf at compile time."""
    from apex_tpu.multi_tensor.buckets import BucketStore
    from apex_tpu.optimizers import functional as F

    rng = np.random.RandomState(0)
    shapes = ([(256, 32)] * (n_leaves // 4)
              + [(512,)] * (n_leaves // 2)
              + [(64, 16)] * (n_leaves - n_leaves // 4 - n_leaves // 2))
    params = {f"p{i:03d}": jnp.asarray(rng.randn(*s).astype(np.float32))
              for i, s in enumerate(shapes)}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-4, p.dtype), params)

    def _measure(make_step, make_operands):
        """(first_compile_seconds, best_pass_seconds_per_step)."""
        step = make_step()
        p0, s0 = make_operands()
        t0 = time.perf_counter()
        p, s = step(p0, s0)
        _force(p)
        compile_s = time.perf_counter() - t0

        def one_pass():
            p, s = make_operands()
            t0 = time.perf_counter()
            for _ in range(iters):
                p, s = step(p, s)
            _force(p)
            return (time.perf_counter() - t0) / iters

        return compile_s, _best_pass(one_pass)

    # leafwise: the pre-ISSUE-4 hot path (donated, jitted, one program).
    state_l = F.adam_init(params)

    def make_leafwise():
        fused = jax.jit(functools.partial(F.adam_update, lr=1e-3),
                        donate_argnums=(1, 2))
        return lambda p, s: fused(grads, s, p)

    def operands_leafwise():
        p, s = jax.tree_util.tree_map(jnp.copy, (params, state_l))
        _force(p)
        return p, s

    compile_l, t_leafwise = _measure(make_leafwise, operands_leafwise)

    # bucketed: params + state as Packed buckets across calls.
    store = BucketStore(params)
    g_packed = store.pack_jit(grads, dtype=jnp.float32)
    p_packed = store.pack_jit(params)
    state_b = F.adam_init(params, store=store)

    def make_bucketed():
        fused = jax.jit(
            functools.partial(F.adam_update, lr=1e-3, store=store),
            donate_argnums=(1, 2))
        return lambda p, s: fused(g_packed, s, p)

    def operands_bucketed():
        p, s = jax.tree_util.tree_map(jnp.copy, (p_packed, state_b))
        _force(p)
        return p, s

    compile_b, t_bucketed = _measure(make_bucketed, operands_bucketed)

    return {
        "n_leaves": len(shapes),
        "n_params": int(sum(np.prod(s) for s in shapes)),
        "leafwise_ms": round(t_leafwise * 1e3, 3),
        "bucketed_ms": round(t_bucketed * 1e3, 3),
        "speedup_bucketed": round(t_leafwise / t_bucketed, 2),
        "leafwise_first_compile_s": round(compile_l, 2),
        "bucketed_first_compile_s": round(compile_b, 2),
    }


# -- long-context flash attention (beyond-parity, SURVEY §5) ------------------

def _bench_flash_attention(seq, batch=1, heads=12, head_dim=64, iters=10):
    """Causal fwd+bwd of the Pallas flash kernel vs the jnp blockwise
    oracle at long context — the long-sequence story on one chip."""
    from apex_tpu.ops.attention import blockwise_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(batch, seq, heads, head_dim),
                           jnp.bfloat16) for _ in range(3))

    def timed(fn, reps=3):
        """Best of ``reps`` timing passes: wall-clock timing is noisy
        pass-to-pass, so a single pass cannot anchor
        a cross-round regression guard.  Min-of-reps reports what the
        chip demonstrably achieves — same policy as the calibration's
        max-of-passes."""
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32))
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        out = g(q, k, v)
        _force(out[0])
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = g(q, k, v)
            _force(out[0])
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    t_flash = timed(lambda q, k, v: flash_attention(q, k, v, causal=True))
    t_block = timed(lambda q, k, v: blockwise_attention(q, k, v, causal=True))
    return t_flash, t_block


# -- DCGAN multi-loss O1 (BASELINE config 5) ----------------------------------

def _make_dcgan_step(batch=64):
    from apex_tpu import training
    from apex_tpu.models import Discriminator, Generator
    from apex_tpu.training import make_train_step

    gen = Generator(dtype=jnp.bfloat16)
    disc = Discriminator(dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    z = jax.random.normal(rng, (batch, 100), jnp.float32)
    real = jnp.asarray(np.random.RandomState(0).rand(
        batch, 64, 64, 3), jnp.float32)
    gv = gen.init(rng, z, train=False)
    gp, g_bs = gv["params"], gv["batch_stats"]
    fake0 = gen.apply(gv, z, train=False)
    dv = disc.init(rng, fake0, train=False)
    dp, d_bs = dv["params"], dv["batch_stats"]

    from apex_tpu.ops.losses import binary_cross_entropy_with_logits

    def bce(logits, target):
        return binary_cross_entropy_with_logits(
            logits, jnp.full(logits.shape, target), reduction="mean")

    def loss_fn(params, b):
        z_b, real_b = b
        g = {"params": params["gen"], "batch_stats": g_bs}
        d = {"params": params["disc"], "batch_stats": d_bs}
        fake = gen.apply(g, z_b, train=False)
        d_loss = (bce(disc.apply(d, real_b, train=False), 1.0)
                  + bce(disc.apply(d, jax.lax.stop_gradient(fake),
                                   train=False), 0.0))
        g_loss = bce(disc.apply(d, fake, train=False), 1.0)
        return d_loss + g_loss       # two losses, one multi-model step

    tx = training.adam(lr=2e-4, beta1=0.5)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level="O2",
                                      loss_scale="dynamic")
    state = init_fn({"gen": gp, "disc": dp})
    return jax.jit(step_fn, donate_argnums=(0,)), state, (z, real)


# -- flagship examples, run in this process ----------------------------------

_ITER_RE = re.compile(
    r"iter (\d+)\s+loss ([\d.infa+-]+)\s+speed ([\d.]+) img/s")
_STEADY_RE = re.compile(r"steady ([\d.]+) img/s over (\d+) iters")
_BESTWIN_RE = re.compile(r"best-window ([\d.]+) img/s")
_DCGAN_RE = re.compile(r"Loss_D: ([\d.infa+-]+) Loss_G: ([\d.infa+-]+)")
_DONE_RE = re.compile(r"done in ([\d.]+)s \(([\d.]+) it/s\)")
_DCGAN_STEADY_RE = re.compile(r"steady ([\d.]+) it/s over (\d+) iters")
_DCGAN_BEST_RE = re.compile(r"best-of-3 windows: ([\d.]+) it/s")
# Input-engine attribution printed by every example (ISSUE 3): the share
# of the wall clock the train loop spent waiting on the loader.
_LOADER_RE = re.compile(r"loader: stall ([\d.]+)%")


def _run_example(rel_path, argv):
    """Run a repo example IN THIS PROCESS (``runpy`` as ``__main__``,
    ``sys.argv`` patched, stdout captured) and return its stdout.  Not a
    subprocess: this process has initialised the TPU backend, a chip
    belongs to one process at a time, and a child that needs it fails or
    hangs.  The driver-facing point stands: the REAL entry points under
    ``examples/`` run unmodified, not a bench-local reconstruction."""
    root = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(root, rel_path)
    out = io.StringIO()
    saved_argv = sys.argv
    sys.argv = [path] + list(argv)
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out):
            runpy.run_path(path, run_name="__main__")
    except SystemExit as e:
        if e.code not in (0, None):
            raise SystemExit(
                f"BENCH EXAMPLE FAILED ({e.code}): {path} "
                f"{' '.join(argv)}\n--- stdout ---\n"
                f"{out.getvalue()[-2000:]}")
    finally:
        sys.argv = saved_argv
    return out.getvalue(), time.perf_counter() - t0


def _window_gap_pct(steady, best_window):
    """Steady-vs-best-window gap, percent of the best window: how much
    of the rate the chip DEMONSTRABLY reached the example's steady loop
    leaves on the table (ISSUE 2: DCGAN's 12x gap hid behind the steady
    number alone).  0 when steady meets or beats the best window."""
    if not steady or not best_window:
        return None
    return round(max(0.0, 100.0 * (1.0 - steady / best_window)), 1)


def _bench_telemetry():
    """ISSUE 5 self-validation: run the SAME pipelined training loop with
    telemetry disabled and enabled, and prove three contracts —

    * **no-op when disabled**: the enabled run's final parameters are
      BITWISE identical to the disabled run's (instrumentation never
      perturbs numerics or dispatch);
    * **zero retraces**: both runs compile the hot program exactly once
      (instrumentation must not change trace signatures);
    * **bounded overhead**: min-of-3 wall time with the recorder active
      is within ``_TEL_OVERHEAD_GATE`` of the disabled rate.

    Also sanity-checks the offline analyzer on the emitted stream (step
    count, dispatch accounting).  Runs on CPU and TPU alike — the
    contracts are backend-independent.
    """
    import tempfile

    from apex_tpu import runtime, telemetry, training
    from apex_tpu.prof import assert_trace_count, timeline
    from apex_tpu.training import make_train_step

    # Isolate the probe from any env-driven recorder (APEX_TPU_TELEMETRY
    # on the whole bench): the DISABLED baseline below must really be
    # disabled — with a live ambient recorder both runs would be
    # instrumented and the 1.5x gate would compare telemetry against
    # itself.  Restored (not cleared) on exit so the ambient stream
    # keeps recording the rest of the bench (review finding).
    prev_ambient = telemetry.set_recorder(None)

    k, n_batches, reps = 4, 16, 3
    rs = np.random.RandomState(0)
    w0 = rs.randn(512, 512).astype(np.float32) / 23.0
    batches = [(rs.randn(64, 512).astype(np.float32),
                rs.randn(64, 512).astype(np.float32))
               for _ in range(n_batches)]

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    export_info = {}

    def one_run(tel_path):
        init_fn, step_fn = make_train_step(
            loss_fn, training.sgd(lr=0.01), opt_level="O2",
            loss_scale="dynamic")
        # watchdog=True (ISSUE 6): the overhead/bitwise gates below now
        # cover the rule engine folding every event on the hot path —
        # the acceptance pins the WATCHDOG-enabled probe loop under the
        # same 1.5x ceiling.  export_* (ISSUE 10): the enabled probe
        # ALSO renders the Prometheus textfile on the event threads and
        # serves the http endpoint, so the same ceiling now covers the
        # full telemetry+watchdog+export stack.
        rec = telemetry.start(tel_path, watchdog=True,
                              example="bench-telemetry",
                              export_textfile=(tel_path + ".prom"),
                              export_port=0, export_every_s=0.05) \
            if tel_path else None
        try:
            pipe = runtime.StepPipeline(step_fn, k)
            state = init_fn({"w": jnp.asarray(w0)})

            def one_pass(state):
                t0 = time.perf_counter()
                state, reader = pipe.run(
                    state, runtime.window_batches(iter(batches), k))
                _force(reader.flush()[0].metrics)   # fence the pipeline
                return time.perf_counter() - t0, state

            with assert_trace_count(pipe.loop, 1):
                _, state = one_pass(state)          # compile pass
                best = float("inf")
                for _ in range(reps):
                    dt, state = one_pass(state)
                    best = min(best, dt)
            if rec is not None and rec.exporter is not None:
                # Scrape-under-load (ISSUE 10): hit the live endpoint
                # while the recorder is still open, prove the exposition
                # carries the loop's own instruments.
                import urllib.request
                body = urllib.request.urlopen(
                    f"http://localhost:{rec.exporter.port}/metrics",
                    timeout=10).read().decode()
                export_info["scrape_ok"] = (
                    "apex_tpu_steps_dispatched_total" in body
                    and "apex_tpu_watchdog_ok" in body
                    and "apex_tpu_run_info" in body)
                export_info["endpoint"] = rec.exporter.describe()
        finally:
            if rec is not None:
                rec.close()
                if rec.exporter is not None:
                    # close() wrote the final render; count it
                    export_info["textfile_renders"] = rec.exporter.renders
                    export_info["textfile_ok"] = os.path.exists(
                        tel_path + ".prom")
        # deep-copy: on CPU device_get can return zero-copy views into
        # device buffers, and the second run's buffer reuse would
        # corrupt the first snapshot — a spurious bitwise-gate failure
        return best, jax.tree_util.tree_map(
            lambda x: np.array(x, copy=True),
            jax.device_get(state.params))

    try:
        t_off, params_off = one_run(None)
        tel_path = os.path.join(
            tempfile.gettempdir(),
            f"apex_tpu_bench_telemetry_{os.getpid()}.jsonl")
        t_on, params_on = one_run(tel_path)
    finally:
        telemetry.set_recorder(prev_ambient)

    identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(params_off),
                        jax.tree_util.tree_leaves(params_on)))
    stream_events = timeline.load_events(tel_path)
    analysis = timeline.analyze(stream_events)
    steps_per_pass = n_batches
    analyzer_ok = (
        analysis["steps"] == steps_per_pass * (reps + 1)
        and analysis["retraces"]["retraces"] == 0
        and 0.0 <= analysis["attribution"]["dispatch_gap_pct"] <= 100.0)
    # Regression-differ self-check (ISSUE 6 acceptance): a self-diff of
    # the analysis must be clean, and a synthetically degraded copy
    # (half the throughput, 3x the p50, fresh retraces) must fail —
    # prof.regress is only a CI gate if both directions hold.
    import copy

    from apex_tpu.prof import regress
    self_diff = regress.diff_summaries(analysis, analysis)
    degraded = copy.deepcopy(analysis)
    if degraded.get("steps_per_s"):
        degraded["steps_per_s"] = degraded["steps_per_s"] / 2.0
    for key in ("mean_ms", "p50_ms", "p90_ms", "p99_ms"):
        if (degraded.get("step_time") or {}).get(key):
            degraded["step_time"][key] *= 3.0
    degraded["retraces"]["retraces"] = (
        degraded["retraces"].get("retraces", 0) + 2)
    deg_diff = regress.diff_summaries(analysis, degraded)
    return {
        "disabled_wall_s": round(t_off, 4),
        "enabled_wall_s": round(t_on, 4),
        "overhead_ratio": round(t_on / t_off, 3) if t_off else None,
        "overhead_gate": _TEL_OVERHEAD_GATE,
        "bitwise_identical_disabled": bool(identical),
        "zero_retraces": analysis["retraces"]["retraces"] == 0,
        "analyzer_consistent": bool(analyzer_ok),
        "analyzer_steps": analysis["steps"],
        "stream": tel_path,
        "stream_events": analysis["n_events"],
        # The enabled run folded every event through the watchdog.  The
        # DETERMINISTIC rules (nonfinite / scale_collapse /
        # retrace_storm — all critical) must stay silent on the clean
        # probe and are gated in main(); the warning-level timing
        # heuristics (step_time, loader_stall) are load-sensitive on a
        # shared host (the probe's pass-boundary fetch IS a host stall)
        # and stay reported, not gated.
        "watchdog_alerts": (analysis.get("alerts") or {}).get("total", 0),
        "watchdog_critical_alerts": sum(
            1 for e in stream_events
            if e.get("kind") == "alert"
            and e.get("severity") == "critical"),
        "regress_self_diff_clean": not self_diff["regressions"],
        "regress_detects_degradation": bool(deg_diff["regressions"]),
        # Live-export self-validation (ISSUE 10): the overhead/bitwise
        # numbers above were measured WITH the exporter attached, so
        # export adds nothing the 1.5x gate does not already cover.
        "export": export_info,
    }


def _bench_fleet():
    """ISSUE 10 self-validation: the fleet merge must identify the
    injected slow host on EVERY window of the deterministic synthetic
    4-host fixture, and the clock aligner must recover the injected
    wall-anchor skew from the per-window dispatch indices.  Pure host
    JSON — backend-independent."""
    import shutil
    import tempfile

    from apex_tpu.prof import fleet

    n_hosts, n_windows, slow = 4, 12, 2
    clock_err = (0.040, -0.040, 0.080, -0.080)   # seconds, per host
    d = tempfile.mkdtemp(prefix="apex_tpu_bench_fleet_")
    try:
        fleet.synthetic_fleet(n_hosts, n_windows, 4, slow_host=slow,
                              clock_err_s=clock_err, dir=d)
        streams = fleet.load_fleet([os.path.join(d, "host*.jsonl")])
        a = fleet.analyze_fleet(streams)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    windows = a.get("windows") or []
    skews = {h["host"]: float(h["clock_skew_ms"])
             for h in a.get("hosts", [])}
    # relative to host 0's clock: skew_h = err_h - err_0, in ms
    expected = {h: (clock_err[h] - clock_err[0]) * 1e3
                for h in range(n_hosts)}
    align_ok = all(abs(skews.get(h, 1e9) - expected[h]) <= 5.0
                   for h in expected)
    return {
        "n_hosts": a.get("n_hosts"),
        "windows": len(windows),
        "straggler_host": (a.get("straggler") or {}).get("host"),
        "straggler_every_window": bool(
            windows and len(windows) == n_windows
            and all(w["slowest_host"] == slow for w in windows)),
        "straggler_consistent": (a.get("straggler") or {})
        .get("consistent"),
        "clock_skew_ms": {str(h): v for h, v in sorted(skews.items())},
        "clock_align_ok": bool(align_ok),
        "loader_worst_host": (a.get("loader") or {}).get("worst_host"),
        "loader_asymmetric": (a.get("loader") or {}).get("asymmetric"),
    }


def _bench_mesh():
    """ISSUE 12 self-validation, backend-independent (both probes run
    as CPU subprocesses so the on-chip bench and the CI smoke measure
    the same thing):

    * **ZeRO-3 memory scaling** — ``tools/mesh_memory_probe.py`` on a
      forced 8-device CPU mesh: per-device param+optimizer-state bytes
      from the committed shardings (exact), corroborated by the
      compiled sharded step's ``memory_analysis`` through
      ``prof.memory`` where the backend exposes it.  main() gates the
      ratio at ~1/shard_count.
    * **multi-host fixture** — ``tools/multihost_smoke.py --nproc 2``:
      REAL processes joined via ``multiproc.initialize`` (gloo
      collectives), bitwise cross-host metric parity, one checkpoint
      shard per host, fleet merge of the two real telemetry streams.
    """
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(f"--xla_force_host_platform_device_count="
                          f"{_MESH_PROBE_DEVICES}"),
               APEX_PROBE_REPO=root)
    out = {}
    probe = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "mesh_memory_probe.py")],
        env=env, capture_output=True, text=True, timeout=600)
    if probe.returncode == 0:
        try:
            out["memory"] = json.loads(probe.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            out["memory"] = {"error": "unparseable probe output"}
    else:
        out["memory"] = {"error": f"probe exited {probe.returncode}",
                         "stderr": probe.stderr[-2000:]}
    smoke = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "multihost_smoke.py"),
         "--nproc", "2"],
        env=dict(os.environ), capture_output=True, text=True, timeout=600)
    try:
        out["multihost"] = json.loads(smoke.stdout)
    except ValueError:
        out["multihost"] = {"ok": False,
                            "error": f"smoke exited {smoke.returncode}",
                            "stderr": smoke.stderr[-2000:]}
    return out


def _bench_checkpoint():
    """ISSUE 9 self-validation: measure ``checkpoint_stall_ms_per_step``
    on one pipelined training loop under three regimes — no
    checkpointing (the wall baseline), the SYNCHRONOUS write (serialize
    + fsync on the loop thread, the v1 shape), and the ASYNC engine
    (snapshot trigger only; serialize/fsync on the writer thread).

    The stall is the summed ON-LOOP-THREAD duration of the save
    triggers divided by steps — a direct measurement of the engine's
    contract ("the loop pays only the snapshot"), robust to host
    contention: a wall-clock difference would also charge the async
    writer's background CPU time to the loop on a CPU backend (where
    XLA compute and the writer share cores), which is exactly the
    regime CI runs this probe in.  Whole-pass walls are recorded for
    context.  main() gates async <= 20% of sync (when sync is
    measurable), and every checkpoint either regime produced must
    validate + restore bitwise against the live state."""
    import shutil
    import tempfile

    from apex_tpu import checkpoint as ckpt_mod
    from apex_tpu import runtime, training
    from apex_tpu.training import make_train_step

    k, n_windows, reps = 4, 8, 3
    # One save per timed pass: a cadence that outruns the writer thread
    # degrades async to sync THROUGH the backpressure path by design —
    # the stall gate measures the sustainable-cadence contract, and the
    # backlog case is the watchdog's checkpoint_stall rule's job.
    save_every = n_windows * k
    rs = np.random.RandomState(0)
    # ~8 MB of fp32 params -> ~32 MB serialized per save under O2
    # (masters + two moments + model copy): enough that a synchronous
    # npz+fsync visibly stalls the loop.
    w0 = rs.randn(1024, 2048).astype(np.float32) / 45.0
    batches = [(rs.randn(16, 1024).astype(np.float32),
                rs.randn(16, 2048).astype(np.float32))
               for _ in range(n_windows * k)]

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    def one_run(mode):
        init_fn, step_fn = make_train_step(
            loss_fn, training.sgd(lr=0.01), opt_level="O2",
            loss_scale="dynamic")
        pipe = runtime.StepPipeline(step_fn, k)
        state = init_fn({"w": jnp.asarray(w0)})
        ck_dir = tempfile.mkdtemp(prefix=f"apex_tpu_bench_ckpt_{mode}_")
        mgr = None
        if mode != "none":
            mgr = ckpt_mod.CheckpointManager(
                ck_dir, every_steps=save_every, keep=2,
                async_write=(mode == "async"))

        gstep = {"n": 0}                        # cumulative across passes
        acc = {"save_s": 0.0, "saves": 0}       # loop-thread trigger time

        def one_pass(state):
            t0 = time.perf_counter()
            for window, n_valid in runtime.window_batches(
                    iter(batches), k):
                state, metrics = pipe.step_window(state, window, n_valid)
                gstep["n"] += n_valid
                if mgr is not None:
                    # cumulative step: the cadence keeps saving on every
                    # timed pass, not only the first.  The time THIS
                    # call holds the loop thread IS the stall under
                    # measurement (sync: snapshot+serialize+fsync;
                    # async: snapshot + any backpressure).
                    ts = time.perf_counter()
                    if mgr.maybe_save(gstep["n"], state):
                        acc["save_s"] += time.perf_counter() - ts
                        acc["saves"] += 1
            _force(metrics)                     # fence the pipeline
            return time.perf_counter() - t0, state

        _, state = one_pass(state)              # compile pass
        acc["save_s"], acc["saves"] = 0.0, 0    # exclude the compile pass
        best = float("inf")
        for _ in range(reps):
            dt, state = one_pass(state)
            best = min(best, dt)
        restored_ok = True
        if mgr is not None:
            # the trailing async writes finish OFF the timed loop; the
            # published checkpoint must still validate and restore the
            # live state bitwise
            if mgr.last_saved != gstep["n"]:
                mgr.save(gstep["n"], state, block=True)
            mgr.wait()
            restored = mgr.restore(like=state)
            restored_ok = restored is not None and all(
                np.array_equal(np.asarray(a, np.float32),
                               np.asarray(b, np.float32))
                for a, b in zip(
                    jax.tree_util.tree_leaves(
                        jax.device_get(restored.state)),
                    jax.tree_util.tree_leaves(jax.device_get(state))))
            mgr.close()
        shutil.rmtree(ck_dir, ignore_errors=True)
        stall_ms = (acc["save_s"] / (reps * n_windows * k) * 1e3
                    if acc["saves"] else 0.0)
        return best, stall_ms, acc["saves"], restored_ok

    steps = n_windows * k
    t_none, _, _, _ = one_run("none")
    t_sync, sync_stall, sync_saves, sync_ok = one_run("sync")
    t_async, async_stall, async_saves, async_ok = one_run("async")
    return {
        "steps_per_pass": steps,
        "save_every_steps": save_every,
        "saves_timed": {"sync": sync_saves, "async": async_saves},
        "baseline_wall_s": round(t_none, 4),
        "sync_wall_s": round(t_sync, 4),
        "async_wall_s": round(t_async, 4),
        "checkpoint_stall_ms_per_step_sync": round(sync_stall, 3),
        "checkpoint_stall_ms_per_step_async": round(async_stall, 3),
        "async_over_sync": (round(async_stall / sync_stall, 3)
                            if sync_stall > 0 else None),
        "async_over_sync_gate": _CKPT_ASYNC_OVER_SYNC_GATE,
        "sync_floor_ms": _CKPT_SYNC_FLOOR_MS,
        "restore_bitwise_ok": bool(sync_ok and async_ok),
    }


def _bench_serving():
    """ISSUE 11 self-validation: a closed-loop load generator against
    the serving engine — submit a burst of mixed-length requests, drive
    the scheduler to completion, and prove the acceptance contracts:

    * **zero compiles after warmup across ALL sequence-length buckets**
      — the engine's jit callables are trace-count pinned at 0 for the
      whole load (every dispatch went through the AOT table) and no
      lookup ever missed;
    * **no failed requests**, including through a MID-LOAD weight
      hot-swap: a new checkpoint published while requests are in
      flight is staged and adopted between decode steps;
    * **post-swap decode output matches the new checkpoint's
      single-request output bitwise** (greedy decode is deterministic,
      so "the swap really took and really serves the new weights" is
      an equality, not a tolerance);
    * throughput (**tokens/sec**) and **p50/p99 request
      latency-under-load** are measured and recorded in
      BENCH_EXTRA/BENCH_SUMMARY.

    Runs on CPU and TPU alike — the contracts are backend-independent
    (absolute rates are only meaningful on chip)."""
    import shutil
    import tempfile

    from apex_tpu import serving
    from apex_tpu.checkpoint import CheckpointManager
    from apex_tpu.models import gpt_tiny
    from apex_tpu.prof import assert_trace_count

    model = gpt_tiny(max_len=128)
    rs = np.random.RandomState(0)
    probe = jnp.asarray(rs.randint(1, 1024, (1, 8)))
    params = model.init(jax.random.PRNGKey(1), probe)["params"]
    params_v2 = jax.tree_util.tree_map(lambda x: x * 1.01, params)

    buckets, page, max_seqs, max_new = (32, 64), 8, 4, 8
    n_requests = 12
    prompts = [rs.randint(1, 1024, (int(n),)).astype(np.int32)
               for n in rs.randint(4, 48, n_requests)]
    ckpt_dir = tempfile.mkdtemp(prefix="apex_tpu_bench_serving_")
    eng = serving.ServingEngine(model, params, buckets=buckets,
                                page_size=page, max_seqs=max_seqs,
                                watch_dir=ckpt_dir, poll_every_s=3600)
    try:
        t0 = time.perf_counter()
        eng.warmup()
        warmup_s = time.perf_counter() - t0
        pins = [assert_trace_count(fn, 0) for fn in eng._jit.values()]
        for p in pins:
            p.__enter__()
        try:
            # phase 1: half the load on the v1 weights
            comps = [eng.submit(p, max_new) for p in prompts[:6]]
            for _ in range(8):
                eng.step()
            # phase 2: publish v2 MID-LOAD; stage + adopt between steps
            mgr = CheckpointManager(ckpt_dir, keep=2, procs=(0, 1),
                                    async_write=False)
            mgr.save(11, params_v2)
            mgr.close()
            staged = eng.watcher.poll_once()
            comps += [eng.submit(p, max_new) for p in prompts[6:]]
            eng.run_until_idle()
            wall = time.perf_counter() - t0 - warmup_s
            results = [c.result(timeout=0) for c in comps]
        finally:
            for p in pins:
                p.__exit__(None, None, None)
        failed = [r for r in results if not r.ok]
        lat = sorted(r.timings["total_s"] for r in results if r.ok)
        tokens = int(eng.stats["tokens_out"])
        # post-swap probe: bitwise vs a fresh engine on the v2 weights
        post = eng.generate([prompts[0]], max_new_tokens=max_new)[0]
        ref_eng = serving.ServingEngine(model, params_v2,
                                        buckets=buckets, page_size=page,
                                        max_seqs=max_seqs)
        ref_eng.warmup(buckets=(post.bucket,))
        ref = ref_eng.generate([prompts[0]], max_new_tokens=max_new)[0]
        ref_eng.close()
        hotswap_ok = (staged and eng.stats["hotswaps"] == 1
                      and np.array_equal(post.tokens, ref.tokens))
        misses = int(eng.stats["aot_misses"])
        tracing = _serving_trace_probe(model, params_v2, buckets, page,
                                       max_seqs, max_new, prompts)
        return {
            "tracing": tracing,
            "n_requests": n_requests,
            "buckets": list(buckets),
            "max_seqs": max_seqs,
            "tokens_out": tokens,
            "tokens_per_s": round(tokens / wall, 2) if wall > 0 else None,
            "warmup_s": round(warmup_s, 3),
            "p50_latency_ms": round(
                _pct(lat, 50.0) * 1e3, 2) if lat else None,
            "p99_latency_ms": round(
                _pct(lat, 99.0) * 1e3, 2) if lat else None,
            "failed_requests": len(failed),
            "aot_misses": misses,
            "zero_compiles_after_warmup": misses == 0,
            "hotswaps": eng.stats["hotswaps"],
            "hotswap_ok": bool(hotswap_ok),
            "decode_steps": eng.stats["decode_steps"],
            "kv_pages_leaked": (
                eng.pages.total_pages - eng.pages.free_pages),
        }
    finally:
        eng.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _serving_trace_probe(model, params, buckets, page, max_seqs,
                         max_new, prompts):
    """ISSUE 20 self-validation: the request-tracing/SLO surface.

    * **tracing must not steer generation** — tokens with
      ``trace_sample_n=1`` + an SLO fold attached are BITWISE identical
      to a recorder-less engine's over the same prompts (greedy decode
      is deterministic, so this is an equality);
    * **no tracer, no spans** — a telemetry run without a tracer emits
      ZERO ``span`` events (the strict-no-op contract);
    * **overhead** — full sampling + SLO stays within the telemetry
      engine's ``_TEL_OVERHEAD_GATE`` of the recorder-less wall
      (min-of-3 loads on a warmed engine);
    * **offline == online** — ``prof.requests`` re-derives TTFT/TPOT
      percentiles from the stream's ``done`` events within 2% of the
      engine's own in-run reservoirs (both use the one shared
      nearest-rank definition), and reports goodput against the SLO
      spec the run served under.
    """
    import shutil
    import tempfile

    from apex_tpu import serving, telemetry
    from apex_tpu.prof import requests as prof_requests

    probe = prompts[:8]
    slo_spec = "ttft_p99<60s,tpot_p99<60s"   # gates mechanism, not speed
    d = tempfile.mkdtemp(prefix="apex_tpu_bench_trace_")
    stream = os.path.join(d, "serve.jsonl")

    def load(rec, reps):
        eng = serving.ServingEngine(model, params, buckets=buckets,
                                    page_size=page, max_seqs=max_seqs,
                                    telemetry=rec)
        try:
            eng.warmup()
            best, toks = float("inf"), None
            for _ in range(reps):
                t0 = time.perf_counter()
                res = eng.generate(probe, max_new_tokens=max_new)
                best = min(best, time.perf_counter() - t0)
                if toks is None:
                    toks = [np.asarray(r.tokens) for r in res]
            return best, toks
        finally:
            eng.close()

    try:
        wall_off, toks_off = load(None, reps=3)

        # no tracer attached -> the stream must hold zero span events
        rec0 = telemetry.start(os.path.join(d, "notrace.jsonl"),
                               trace_sample_n=0)
        load(rec0, reps=1)
        rec0.close()
        with open(os.path.join(d, "notrace.jsonl")) as f:
            dark_spans = sum(1 for ln in f if '"kind": "span"' in ln)

        rec = telemetry.start(stream, watchdog=True, trace_sample_n=1,
                              slo=slo_spec, example="bench_trace")
        wall_on, toks_on = load(rec, reps=3)
        eng_p = {
            name: rec.metrics.histogram(f"serving_{name}_s")
                     .percentiles((50.0, 99.0))
            for name in ("ttft", "tpot")}
        rec.close()

        bitwise_ok = (len(toks_off) == len(toks_on) and all(
            np.array_equal(a, b) for a, b in zip(toks_off, toks_on)))

        events = prof_requests.load_request_events([stream])
        a = prof_requests.analyze(events, slo=slo_spec)
        spans = sum(1 for e in events if e.get("kind") == "span")
        agree = []
        for name in ("ttft", "tpot"):
            st = (a["requests"] or {}).get(name) or {}
            for q, ms_key in ((0, "p50_ms"), (1, "p99_ms")):
                eng_v, ana_ms = eng_p[name][q], st.get(ms_key)
                if eng_v and ana_ms is not None:
                    agree.append(abs(ana_ms / 1e3 - eng_v) / eng_v * 100)
        slo_res = a.get("slo") or {}
        return {
            "tokens_bitwise_ok": bool(bitwise_ok),
            "zero_spans_without_tracer": dark_spans == 0,
            "overhead_ratio": (round(wall_on / wall_off, 3)
                               if wall_off > 0 else None),
            "overhead_gate": _TEL_OVERHEAD_GATE,
            "span_events": spans,
            "sampled_requests": a.get("n_sampled", 0),
            "analyzer_vs_engine_pct": (round(max(agree), 3)
                                       if agree else None),
            "analyzer_ttft_p99_ms": ((a["requests"] or {}).get("ttft")
                                     or {}).get("p99_ms"),
            "engine_ttft_p99_ms": (round(eng_p["ttft"][1] * 1e3, 3)
                                   if eng_p["ttft"][1] else None),
            "slo_spec": slo_spec,
            "goodput_pct": slo_res.get("goodput_pct"),
            "slo_met": slo_res.get("met"),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _pct(sorted_vals, q):
    """Nearest-rank percentile of an already-sorted list."""
    from apex_tpu.telemetry.metrics import nearest_rank_percentiles
    return nearest_rank_percentiles(sorted_vals, (q,))[0]


def _bench_quant(on_tpu):
    """ISSUE 13 self-validation: the int8 engine's three acceptance
    surfaces, measured on whatever backend runs the bench:

    * **matmul probe** — the calibrated :func:`quantized_matmul` vs the
      bf16 ``jnp.dot`` at a projection-sized shape.  main() gates
      ``o4_over_bf16 <= 1.0`` ON CHIP only (the MXU's int8 path is the
      2x; the CPU jnp fallback pays quantize/dequant with no int8 MAC
      rate to buy it back and is reported, not gated).
    * **LM step probe** — ms/step of the convergence harness's small
      GPT at O2 vs O4 (same model, same data, quantized sites the only
      difference), compile excluded (:func:`_time_steps` warmup).
    * **int8 KV capacity** — pages the pool admits at the SAME HBM
      budget under bf16 vs int8 storage (scales included), plus
      tokens/sec of a real closed-loop generate on both engines.
      Backend-independent gates in main(): capacity ratio >= 1.5 and
      the int8-KV engine completes its load bitwise-greedy with zero
      AOT misses.
    * the committed **CONVERGENCE_QUANT.json** gate file (O4 tracks O2
      on the LM trajectory) — present and green, re-read here so the
      bench fails loudly if the artifact regresses or goes missing.
    """
    import jax.random as jrandom

    from apex_tpu import quant
    from apex_tpu.models import gpt_tiny
    from apex_tpu import serving

    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "tools"))
    import convergence_quant as cq

    out = {}

    # -- matmul probe: calibrated int8 vs the bf16 dot --------------------
    m, k, n = (8192, 4096, 4096) if on_tpu else (2048, 512, 512)
    key = jrandom.PRNGKey(0)
    x = (jrandom.normal(key, (m, k), jnp.float32)).astype(jnp.bfloat16)
    w = (jrandom.normal(jrandom.PRNGKey(1), (k, n), jnp.float32) * 0.05
         ).astype(jnp.bfloat16)
    x_scale = float(np.abs(np.asarray(x, np.float32)).max() / 127.0)

    bf16_mm = jax.jit(lambda a, b: jnp.dot(a, b))
    q_mm = jax.jit(functools.partial(quant.quantized_matmul,
                                     x_scale=x_scale))

    def _mm_ms(fn):
        jax.block_until_ready(fn(x, w))            # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                r = fn(x, w)
            jax.block_until_ready(r)  # jaxlint: disable=J001 -- timing fence: the probe must block until the last matmul completes
            best = min(best, (time.perf_counter() - t0) / 10)
        return best * 1e3

    t_bf16, t_q = _mm_ms(bf16_mm), _mm_ms(q_mm)
    out["matmul"] = {
        "shape": [m, k, n],
        "bf16_ms": round(t_bf16, 3),
        "o4_ms": round(t_q, 3),
        "o4_over_bf16": round(t_q / t_bf16, 3) if t_bf16 > 0 else None,
    }

    # -- LM step probe: O2 vs O4 ms/step on the convergence model ---------
    steps = 12 if on_tpu else 6

    def _lm_ms(opt_level):
        from apex_tpu import training
        from apex_tpu.training import make_train_step
        batches = cq.make_lm_dataset(8, 8, 32, 64)
        params = cq.build_model(None, vocab=64).init(
            jrandom.PRNGKey(0), jnp.asarray(batches[0][:, :-1]))["params"]
        if opt_level == "O4":
            calib = cq.calibrate(params, batches, vocab=64)
            model = cq.build_model(quant.QuantConfig.frozen(calib),
                                   vocab=64)
        else:
            model = cq.build_model(None, vocab=64)

        def loss_fn(p, b):
            logits = model.apply({"params": p}, b[:, :-1])
            logp = jax.nn.log_softmax(
                logits.reshape(-1, 64).astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(
                logp, b[:, 1:].reshape(-1)[:, None], axis=1))

        init_fn, step_fn = make_train_step(loss_fn, training.adam(3e-3),
                                           opt_level=opt_level,
                                           loss_scale="dynamic")
        step = jax.jit(step_fn, donate_argnums=(0,))
        sec, _ = _time_steps(step, init_fn(params),
                             jnp.asarray(batches[0]), steps)
        return sec * 1e3

    out["lm_ms_per_step_o2"] = round(_lm_ms("O2"), 3)
    out["lm_ms_per_step_o4"] = round(_lm_ms("O4"), 3)

    # -- int8 KV: equal-HBM capacity + tokens/sec on a real load ----------
    model = gpt_tiny(max_len=128)
    page = 8
    budget = 64 * 1024 * 1024
    cap_bf16 = serving.kv_cache.pages_for_budget(model, page, budget,
                                                 jnp.bfloat16)
    cap_int8 = serving.kv_cache.pages_for_budget(model, page, budget,
                                                 jnp.int8)
    rs = np.random.RandomState(0)
    probe = jnp.asarray(rs.randint(1, 1024, (1, 8)))
    params = model.init(jrandom.PRNGKey(1), probe)["params"]
    prompts = [rs.randint(1, 1024, (int(ln),)).astype(np.int32)
               for ln in rs.randint(4, 24, 8)]

    def _tokens_per_s(cache_dtype):
        eng = serving.ServingEngine(model, params, buckets=(32,),
                                    page_size=page, max_seqs=4,
                                    cache_dtype=cache_dtype)
        try:
            eng.warmup()
            t0 = time.perf_counter()
            res = eng.generate(prompts, max_new_tokens=8)
            wall = time.perf_counter() - t0
            toks = [tuple(np.asarray(r.tokens).tolist()) for r in res]
            return {
                "tokens_per_s": round(
                    int(eng.stats["tokens_out"]) / wall, 2),
                "kv_bytes_per_token": eng.stats["kv_bytes_per_token"],
                "kv_cache_dtype": eng.kv_cache_dtype,
                "aot_misses": int(eng.stats["aot_misses"]),
            }, toks
        finally:
            eng.close()

    srv_ref, toks_ref = _tokens_per_s(None)
    srv_int8, toks_int8 = _tokens_per_s(jnp.int8)
    agree = sum(a == b for a, b in zip(toks_ref, toks_int8))
    out["kv"] = {
        "page_size": page,
        "budget_mb": budget // (1024 * 1024),
        "pages_bf16": cap_bf16,
        "pages_int8": cap_int8,
        "capacity_ratio": (round(cap_int8 / cap_bf16, 3)
                           if cap_bf16 else None),
        "serving_ref": srv_ref,
        "serving_int8": srv_int8,
        "token_agreement": f"{agree}/{len(prompts)}",
        "int8_aot_misses": srv_int8["aot_misses"],
    }

    # -- the committed convergence gate file ------------------------------
    art_path = os.path.join(root, "CONVERGENCE_QUANT.json")
    try:
        with open(art_path) as f:
            art = json.load(f)
        v = art.get("verdict", {})
        out["convergence"] = {
            "file": "CONVERGENCE_QUANT.json", "ok": bool(v.get("ok")),
            "rel_tail_gap": v.get("rel_tail_gap"),
            "track_tol": v.get("track_tol"),
            "steps": art.get("config", {}).get("steps"),
        }
    except (OSError, ValueError) as e:
        out["convergence"] = {"file": "CONVERGENCE_QUANT.json",
                              "ok": False,
                              "error": f"{type(e).__name__}: {e}"}
    return out


def _bench_tune(on_tpu, ledger=None):
    """ISSUE 14 self-validation: the kernel autotuner end to end.

    For every registered kernel (flash_attention fwd+bwd,
    fused_layer_norm, bn_relu_residual, xentropy, quantized_matmul,
    conv2d fwd+bwd):
    search the config space on this backend (real device timing on
    chip; interpreter-mode probe on CPU so the whole machinery still
    runs in CI), candidate priority driven by the freshest resnet
    roofline ``ledger`` when one was harvested this run.  Recorded per
    kernel: the winning config, default-vs-tuned ms, and
    ``tuned_over_default`` — gated <= 1.0 in main() on EVERY kernel
    (the fallback guarantee: the default config is always a candidate,
    so tuning can only ever match or beat it).  The persisted cache is
    then re-read from disk with the in-memory memo dropped (the
    process-restart probe) and every kernel's lookup must hit.
    """
    import tempfile

    from apex_tpu.tune import measure, registry, store

    registry.load_builtin()
    cache_dir = tempfile.mkdtemp(prefix="apex_tpu_bench_tune_")
    cache_path = os.path.join(cache_dir, "tune_configs.json")
    out = {"kernels": {}, "cache_path": cache_path,
           "device_kind": store.device_kind(),
           "ledger_driven": ledger is not None}
    iters, reps = (5, 3) if on_tpu else (1, 1)
    lookups = []
    for spec in registry.all_specs():
        bound = (measure.bound_from_ledger(ledger, spec)
                 if ledger else None)
        res = measure.tune_kernel(spec, bound=bound,
                                  interpret=not on_tpu,
                                  iters=iters, reps=reps,
                                  path=cache_path)
        out["kernels"][spec.name] = {
            "bucket": res.bucket,
            "bound": res.bound,
            "config": res.config,
            "default_config": res.default_config,
            "default_ms": res.default_ms,
            "tuned_ms": res.best_ms,
            "tuned_over_default": res.tuned_over_default,
            "candidates": res.candidates,
            "rejected_constraint": res.rejected_constraint,
            "rejected_oracle": res.rejected_oracle,
            "truncated": res.truncated,
            "source": res.source,
        }
        lookups.append((spec.name, spec.version, res.bucket))
    # restart-survival probe: only the persisted file may answer
    store.load(cache_path, reload=True)
    out["persisted_ok"] = all(
        store.lookup(name, ver, bucket, path=cache_path) is not None
        for name, ver, bucket in lookups)
    out["max_tuned_over_default"] = max(
        (k["tuned_over_default"] for k in out["kernels"].values()
         if k["tuned_over_default"] is not None), default=None)
    return out


def _bench_examples(on_tpu):
    """Execute the flagship example entry points and distill their own
    printed metrics.  Gates: the run completed, every printed loss is
    finite, and the steady-state throughput is nonzero."""
    out = {}

    # examples/imagenet — the north-star "runs unmodified" claim
    # (reference examples/imagenet/main_amp.py), O2 + dynamic scaling.
    # steps-per-call 16: the device-loop shape (training.chain_steps),
    # amortizing the per-call dispatch cost over 16 steps; print-freq 32:
    # each print is a full pipeline drain, so per-step printing measures
    # the host round-trip, not training.  prof 80 = 5 calls of 16; print
    # cadence 32/16 = every
    # 2nd call, so the LAST call (ci=4) prints and the speed line covers
    # all 80 iters.
    args = (["--synthetic", "-a", "resnet50", "-b", "128", "--opt-level",
             "O2", "--loss-scale", "dynamic", "--prof", "80",
             "--print-freq", "32", "--steps-per-call", "16"] if on_tpu else
            ["--synthetic", "-a", "resnet18", "-b", "8", "--image-size",
             "64", "--opt-level", "O2", "--prof", "5", "--print-freq", "1"])
    # ISSUE 5: record the run's telemetry stream alongside — the offline
    # analyzer's stall/gap attribution is cross-checked against the
    # numbers the example prints (parsed below) in main().
    tel_path = os.path.join(
        __import__("tempfile").gettempdir(),
        f"apex_tpu_bench_imagenet_{os.getpid()}.jsonl")
    args = args + ["--telemetry", tel_path]
    stdout, wall = _run_example("examples/imagenet/main_amp.py", args)
    iters = [(int(i), float(l), float(s))
             for i, l, s in _ITER_RE.findall(stdout)]
    if not iters or "done" not in stdout:
        raise SystemExit(
            f"BENCH EXAMPLE FAILED: imagenet printed no iteration lines\n"
            f"{stdout[-2000:]}")
    losses = [l for _, l, _ in iters]
    if not all(np.isfinite(losses)):
        raise SystemExit(f"BENCH EXAMPLE FAILED: imagenet non-finite loss "
                         f"trajectory {losses}")
    steady = _STEADY_RE.search(stdout)
    bestwin = _BESTWIN_RE.search(stdout)
    out["imagenet_main_amp"] = {
        "argv": " ".join(args),
        "iters_run": iters[-1][0] + 1,
        "first_loss": losses[0], "last_loss": losses[-1],
        # averaged from loop start, i.e. includes the jit compile:
        "img_per_sec_incl_compile": iters[-1][2],
        # post-compile rate the example prints itself (excl 2 warmup
        # iters).  Still includes the example's per-print host syncs
        # (each a pipeline drain) — the device-resident step time is
        # resnet50.ms_per_step_o2 above.
        "img_per_sec_steady": float(steady.group(1)) if steady else None,
        # best of 3 post-loop windows (2 calls each) — the min-of-reps
        # policy applied to the example run: robust to a host stall
        # inside a single steady window.
        "img_per_sec_best_window": (float(bestwin.group(1))
                                    if bestwin else None),
        # steady-vs-best-window gap, regression-gated in main() next to
        # the MFU sanity check (ISSUE 2 acceptance: <= 10% on chip).
        "window_gap_pct": _window_gap_pct(
            float(steady.group(1)) if steady else None,
            float(bestwin.group(1)) if bestwin else None),
        # The ISSUE-7 ratio-floor view of the same number (gated in
        # main(): >= _STEADY_OVER_BEST_FLOORS["imagenet"]).
        "steady_over_best_window": (
            round(float(steady.group(1)) / float(bestwin.group(1)), 3)
            if steady and bestwin and float(bestwin.group(1)) else None),
        # Input-engine attribution (ISSUE 3): % of the loop's wall time
        # spent waiting on the loader (0.0 for the pre-staged synthetic
        # pool; real-data runs report PrefetchLoader's measured stall).
        "loader_stall_pct": (float(m.group(1)) if
                             (m := _LOADER_RE.search(stdout)) else None),
        "wall_s": round(wall, 1),
    }
    # Offline analysis of the stream the example just emitted (ISSUE 5):
    # step count, step-time percentiles, and the stall/gap attribution
    # main() validates against the example's own printed numbers.
    try:
        from apex_tpu.prof import timeline
        ta = timeline.analyze(timeline.load_events(tel_path))
        out["imagenet_main_amp"]["telemetry"] = {
            "stream": tel_path,
            "events": ta["n_events"],
            "steps": ta["steps"],
            "step_p50_ms": (ta.get("step_time") or {}).get("p50_ms"),
            "step_p99_ms": (ta.get("step_time") or {}).get("p99_ms"),
            "loader_stall_pct": (ta.get("attribution")
                                 or {}).get("loader_stall_pct"),
            "dispatch_gap_pct": (ta.get("attribution")
                                 or {}).get("dispatch_gap_pct"),
            "retraces": ta["retraces"]["retraces"],
        }
    except Exception as e:            # analysis must never mask the run
        out["imagenet_main_amp"]["telemetry"] = {
            "error": f"{type(e).__name__}: {e}"}

    # examples/dcgan — the three-scaler multi-loss path (BASELINE config
    # 5), now step-pipelined by default (ISSUE 2): the whole iteration —
    # both D backwards, the G phase, and all three dynamic loss-scale
    # machines — is ONE program, chained --steps-per-call iterations per
    # dispatch through runtime.StepPipeline.  The reference-parity
    # imperative surface (amp.initialize num_losses=3 + scale_loss
    # loss_id + FusedAdam.step) remains under --imperative: 10 program
    # dispatches per iteration, which is the cost the pipelined default
    # removes.
    # 64 iters = 8 calls of 8: the steady clock starts after the 2
    # compile calls and covers 48 iters; print-freq 16 = every 2nd call.
    args = (["--niter", "1", "--iters-per-epoch", "64", "--opt_level", "O1",
             "--print-freq", "16", "--steps-per-call", "8"]
            if on_tpu else
            ["--niter", "1", "--iters-per-epoch", "3", "--batchSize", "4",
             "--opt_level", "O1", "--steps-per-call", "2"])
    stdout, wall = _run_example("examples/dcgan/main_amp.py", args)
    pairs = [(float(d), float(g)) for d, g in _DCGAN_RE.findall(stdout)]
    done = _DONE_RE.search(stdout)
    steady = _DCGAN_STEADY_RE.search(stdout)
    if not pairs or not done:
        raise SystemExit(
            f"BENCH EXAMPLE FAILED: dcgan printed no loss/done lines\n"
            f"{stdout[-2000:]}")
    flat = [v for p in pairs for v in p]
    if not all(np.isfinite(flat)):
        raise SystemExit(f"BENCH EXAMPLE FAILED: dcgan non-finite losses")
    best = _DCGAN_BEST_RE.search(stdout)
    # Renamed from dcgan_main_amp_imperative_3scaler: the three-scaler
    # example now runs step-pipelined by default; "mode" records which
    # path produced the numbers.
    out["dcgan_main_amp_3scaler"] = {
        "argv": " ".join(args),
        "mode": ("imperative" if "--imperative" in args else "pipelined"),
        "it_per_sec_incl_compile": float(done.group(2)),
        # min-of-reps policy applied to the loop: the rate it
        # demonstrably achieves (a single window can eat a host stall)
        "it_per_sec_best_window": (float(best.group(1)) if best else None),
        # compile-excluded rate the example prints itself (VERDICT r3
        # next #6); the fused single-program joint-loss step is benched
        # separately in dcgan_fused_joint_step_o2.
        "it_per_sec_steady": float(steady.group(1)) if steady else None,
        # steady-vs-best-window gap (ISSUE 2: this example's 12x gap hid
        # behind the steady number) — regression-gated in main().
        "window_gap_pct": _window_gap_pct(
            float(steady.group(1)) if steady else None,
            float(best.group(1)) if best else None),
        "steady_over_best_window": (
            round(float(steady.group(1)) / float(best.group(1)), 3)
            if steady and best and float(best.group(1)) else None),
        "loader_stall_pct": (float(m.group(1)) if
                             (m := _LOADER_RE.search(stdout)) else None),
        "last_loss_d": pairs[-1][0], "last_loss_g": pairs[-1][1],
        "wall_s": round(wall, 1),
    }
    return out


def _harvest_or_none(name, step_fn, args, on_tpu):
    """Trace-time roofline cost harvest of one workload's step
    (ISSUE 6) — never fails the bench.  XLA's cost analysis (a lowering)
    only on chip; the jaxpr walk (regions + matmul split) runs
    everywhere."""
    from apex_tpu.prof import roofline

    try:
        return roofline.harvest_costs(step_fn, *args, xla=on_tpu)
    except Exception as e:                           # pragma: no cover
        print(f"{name} roofline harvest failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return None


# Harvested-vs-analytic FLOPs cross-check (ISSUE 6): the jaxpr-walk
# matmul count and the hand-derived formula must agree within 10% or
# one of them is wrong (the gate that keeps the MFU numerator honest
# while the harvested path replaces the hand-coded one).
_HARVEST_XCHECK_TOL = 0.10


def _roofline_entry(harvest, step_time_s, peaks, top=5, memory=None):
    """One workload's MFU ledger for BENCH_EXTRA (top regions by
    modeled device time, MFU, boundedness, and — ISSUE 10 — the
    peak-HBM column when a memory harvest is supplied); never fails
    the bench."""
    if harvest is None:
        return None
    from apex_tpu.prof import roofline

    try:
        return roofline.mfu_ledger(harvest, step_time_s=step_time_s,
                                   peaks=peaks, top=top, memory=memory)
    except Exception as e:                           # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _memory_or_none(name, step_fn, args):
    """Trace/AOT-compile memory harvest of one workload's step
    (ISSUE 10) — never fails the bench and never touches the step's
    own jit cache (harvest_memory compiles its OWN jit instance;
    nothing runs, nothing is donated)."""
    from apex_tpu.prof import memory as memory_mod

    try:
        return memory_mod.harvest_memory(step_fn, *args)
    except Exception as e:                           # pragma: no cover
        print(f"{name} memory harvest failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return None


def _load_prev_bench():
    """Previous round's full bench data (``BENCH_EXTRA.json`` committed at
    the end of the prior round) for the regression guard (VERDICT r3 next
    #4): every headline timing gets a ``vs_prev`` ratio, and ratios > 1.05
    are flagged loudly in the summary instead of sliding silently."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_PREV.json")
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return None


def _vs_prev(cur_ms, prev_ms):
    if not prev_ms:
        return None
    return round(cur_ms / prev_ms, 3)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip and runs on a TPU only; JAX found "
            f"platform={dev.platform!r} (device_kind={dev.device_kind!r}). "
            f"CPU checks live in tests/ (see .claude/skills/verify).")
    # Flags-free instrumentation (ISSUE 10 satellite): APEX_TPU_TELEMETRY
    # (+ APEX_TPU_WATCHDOG / APEX_TPU_METRICS_*) records this whole
    # bench run's stream without any new CLI surface; close() is
    # idempotent and atexit-safe across the gate SystemExits.
    from apex_tpu import telemetry as _tel
    rec_env = _tel.start_from_env(example="bench")
    if rec_env is not None:
        import atexit
        atexit.register(rec_env.close)
    on_tpu = jax.default_backend() == "tpu"
    peak = _chip_peak_flops()
    device_kind = jax.devices()[0].device_kind

    batch = 128 if on_tpu else 8
    size = 224 if on_tpu else 32
    iters = 20 if on_tpu else 3

    # Calibrate BEFORE the workloads; repeated after, so every gate uses
    # the max the chip demonstrably reached during THIS bench run and the
    # JSON reports the spread (VERDICT r2 next #3).
    cal_before = _calibrate_peak() if on_tpu else []

    step2, state2, data2, step_fn2 = _make_resnet_step("O2", batch, size)
    # Copy the state BEFORE the donated jitted-per-step timing consumes
    # it; the copies seed the device-loop and pipeline timings below.
    state_dl = jax.tree_util.tree_map(jnp.copy, state2)
    state_pl = jax.tree_util.tree_map(jnp.copy, state2)
    # Roofline cost harvest (ISSUE 6): trace-time FLOP/byte totals +
    # per-region attribution of the SAME step, harvested BEFORE the
    # donated timing consumes the state (pure tracing — nothing runs,
    # nothing is donated).  Joined with the measured step times into
    # per-workload MFU ledgers at the bottom of main().
    harvest_resnet = _harvest_or_none("resnet50", step_fn2,
                                     (state2, data2), on_tpu)
    # HBM ledger of the SAME step (ISSUE 10) — also before the donated
    # timing consumes the state (pure trace + AOT compile analysis).
    mem_resnet = _memory_or_none("resnet50", step_fn2, (state2, data2))
    t_o2, state2 = _time_steps(step2, state2, data2, iters)
    prof_resnet, tp_resnet = (_prof_top_ops(step2, state2, data2)
                              if on_tpu else (None, None))
    # Bytes ledger (VERDICT r4 next #1): measured fusion traffic from the
    # trace just captured vs the model-intrinsic traffic of the SAME step
    # (conv/dot operands+outputs at their dtypes + optimizer-side bytes)
    # — the number that says whether "roofline-bound" is the model's
    # fault or the schedule's.
    ledger_resnet = None
    if tp_resnet is not None:
        try:
            from apex_tpu.prof.ledger import bytes_ledger
            n_par = int(sum(np.prod(l.shape) for l in
                            jax.tree_util.tree_leaves(state2.params)))
            ledger_resnet = bytes_ledger(
                step_fn2, (state2, data2), tp_resnet,
                steps=_PROF_TRACE_STEPS, n_params=n_par, optimizer="sgd")
            # keep the JSON small: top-10 intrinsic layers only
            ledger_resnet["intrinsic"]["by_layer"] = (
                ledger_resnet["intrinsic"]["by_layer"][:10])
        except Exception as e:           # never fail the bench on prof
            ledger_resnet = {"error": f"{type(e).__name__}: {e}"}
    # k=32 (r5 sweep: 50.48 / 48.80 / 47.98 ms/step at k=8/16/32 vs
    # 46.87 traced device — deeper chaining amortizes the ~16 ms/call
    # dispatch tax to <1 ms/step; real TPU loops chain hundreds).
    t_o2_dl = (_time_steps_device_loop(step_fn2, state_dl, data2)
               if on_tpu else t_o2)
    # The user-facing wall rate through runtime.StepPipeline — the
    # ISSUE-2 acceptance pins it within 5% of the device-loop rate
    # (the dispatch gap the step-pipelining runtime exists to close).
    t_o2_pipe = (_time_steps_pipeline(step_fn2, state_pl, data2)
                 if on_tpu else t_o2)
    del step2, state2, data2, state_dl, state_pl
    # O2 precision machinery measured in isolation on the same param tree
    # (cast + unscale/overflow + masked SGD update as ONE program): the
    # honest numerator for "plumbing share of step" — the full-step trace
    # can't attribute it because XLA fuses the update into wgrad convs.
    plumbing_ms = _measure_precision_plumbing() if on_tpu else None
    step0, state0, data0, step_fn0 = _make_resnet_step("O0", batch, size)
    state0_dl = jax.tree_util.tree_map(jnp.copy, state0)
    t_o0, _ = _time_steps(step0, state0, data0, iters)
    t_o0_dl = (_time_steps_device_loop(step_fn0, state0_dl, data0)
               if on_tpu else t_o0)
    del step0, state0, data0, state0_dl

    # Headline img/s, MFU and the O2-vs-O0 ratio all use the device-loop
    # rate (the deployment shape of a TPU training loop) for BOTH opt
    # levels — same harness on both sides; the jitted-per-step wall
    # numbers are reported beside them and carry the cross-round
    # regression guard.
    ips_o2, ips_o0 = batch / t_o2_dl, batch / t_o0_dl
    flops = _resnet_flops_per_step(batch, size)
    implied_o2, implied_o0 = flops / t_o2_dl, flops / t_o0_dl

    # BERT-base FusedAdam O2 — Pallas FusedLayerNorm + xentropy + flash
    # attention on chip.
    b_batch, b_seq = (16, 128) if on_tpu else (2, 32)
    (bstep, bstate, bdata, n_params, n_dense,
     hidden, vocab, bstep_fn) = _make_bert_step(b_batch, b_seq)
    bstate_dl = jax.tree_util.tree_map(jnp.copy, bstate)
    # Harvested BEFORE the donated timing consumes bstate.  The
    # harvest's matmul_flops replaces the hand-coded
    # _bert_flops_per_step estimate as the MFU numerator below
    # (ISSUE 6 satellite); the analytic formula stays as a cross-check
    # gated to 10% agreement.
    harvest_bert = _harvest_or_none("bert", bstep_fn, (bstate, bdata),
                                    on_tpu)
    mem_bert = _memory_or_none("bert", bstep_fn, (bstate, bdata))
    t_bert, bstate = _time_steps(bstep, bstate, bdata, max(iters // 2, 2))
    prof_bert, _tp_b = (_prof_top_ops(bstep, bstate, bdata)
                       if on_tpu else (None, None))
    # Bytes ledger for BERT (r5): the mfu_vs_measured gap is bounded by
    # the NON-matmul intrinsic traffic (Adam state sweep, embedding
    # gathers, LN/residual streams) — same evidence the ResNet-50 ledger
    # gives for "roofline vs schedule".
    ledger_bert = None
    if _tp_b is not None:
        try:
            from apex_tpu.prof.ledger import bytes_ledger
            ledger_bert = bytes_ledger(
                bstep_fn, (bstate, bdata), _tp_b,
                steps=_PROF_TRACE_STEPS, n_params=n_params,
                optimizer="adam")
            ledger_bert["intrinsic"]["by_layer"] = (
                ledger_bert["intrinsic"]["by_layer"][:10])
        except Exception as e:           # never fail the bench on prof
            ledger_bert = {"error": f"{type(e).__name__}: {e}"}
    t_bert_dl = (_time_steps_device_loop(bstep_fn, bstate_dl, bdata)
                 if on_tpu else t_bert)
    del bstep, bstate, bdata, bstate_dl
    # BERT FLOPs/step: the harvested cost analysis is the numerator
    # (ISSUE 6); the hand-derived formula survives as a cross-check —
    # a >10% disagreement means either the harvest walk or the formula
    # drifted, and the bench refuses to report an MFU built on it.
    bert_flops_analytic = _bert_flops_per_step(n_dense, b_batch, b_seq,
                                               hidden, vocab, 12)
    bert_flops, bert_flops_source = bert_flops_analytic, "analytic"
    harvest_vs_analytic = None
    if harvest_bert is not None and harvest_bert.matmul_flops:
        harvest_vs_analytic = (harvest_bert.matmul_flops
                               / bert_flops_analytic)
        if abs(harvest_vs_analytic - 1.0) > _HARVEST_XCHECK_TOL:
            raise SystemExit(
                f"BENCH SELF-CHECK FAILED: harvested BERT matmul FLOPs "
                f"({harvest_bert.matmul_flops:.3e}, source "
                f"{harvest_bert.source}) disagree with the analytic "
                f"formula ({bert_flops_analytic:.3e}) by "
                f"{abs(harvest_vs_analytic - 1.0) * 100:.1f}% "
                f"(> {_HARVEST_XCHECK_TOL * 100:.0f}% gate) — the MFU "
                f"numerator is not trustworthy; refusing to report.")
        bert_flops = harvest_bert.matmul_flops
        bert_flops_source = f"harvested_{harvest_bert.source}"
    bert_implied = bert_flops / t_bert_dl
    from apex_tpu.normalization.fused_layer_norm import _dispatch_pallas
    from apex_tpu.ops.flash_attention import _KERNEL_MIN_KV
    # Report the kernels the step ACTUALLY dispatches to at this shape:
    # LN routes to jnp below its in-context crossover (r5), like
    # attention below _KERNEL_MIN_KV.  Ask the dispatch itself so the
    # report can't drift from the rule — including the itemsize the gate
    # now keys on: the O2 step feeds LN bf16 activations (itemsize 2).
    bert_kernels = (["xentropy"]
                    + (["fused_layer_norm"]
                       if _dispatch_pallas(b_batch * b_seq, hidden, None,
                                           itemsize=2)
                       else [])
                    + (["flash_attention"] if b_seq >= _KERNEL_MIN_KV
                       else []))

    # Long-context flash attention (beyond-parity): causal fwd+bwd at 8k.
    fa_seq = 8192 if on_tpu else 512
    t_flash, t_block = _bench_flash_attention(fa_seq)

    # FusedAdam whole-model step vs eager per-tensor loop (+ the ISSUE-4
    # bucketed flat-buffer path on the same tree).
    adam_res = _adam_fused_vs_eager(max(iters // 2, 2))
    t_fused = adam_res["fused_s"]
    t_eager = adam_res["eager_s"]
    n_tensors = adam_res["n_tensors"]
    t_adam_dev_ms = adam_res["device_ms"]
    t_adam_chained = adam_res["chained_s"]
    t_adam_bucketed = adam_res["bucketed_s"]
    t_adam_bucketed_dev_ms = adam_res["bucketed_device_ms"]

    # Deep-pytree (>=200-leaf) FusedAdam: leafwise vs bucketed wall +
    # first-compile (ISSUE 4 satellite).
    adam_deep = _adam_deep_pytree(max(iters // 2, 2))

    # DCGAN, both BASELINE-config-5 flavors: the fused single-program O2
    # joint-loss step here; the REAL imperative 3-scaler O1 path is timed
    # through the example run below.
    dstep, dstate, ddata = _make_dcgan_step(batch=64 if on_tpu else 4)
    harvest_dcgan = _harvest_or_none("dcgan", dstep, (dstate, ddata),
                                     on_tpu)
    mem_dcgan = _memory_or_none("dcgan", dstep, (dstate, ddata))
    t_dcgan, _ = _time_steps(dstep, dstate, ddata, max(iters // 2, 2))
    del dstep, dstate, ddata

    # Calibrate AFTER all timed workloads; the gate ceiling is the max the
    # chip demonstrably reached during THIS run and the JSON reports every
    # pass, so the chip's throughput noise is visible (VERDICT r2 next #3).
    cal_after = _calibrate_peak() if on_tpu else []
    cals = cal_before + cal_after
    # max = the sanity-gate ceiling (nothing real may beat the chip's best
    # demonstrated rate); MEDIAN = the MFU denominator (VERDICT r4 weak
    # #3: dividing by the max made MFU wobble with one lucky pass).
    measured_peak = max(cals) if cals else None
    measured_med = float(np.median(cals)) if cals else None

    if measured_peak and measured_peak >= peak:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: calibration measured "
            f"{measured_peak/1e12:.1f} TFLOP/s >= nameplate "
            f"{peak/1e12:.0f} TFLOP/s — the chain was optimized away; "
            f"its rates (and the gates built on them) are meaningless.")
    if on_tpu:
        _gate_implied("ResNet-50 O2", implied_o2, peak, measured_peak)
        _gate_implied("ResNet-50 O0", implied_o0, peak, measured_peak)
        _gate_implied("BERT-base O2", bert_implied, peak, measured_peak)

    extra = {
        "backend": jax.default_backend(),
        "device_kind": device_kind,
        "timing_policy": _TIMING_POLICY,
        "peak_bf16_tflops": round(peak / 1e12, 1),
        # Achievable wall-clock bf16 matmul rate measured on THIS chip
        # during THIS run (serial 8k chain, see _calibrate_peak): the
        # achievable-rate MFU denominator beside the published peak.
        # MEDIAN of the
        # passes; the [min, max] band is the run-to-run truth and every
        # MFU claim downstream carries it (VERDICT r4 weak #3).
        "measured_matmul_tflops": (round(measured_med / 1e12, 1)
                                   if measured_med else None),
        "measured_matmul_tflops_band": (
            [round(min(cals) / 1e12, 1), round(max(cals) / 1e12, 1)]
            if cals else None),
        "measured_matmul_tflops_spread_pct": (
            round(100 * (max(cals) - min(cals)) / measured_med, 1)
            if cals else None),
        "measured_matmul_tflops_passes": [round(c / 1e12, 1) for c in cals],
        "gate_ceiling_tflops": (round(measured_peak / 1e12, 1)
                                if measured_peak else None),
        "gate_tolerance": _GATE_TOL,
        "resnet50": {
            "batch": batch, "image_size": size, "iters": iters,
            "ms_per_step_o2": round(t_o2 * 1e3, 2),
            # K=8 steps per program (apex_tpu.training.chain_steps): the
            # deployment-shape rate the headline img/s and MFU use.
            "ms_per_step_o2_device_loop": round(t_o2_dl * 1e3, 2),
            # Wall rate of the USER-FACING path (runtime.StepPipeline,
            # K steps/dispatch, deferred metric reads) — the gap between
            # this and the device-loop number is the dispatch tax the
            # step-pipelining runtime leaves on the table.
            "ms_per_step_o2_pipeline_wall": round(t_o2_pipe * 1e3, 2),
            "ms_per_step_o0": round(t_o0 * 1e3, 2),
            "ms_per_step_o0_device_loop": round(t_o0_dl * 1e3, 2),
            "images_per_sec_o2": round(ips_o2, 2),
            "images_per_sec_o0": round(ips_o0, 2),
            "mfu_o2_pct": round(100 * implied_o2 / peak, 1),
            "mfu_o0_pct": round(100 * implied_o0 / peak, 1),
            "mfu_o2_vs_measured_pct": (
                round(100 * implied_o2 / measured_med, 1)
                if measured_med else None),
            # prof dogfood: measured per-op device time for this exact
            # step, via prof.capture.trace + prof.parse.parse_trace.
            "prof_measured": prof_resnet,
            # measured vs intrinsic HBM traffic (prof.ledger)
            "bytes_ledger": ledger_resnet,
            # O2 cast + unscale + masked-SGD update measured as their own
            # on-device program over the same tree (see
            # _measure_precision_plumbing): what the precision machinery
            # actually costs, free of fusion attribution.
            "precision_plumbing_ms": plumbing_ms,
            "precision_plumbing_pct_of_step": (
                round(100 * plumbing_ms / (t_o2 * 1e3), 1)
                if plumbing_ms else None),
        },
        "bert_base_fusedadam": {
            "batch": b_batch, "seq": b_seq, "n_params": n_params,
            "n_dense_params": n_dense,
            "ms_per_step": round(t_bert * 1e3, 2),
            "ms_per_step_device_loop": round(t_bert_dl * 1e3, 2),
            "mfu_pct": round(100 * bert_implied / peak, 1),
            "mfu_vs_measured_pct": (
                round(100 * bert_implied / measured_med, 1)
                if measured_med else None),
            # dispatch-aware (r5): below the measured crossover the
            # attention_impl="flash" surface routes to jnp, so the Pallas
            # attention kernel genuinely does not run in this step.
            "pallas_kernels": (bert_kernels if on_tpu else []),
            "prof_measured": prof_bert,
            "bytes_ledger": ledger_bert,
            # Additive no-overlap decomposition of the step (see
            # _bert_mfu_bound): matmul FLOPs at the measured-median
            # rate + the intrinsic Adam state sweep (30 B/param) at the
            # trace's loop-fusion bandwidth.  Explains where the
            # distance to 100% mfu_vs_measured physically goes; not a
            # ceiling (the schedule overlaps part of the sweep).  Now
            # driven by the HARVESTED FLOPs (ISSUE 6).
            "mfu_additive_model": _bert_mfu_bound(
                ledger_bert, bert_flops, measured_med, prof_bert),
            # FLOPs provenance (ISSUE 6): harvested cost analysis is
            # the MFU numerator; the hand formula is the cross-check
            # (gated to 10% agreement in the self-validation above).
            "flops_source": bert_flops_source,
            "flops_g": round(bert_flops / 1e9, 2),
            "flops_g_analytic": round(bert_flops_analytic / 1e9, 2),
            "harvest_vs_analytic": (round(harvest_vs_analytic, 4)
                                    if harvest_vs_analytic else None),
        },
        "flash_attention_causal": {
            "seq": fa_seq, "heads": 12, "head_dim": 64,
            "flash_ms": round(t_flash * 1e3, 2),
            "blockwise_jnp_ms": round(t_block * 1e3, 2),
            "speedup": round(t_block / t_flash, 2),
        },
        "fused_adam_step": {
            "n_tensors": n_tensors,
            "fused_ms": round(t_fused * 1e3, 3),
            # device time of ONE fused update traced as its own program —
            # the kernel, not the host dispatch (the wall number above
            # pays per-argument dispatch for ≈790 leaves):
            "fused_device_ms": t_adam_dev_ms,
            # K=16 updates chained in one program: the amortized wall
            # rate a real train loop sees for the optimizer stage.
            "fused_chained_ms_per_step": round(t_adam_chained * 1e3, 3),
            # ISSUE 4: the flat-bucket path — masters/state/grads cross
            # the jit boundary as a few large per-dtype buffers, so the
            # per-leaf marshalling tax is gone by construction.
            "bucketed_ms": round(t_adam_bucketed * 1e3, 3),
            "bucketed_device_ms": t_adam_bucketed_dev_ms,
            "n_buckets": adam_res["n_buckets"],
            # wall_over_device now tracks the BUCKETED hot path (gated in
            # self-validation, <= _ADAM_WOD_GATE); the leafwise ratio —
            # r05 measured 16.9 wall vs 4.8 device (3.5x) — stays
            # reported for the before/after story.
            "wall_over_device": (
                round(t_adam_bucketed * 1e3 / t_adam_bucketed_dev_ms, 2)
                if t_adam_bucketed_dev_ms else None),
            "wall_over_device_leafwise": (
                round(t_fused * 1e3 / t_adam_dev_ms, 2)
                if t_adam_dev_ms else None),
            "eager_per_tensor_ms": round(t_eager * 1e3, 3),
            "speedup_vs_eager": round(t_eager / t_fused, 2),
        },
        # ISSUE 4 satellite: the >=200-leaf deep-pytree variant, where
        # the O(leaves) wall/compile floors are the whole story.
        "fused_adam_deep": adam_deep,
        # Renamed from "dcgan_two_loss": this is the fused single-program
        # joint-loss step, not the multi-scaler imperative path.
        "dcgan_fused_joint_step_o2": {
            "ms_per_step": round(t_dcgan * 1e3, 2)},
    }

    # Per-workload roofline / MFU ledgers (ISSUE 6): harvested costs
    # joined with the measured step times against THIS run's measured
    # matmul peak — top-5 regions by modeled device time, achieved
    # FLOP/s, and compute-vs-memory boundedness per region.
    peaks = {"flops": (measured_med or peak),
             "hbm_gb_s": _roofline_peaks.device_peaks()["hbm_gb_s"],
             "source": ("measured_matmul_median" if measured_med
                        else "published_bf16"),
             "bw_source": "published_hbm"}
    extra["resnet50"]["roofline"] = _roofline_entry(
        harvest_resnet, t_o2_dl, peaks, memory=mem_resnet)
    extra["bert_base_fusedadam"]["roofline"] = _roofline_entry(
        harvest_bert, t_bert_dl, peaks, memory=mem_bert)
    extra["dcgan_fused_joint_step_o2"]["roofline"] = _roofline_entry(
        harvest_dcgan, t_dcgan, peaks, memory=mem_dcgan)

    # Peak-HBM self-check (ISSUE 10 acceptance): every workload's ledger
    # must carry a NONZERO peak-HBM column, and the recorded (rounded/
    # json-ified) value must agree with the harvest's own bytes within
    # 10% — where memory_analysis() was available the column IS the
    # compiled accounting, so drift means broken plumbing, not noise.
    for wl_name, wl_key, wl_mem in (
            ("resnet50", "resnet50", mem_resnet),
            ("bert", "bert_base_fusedadam", mem_bert),
            ("dcgan", "dcgan_fused_joint_step_o2", mem_dcgan)):
        entry = extra[wl_key].get("roofline") or {}
        recorded = ((entry.get("total") or {}).get("peak_hbm_gb") or 0.0)
        if wl_mem is None:
            continue                     # harvest failure already printed
        if not recorded:
            raise SystemExit(
                f"BENCH SELF-CHECK FAILED: {wl_name} roofline ledger "
                f"carries no peak-HBM column despite a successful "
                f"memory harvest ({wl_mem.peak_bytes} bytes, source "
                f"{wl_mem.source}) — the mfu_ledger memory join is "
                f"broken; refusing to report.")
        if wl_mem.peak_bytes and abs(recorded * 1e9 / wl_mem.peak_bytes
                                     - 1.0) > 0.10:
            raise SystemExit(
                f"BENCH SELF-CHECK FAILED: {wl_name} ledger peak-HBM "
                f"{recorded} GB disagrees with the harvested "
                f"{wl_mem.peak_bytes / 1e9:.6f} GB "
                f"({wl_mem.source}) by more than 10%; refusing to "
                f"report.")
        extra[wl_key]["peak_hbm_gb"] = recorded
        extra[wl_key]["peak_hbm_source"] = wl_mem.source
        if wl_mem.source == "memory_analysis" and wl_mem.peak_bytes:
            # walk-vs-XLA ratio, reported not gated: the conservative
            # walk has no donation/remat, so >= ~1 is expected; << 1
            # would mean the walk under-counts.
            extra[wl_key]["hbm_walk_over_xla"] = round(
                wl_mem.walk_peak_bytes / wl_mem.peak_bytes, 3)

    # Flagship examples in this process on this same device: the real
    # entry points under examples/, unmodified.
    extra["examples"] = _bench_examples(on_tpu)

    # Run-telemetry self-validation (ISSUE 5), backend-independent: the
    # disabled path must be a bitwise no-op, instrumentation must cause
    # zero retraces, and the enabled stream must cost within the gate.
    extra["telemetry"] = tel = _bench_telemetry()
    if not tel["bitwise_identical_disabled"]:
        raise SystemExit(
            "BENCH SELF-CHECK FAILED: a telemetry-enabled run produced "
            "different parameters than the disabled run — the recorder "
            "perturbed numerics or dispatch; refusing to report.")
    if not tel["zero_retraces"] or not tel["analyzer_consistent"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: telemetry stream inconsistent "
            f"(zero_retraces={tel['zero_retraces']}, "
            f"analyzer_consistent={tel['analyzer_consistent']}, "
            f"steps={tel['analyzer_steps']}) — instrumentation changed "
            f"compile behavior or the analyzer miscounts; refusing to "
            f"report.")
    if tel["overhead_ratio"] and tel["overhead_ratio"] > _TEL_OVERHEAD_GATE:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: telemetry+watchdog-enabled step "
            f"time is {tel['overhead_ratio']}x the disabled rate "
            f"(> {_TEL_OVERHEAD_GATE}x gate) — the event stream or the "
            f"watchdog fold is back on the hot path (per-step events, a "
            f"stray sync, or an expensive rule); refusing to report.")
    if tel["watchdog_critical_alerts"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: the watchdog raised "
            f"{tel['watchdog_critical_alerts']} CRITICAL alert(s) on the "
            f"clean probe loop — a deterministic rule (nonfinite / "
            f"scale_collapse / retrace_storm) is crying wolf; refusing "
            f"to report.")
    if not tel["regress_self_diff_clean"] \
            or not tel["regress_detects_degradation"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: prof.regress self-check "
            f"(self_diff_clean={tel['regress_self_diff_clean']}, "
            f"detects_degradation={tel['regress_detects_degradation']}) "
            f"— the regression differ is either crying wolf on identical "
            f"summaries or blind to a 2x slowdown; refusing to report.")
    exp = tel.get("export") or {}
    if not exp.get("scrape_ok") or not exp.get("textfile_ok"):
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: live metrics export "
            f"(scrape_ok={exp.get('scrape_ok')}, "
            f"textfile_ok={exp.get('textfile_ok')}) — the Prometheus "
            f"endpoint or the atomic textfile did not serve the probe "
            f"loop's instruments; refusing to report.")

    # Fleet-merge self-validation (ISSUE 10): straggler attribution on
    # the synthetic 4-host fixture must name the injected slow host on
    # EVERY window, and the aligner must recover the injected skew.
    extra["fleet"] = flv = _bench_fleet()
    if not flv["straggler_every_window"] \
            or flv["straggler_host"] != 2 or not flv["clock_align_ok"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: prof.fleet attribution "
            f"(straggler_host={flv['straggler_host']}, "
            f"every_window={flv['straggler_every_window']}, "
            f"clock_align_ok={flv['clock_align_ok']}, "
            f"skews={flv['clock_skew_ms']}) — the merge cannot name an "
            f"unambiguous injected straggler or recover a known clock "
            f"skew; refusing to report.")
    # Attribution cross-check: the analyzer's loader stall (read from the
    # LoaderStats.as_dict snapshot in the stream) must agree with the
    # 'loader: stall X%' line the imagenet example printed.
    ex_im = extra["examples"].get("imagenet_main_amp") or {}
    tel_im = ex_im.get("telemetry") or {}
    if (ex_im.get("loader_stall_pct") is not None
            and tel_im.get("loader_stall_pct") is not None
            and abs(ex_im["loader_stall_pct"]
                    - tel_im["loader_stall_pct"]) > _TEL_STALL_TOL_PCT):
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: telemetry stall attribution "
            f"{tel_im['loader_stall_pct']}% disagrees with the example's "
            f"printed {ex_im['loader_stall_pct']}% by more than "
            f"{_TEL_STALL_TOL_PCT} points — the stream and "
            f"format_loader_line no longer share one snapshot; refusing "
            f"to report.")

    # Mesh-frontend self-validation (ISSUE 12), backend-independent:
    # ZeRO-3 must actually divide per-device state bytes by the shard
    # count, and the REAL 2-process multi-host fixture must pass.
    extra["mesh"] = mz = _bench_mesh()
    z3 = (mz.get("memory") or {}).get("zero3") or {}
    if z3.get("ratio") is None or z3["ratio"] > _MESH_Z3_RATIO_GATE:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: ZeRO-3 per-device state ratio "
            f"{z3.get('ratio')} (gate <= {_MESH_Z3_RATIO_GATE} on the "
            f"{_MESH_PROBE_DEVICES}-way probe mesh; "
            f"memory={mz.get('memory')}) — the sharded flat buckets are "
            f"not actually dividing param+optimizer-state memory; "
            f"refusing to report.")
    if not (mz.get("multihost") or {}).get("ok"):
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: the 2-process multi-host fixture "
            f"did not pass ({mz.get('multihost')}) — real cross-process "
            f"mesh parity, per-host checkpoint shards, or the fleet "
            f"merge of the two live streams is broken; refusing to "
            f"report.")

    # Async-checkpoint self-validation (ISSUE 9), backend-independent:
    # the engine's whole point is that the loop pays only the snapshot
    # trigger — if the async stall creeps toward the synchronous
    # write's, serialization is back on the loop thread.
    extra["checkpoint"] = ckpt_v = _bench_checkpoint()
    if not ckpt_v["restore_bitwise_ok"]:
        raise SystemExit(
            "BENCH SELF-CHECK FAILED: a checkpoint written during the "
            "stall probe did not restore bitwise against the live "
            "state — the async writer is publishing corrupt or stale "
            "snapshots; refusing to report.")
    if (ckpt_v["checkpoint_stall_ms_per_step_sync"]
            >= _CKPT_SYNC_FLOOR_MS
            and ckpt_v["async_over_sync"] is not None
            and ckpt_v["async_over_sync"] > _CKPT_ASYNC_OVER_SYNC_GATE):
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: async checkpoint stall is "
            f"{ckpt_v['async_over_sync']}x the synchronous write's "
            f"(> {_CKPT_ASYNC_OVER_SYNC_GATE}x gate; "
            f"async {ckpt_v['checkpoint_stall_ms_per_step_async']} vs "
            f"sync {ckpt_v['checkpoint_stall_ms_per_step_sync']} "
            f"ms/step) — serialize/fsync leaked back onto the train "
            f"loop; refusing to report.")

    # Serving-engine self-validation (ISSUE 11), backend-independent:
    # the closed-loop load generator's acceptance contracts — zero
    # compiles after warmup across all buckets, no failed requests, and
    # a mid-load hot-swap that really serves the new weights.
    extra["serving"] = srv = _bench_serving()
    if not srv["zero_compiles_after_warmup"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: serving paid "
            f"{srv['aot_misses']} compile(s) after warmup — an AOT "
            f"bucket key is drifting (signature/static-param mismatch) "
            f"or a dispatch fell off the warmed table; steady-state "
            f"serving must pay ZERO compiles; refusing to report.")
    if srv["failed_requests"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: {srv['failed_requests']} serving "
            f"request(s) failed under the closed-loop load (incl. the "
            f"mid-load hot-swap window) — the scheduler dropped or "
            f"errored requests; refusing to report.")
    if not srv["hotswap_ok"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: mid-load weight hot-swap "
            f"(hotswaps={srv['hotswaps']}) did not produce decode "
            f"output bitwise-matching the new checkpoint's "
            f"single-request output — the watcher staged stale/corrupt "
            f"weights or the swap never took; refusing to report.")
    if srv["kv_pages_leaked"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: {srv['kv_pages_leaked']} KV "
            f"page(s) still held after the load drained — the scheduler "
            f"leaks pages on eviction and a long-running server would "
            f"strand its whole pool; refusing to report.")
    # Request-tracing/SLO self-validation (ISSUE 20), backend-independent.
    trc = srv["tracing"]
    if not trc["tokens_bitwise_ok"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: enabling request tracing "
            f"(trace_sample_n=1 + SLO fold) changed the generated "
            f"tokens — observability steered the decode path; the "
            f"traced engine must be bitwise identical; refusing to "
            f"report.")
    if not trc["zero_spans_without_tracer"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: a telemetry run with NO tracer "
            f"attached emitted span events — the strict-no-op contract "
            f"of the disabled tracing path broke; refusing to report.")
    if trc["overhead_ratio"] and trc["overhead_ratio"] > _TEL_OVERHEAD_GATE:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: serving with full request "
            f"tracing ran {trc['overhead_ratio']}x the recorder-less "
            f"load (> {_TEL_OVERHEAD_GATE}x gate) — span emission "
            f"leaked onto the scheduler hot path; refusing to report.")
    if trc["analyzer_vs_engine_pct"] is None \
            or trc["analyzer_vs_engine_pct"] > 2.0:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: prof.requests re-derived "
            f"TTFT/TPOT {trc['analyzer_vs_engine_pct']}% away from the "
            f"engine's in-run reservoirs (gate 2%; analyzer ttft p99 "
            f"{trc['analyzer_ttft_p99_ms']} vs engine "
            f"{trc['engine_ttft_p99_ms']} ms) — the offline and online "
            f"percentile paths diverged; refusing to report.")
    if trc["goodput_pct"] is None:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: the SLO evaluation returned no "
            f"goodput for spec {trc['slo_spec']!r} — the done events "
            f"lost their latency fields or the offline evaluator "
            f"matched zero requests; refusing to report.")

    # int8 engine self-validation (ISSUE 13): equal-HBM KV capacity and
    # the committed convergence artifact are backend-independent gates;
    # the matmul speedup is a chip property (the CPU jnp fallback pays
    # quantize/dequant with no int8 MAC rate to buy it back) and gates
    # on TPU only.
    extra["quant"] = qnt = _bench_quant(on_tpu)
    if not qnt["convergence"]["ok"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: the CONVERGENCE_QUANT gate file "
            f"is missing or red ({qnt['convergence']}) — O4 no longer "
            f"tracks O2 on the LM trajectory (or the artifact was never "
            f"recorded); rerun tools/convergence_quant.py; refusing to "
            f"report.")
    if qnt["kv"]["capacity_ratio"] is None \
            or qnt["kv"]["capacity_ratio"] < 1.5:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: int8 KV storage admits only "
            f"{qnt['kv']['capacity_ratio']}x the pages bf16 does at the "
            f"same HBM budget (gate >= 1.5x) — the per-row scale "
            f"overhead outgrew the int8 saving or the byte accounting "
            f"broke; refusing to report.")
    if qnt["kv"]["int8_aot_misses"]:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: the int8-KV serving load paid "
            f"{qnt['kv']['int8_aot_misses']} compile(s) after warmup — "
            f"the QuantPool pytree is perturbing the AOT signature; "
            f"steady-state quantized serving must pay ZERO compiles; "
            f"refusing to report.")
    if on_tpu and qnt["matmul"]["o4_over_bf16"] is not None \
            and qnt["matmul"]["o4_over_bf16"] > 1.0:
        raise SystemExit(
            f"BENCH SELF-CHECK FAILED: the calibrated int8 matmul ran "
            f"{qnt['matmul']['o4_over_bf16']}x the bf16 dot on the "
            f"{qnt['matmul']['shape']} probe — the quantized kernel "
            f"must not be SLOWER than what it replaces on chip "
            f"(dequant epilogue unfused, or the dispatch gate routed a "
            f"probe-sized matmul to jnp); refusing to report.")

    # ISSUE 14: the kernel autotuner, ledger-driven by the resnet
    # roofline harvested above when present.
    extra["tune"] = tn = _bench_tune(
        on_tpu, ledger=(extra.get("resnet50") or {}).get("roofline"))
    for kname, krow in tn["kernels"].items():
        tod = krow.get("tuned_over_default")
        if tod is not None and tod > 1.0:
            raise SystemExit(
                f"BENCH SELF-CHECK FAILED: tuned {kname} config "
                f"{krow['config']} ran {tod}x the default "
                f"{krow['default_config']} — the default config is "
                f"always a candidate, so the tuner can never pick a "
                f"slower winner (fallback guarantee broken: the "
                f"measurement or the oracle gate regressed); refusing "
                f"to report.")
    if not tn["persisted_ok"]:
        raise SystemExit(
            "BENCH SELF-CHECK FAILED: tuned configs did not survive the "
            "process-restart probe (cache re-read from disk missed at "
            "least one (device kind, kernel, version, bucket) key) — "
            "the persistent tune cache is broken; refusing to report.")

    # Self-validation, same contract as the MFU gates above: a steady
    # rate far below the example's own best window means the hot loop is
    # stalling on dispatch/syncs again (the exact regression class the
    # step-pipelining runtime closed — DCGAN sat at 12x for five
    # rounds).  Target is <= _WINDOW_GAP_TARGET_PCT (ISSUE 2); the gate
    # fails at _WINDOW_GAP_GATE_PCT to absorb pass-to-pass noise while
    # still catching order-of-magnitude stalls.
    if on_tpu:
        for ex_key, label in (("imagenet_main_amp", "imagenet"),
                              ("dcgan_main_amp_3scaler", "dcgan")):
            exd = extra["examples"].get(ex_key) or {}
            gap = exd.get("window_gap_pct")
            if gap is not None and gap > _WINDOW_GAP_GATE_PCT:
                raise SystemExit(
                    f"BENCH SELF-CHECK FAILED: {label} example steady "
                    f"throughput trails its own best window by {gap}% "
                    f"(> {_WINDOW_GAP_GATE_PCT}% gate; target "
                    f"<= {_WINDOW_GAP_TARGET_PCT}%) — the example's hot "
                    f"loop is stalling on dispatch or host syncs; "
                    f"refusing to report.")
            # ISSUE 7: the same contract as a FLOOR on steady/best —
            # with cache.enable + AOT warmup the steady loop no longer
            # has compile excuses, so a ratio under the floor means the
            # warm-start engine (or the dispatch path) regressed.
            ratio = exd.get("steady_over_best_window")
            floor = _STEADY_OVER_BEST_FLOORS[label]
            if ratio is not None and ratio < floor:
                raise SystemExit(
                    f"BENCH SELF-CHECK FAILED: {label} example steady "
                    f"rate is only {ratio}x its own best window "
                    f"(floor {floor}) — the warm-start engine (AOT "
                    f"warmup / persistent cache) or the hot loop's "
                    f"dispatch path has regressed; refusing to report.")
        # ResNet MFU ratchet (ISSUE 14, replacing ISSUE 7's static
        # >26% floor): each round's measured MFU is gated against the
        # PREVIOUS committed bench via prof.regress — the same
        # name-inferred higher-is-better differ CI already runs, so
        # the floor rises automatically with every improvement instead
        # of being re-legislated by hand.  With no comparable previous
        # summary the static constant remains as the backstop.
        resnet_mfus = {
            "mfu_o2_vs_measured_pct":
                extra["resnet50"].get("mfu_o2_vs_measured_pct"),
            "roofline.total.mfu_pct":
                ((extra["resnet50"].get("roofline") or {}).get("total")
                 or {}).get("mfu_pct"),
        }
        prev_bench = _load_prev_bench() or {}
        prev_mfus = {
            "mfu_o2_vs_measured_pct":
                (prev_bench.get("resnet50") or {}).get(
                    "mfu_o2_vs_measured_pct"),
            "roofline.total.mfu_pct":
                (((prev_bench.get("resnet50") or {}).get("roofline")
                  or {}).get("total") or {}).get("mfu_pct"),
        }
        from apex_tpu.prof import regress as _regress
        # The static floor stays the ratchet's LOWER BOUND: re-basing on
        # the raw previous value each round would let the 5%+2pt
        # allowance compound downward release over release (30 -> 26.5
        # -> 23.2 ... each passing individually).  base = max(prev,
        # floor) bounds any drift inside the floor's own tolerance band
        # while genuine improvements keep raising the bar.
        ratchet_base = {k: max(v, _RESNET_MFU_FLOOR_PCT)
                        for k, v in prev_mfus.items()
                        if v is not None and resnet_mfus.get(k) is not None}
        if ratchet_base:
            diff = _regress.diff_summaries(
                {"resnet50": ratchet_base},
                {"resnet50": {k: resnet_mfus[k] for k in ratchet_base}},
                default_tol_pct=_RESNET_MFU_RATCHET_TOL_PCT)
            if diff["regressions"]:
                rows = "; ".join(
                    f"{e['metric']} {e['base']}% -> {e['cur']}%"
                    for e in diff["regressions"])
                raise SystemExit(
                    f"BENCH SELF-CHECK FAILED: ResNet-50 O2 MFU fell "
                    f"below the previous round's ratchet ({rows}; tol "
                    f"{_RESNET_MFU_RATCHET_TOL_PCT}% + pct-point "
                    f"slack) — the conv-path fusion engine or the tuned "
                    f"kernel configs regressed the measured device "
                    f"rate; refusing to report.")
            extra["resnet50"]["mfu_ratchet"] = {
                "base": ratchet_base,
                "tol_pct": _RESNET_MFU_RATCHET_TOL_PCT,
                "improvements": len(diff["improvements"]),
            }
        # The static floor stays a HARD lower bound on every current
        # metric, ratcheted or not: the ratchet's tolerance band sits
        # below its base, so without this a sequence of
        # individually-passing rounds could still decay to ~floor*0.95
        # - slack and camp there — and a metric whose baseline went
        # missing (failed prev harvest) must never lose gating at all.
        for mfu_name, mfu_val in resnet_mfus.items():
            if mfu_val is None:
                continue
            if mfu_val <= _RESNET_MFU_FLOOR_PCT:
                raise SystemExit(
                    f"BENCH SELF-CHECK FAILED: ResNet-50 O2 "
                    f"{mfu_name} {mfu_val}% is not above the "
                    f"{_RESNET_MFU_FLOOR_PCT}% hard floor (the ratchet "
                    f"only ever RAISES the bar from here) — the "
                    f"conv-path fusion engine is not reaching the "
                    f"hot path; refusing to report.")
        # Absolute DCGAN floor (ISSUE 3): a window-gap gate alone can't
        # catch "steady AND best-window both collapsed" — pin steady to
        # >= 3x the r05 imperative baseline.
        dc_steady = (extra["examples"].get("dcgan_main_amp_3scaler")
                     or {}).get("it_per_sec_steady")
        if dc_steady is not None and dc_steady < _DCGAN_STEADY_GATE_IT_S:
            raise SystemExit(
                f"BENCH SELF-CHECK FAILED: dcgan steady {dc_steady} it/s "
                f"below the {_DCGAN_STEADY_GATE_IT_S:.1f} it/s floor "
                f"(3x the r05 imperative baseline) — the pipelined "
                f"default or the input engine has regressed; refusing "
                f"to report.")
        # FusedAdam dispatch-overhead gates (ISSUE 4): wall/device on the
        # bucketed step, and the deep-tree bucketed speedup.
        adam_wod = extra["fused_adam_step"].get("wall_over_device")
        if adam_wod is not None and adam_wod > _ADAM_WOD_GATE:
            raise SystemExit(
                f"BENCH SELF-CHECK FAILED: bucketed FusedAdam wall/device "
                f"{adam_wod}x > {_ADAM_WOD_GATE}x gate — per-call dispatch "
                f"overhead is back on the optimizer hot path (the exact "
                f"O(leaves) tax the flat-bucket engine removed); refusing "
                f"to report.")
        deep_speedup = adam_deep.get("speedup_bucketed")
        if deep_speedup is not None and deep_speedup < _ADAM_DEEP_SPEEDUP_GATE:
            raise SystemExit(
                f"BENCH SELF-CHECK FAILED: deep-pytree bucketed FusedAdam "
                f"is only {deep_speedup}x the leafwise wall rate "
                f"(gate >= {_ADAM_DEEP_SPEEDUP_GATE}x, "
                f"{adam_deep['n_leaves']} leaves) — the bucketed path has "
                f"regressed toward per-leaf dispatch; refusing to report.")

    # Regression guard vs the previous round (VERDICT r3 next #4): compare
    # each headline timing against the committed BENCH_PREV.json.
    prev = _load_prev_bench()
    vs_prev = {}
    regressions = []
    if prev and not on_tpu:
        prev = None     # prev numbers are TPU numbers; a CPU smoke run
    if prev and prev.get("timing_policy") != _TIMING_POLICY:
        # Like-for-like only: timing is noisy pass to pass, so
        # comparing min-of-reps numbers against a prev round's
        # single-pass numbers systematically flatters the ratios.
        extra_note = (f"regression guard skipped: prev timing_policy "
                      f"{prev.get('timing_policy')!r} != {_TIMING_POLICY!r}")
        print(extra_note, file=sys.stderr)
        prev = None
    if prev:            # comparing against them would scream regressions
        pairs = [
            ("resnet50_ms_o2", t_o2 * 1e3,
             (prev.get("resnet50") or {}).get("ms_per_step_o2")),
            ("bert_ms", t_bert * 1e3,
             (prev.get("bert_base_fusedadam") or {}).get("ms_per_step")),
            ("flash_ms", t_flash * 1e3,
             (prev.get("flash_attention_causal") or {}).get("flash_ms")),
            ("fused_adam_ms", t_fused * 1e3,
             (prev.get("fused_adam_step") or {}).get("fused_ms")),
        ]
        for name, cur, prev_ms in pairs:
            r = _vs_prev(cur, prev_ms)
            if r is None:
                continue
            vs_prev[name] = r
            if r > 1.05:
                regressions.append(f"{name} {r}x")
    extra["vs_prev"] = vs_prev or None
    extra["regressions_vs_prev"] = regressions

    # The driver captures only the last ~2,000 chars of stdout (round 3's
    # headline outgrew it -> parsed: null).  Keep the final line SHORT and
    # write the full data to BENCH_EXTRA.json next to this script.
    root = os.path.dirname(os.path.abspath(__file__))
    extra_path = os.path.join(root, "BENCH_EXTRA.json")
    with open(extra_path, "w") as f:
        json.dump(extra, f, indent=1)

    prof_dev_ms = None
    if prof_resnet and "device_us_per_step" in (prof_resnet or {}):
        prof_dev_ms = round(prof_resnet["device_us_per_step"] / 1e3, 2)
    ex = extra["examples"].get("imagenet_main_amp", {})
    dc = extra["examples"].get("dcgan_main_amp_3scaler", {})
    headline = {
        "metric": "resnet50_amp_o2_images_per_sec_per_chip",
        "value": round(ips_o2, 2),
        "unit": "images/sec",
        "vs_baseline": round(t_o0_dl / t_o2_dl, 3),
        "summary": {
            # The user-facing training-path wall rate (StepPipeline):
            # the ISSUE-2 acceptance compares this against the
            # device-loop rate.  The jitted-PER-STEP wall time (which
            # inherently pays one dispatch per step) moved to
            # resnet50_ms_o2_per_step_wall.
            "resnet50_ms_o2_wall": round(t_o2_pipe * 1e3, 2),
            "resnet50_ms_o2_per_step_wall": round(t_o2 * 1e3, 2),
            "resnet50_ms_o2_device_loop": round(t_o2_dl * 1e3, 2),
            "resnet50_ms_o2_device": prof_dev_ms,
            "resnet50_mfu_vs_measured_pct": (
                round(100 * implied_o2 / measured_med, 1)
                if measured_med else None),
            "plumbing_ms": plumbing_ms,
            "bert_ms": round(t_bert * 1e3, 2),
            "bert_ms_device_loop": round(t_bert_dl * 1e3, 2),
            "bert_mfu_vs_measured_pct": (
                round(100 * bert_implied / measured_med, 1)
                if measured_med else None),
            "flash8k_ms": round(t_flash * 1e3, 2),
            "fused_adam_ms": round(t_fused * 1e3, 3),
            "fused_adam_device_ms": t_adam_dev_ms,
            "fused_adam_chained_ms": round(t_adam_chained * 1e3, 3),
            "fused_adam_bucketed_ms": round(t_adam_bucketed * 1e3, 3),
            "fused_adam_wall_over_device": (
                extra["fused_adam_step"].get("wall_over_device")),
            "fused_adam_deep_ms": adam_deep["leafwise_ms"],
            "fused_adam_deep_bucketed_ms": adam_deep["bucketed_ms"],
            "imagenet_example_img_s_steady": ex.get("img_per_sec_steady"),
            "imagenet_example_img_s_best_window": ex.get(
                "img_per_sec_best_window"),
            "imagenet_example_window_gap_pct": ex.get("window_gap_pct"),
            "imagenet_example_loader_stall_pct": ex.get("loader_stall_pct"),
            "dcgan_example_it_s_steady": dc.get("it_per_sec_steady"),
            "dcgan_example_it_s_best_window": dc.get(
                "it_per_sec_best_window"),
            "dcgan_example_window_gap_pct": dc.get("window_gap_pct"),
            "dcgan_example_loader_stall_pct": dc.get("loader_stall_pct"),
            "zero3_state_ratio_8way": ((extra["mesh"].get("memory") or {})
                                       .get("zero3") or {}).get("ratio"),
            "multihost_fixture_ok": (extra["mesh"].get("multihost")
                                     or {}).get("ok"),
            "serving_tokens_per_s": extra["serving"].get("tokens_per_s"),
            "serving_p99_latency_ms": (
                extra["serving"].get("p99_latency_ms")),
            "serving_trace_overhead_ratio": (
                extra["serving"]["tracing"].get("overhead_ratio")),
            "serving_goodput_pct": (
                extra["serving"]["tracing"].get("goodput_pct")),
            "serving_ttft_p99_ms": (
                extra["serving"]["tracing"].get("analyzer_ttft_p99_ms")),
            "quant_matmul_o4_over_bf16": (
                extra["quant"]["matmul"].get("o4_over_bf16")),
            "quant_lm_ms_per_step_o4": (
                extra["quant"].get("lm_ms_per_step_o4")),
            "quant_kv_capacity_ratio": (
                extra["quant"]["kv"].get("capacity_ratio")),
            "quant_serving_tokens_per_s_int8kv": (
                extra["quant"]["kv"]["serving_int8"].get("tokens_per_s")),
            "tune_max_tuned_over_default": (
                extra["tune"].get("max_tuned_over_default")),
            "tune_kernels_persisted": (
                len(extra["tune"]["kernels"])
                if extra["tune"].get("persisted_ok") else 0),
            "telemetry_overhead_ratio": (
                extra["telemetry"].get("overhead_ratio")),
            "telemetry_step_p50_ms": (
                (ex.get("telemetry") or {}).get("step_p50_ms")),
            "measured_matmul_tflops": (
                round(measured_med / 1e12, 1) if measured_med else None),
            "measured_matmul_tflops_band": (
                [round(min(cals) / 1e12, 1), round(max(cals) / 1e12, 1)]
                if cals else None),
            "vs_prev": vs_prev or None,
            "regressions_vs_prev": regressions,
        },
        # Top-level too (not only in summary): the regression guard must
        # survive the truncation fallback below.
        "regressions_vs_prev": regressions,
        "extra_file": "BENCH_EXTRA.json",
    }
    # The headline as its own artifact: the current-side input of the
    # cross-run regression differ (python -m apex_tpu.prof.regress).
    if on_tpu:
        with open(os.path.join(root, "BENCH_SUMMARY.json"), "w") as f:
            json.dump(headline, f, indent=1)

    line = json.dumps(headline)
    if len(line) > 1500:     # belt-and-braces: never outgrow the driver
        del headline["summary"]
        line = json.dumps(headline)
    print(line)


if __name__ == "__main__":
    main()
