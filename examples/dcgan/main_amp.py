"""DCGAN with amp — the TPU port of the reference
``examples/dcgan/main_amp.py:214-253``: two models, two optimizers, THREE
losses with separate loss scalers.

Two modes:

* default — the step-pipelined path: the whole iteration (G forward,
  both D backwards, D update, G backward, G update, all three dynamic
  loss-scale machines) compiles into ONE program, and
  :class:`apex_tpu.runtime.StepPipeline` chains ``--steps-per-call`` of
  them per host dispatch with losses read back one dispatch behind.
  This is the three-scaler stress test for the runtime: every scaler's
  overflow flag stays a device-side select inside the scan carry.
  (BENCH r05 measured the old imperative loop at 4.67 it/s steady
  against 57 it/s best-window — 10 host dispatches per iteration; the
  pipelined program is one dispatch per K iterations.)
* ``--imperative`` — the reference-parity surface (``amp.initialize(...,
  num_losses=3)``, ``scale_loss(loss_id=0/1/2)``, ``FusedAdam.step()``),
  exercised through the imperative API exactly as the reference example
  drives it.

    python main_amp.py --niter 1 --batchSize 64 --opt_level O1
    python main_amp.py --niter 1 --imperative
"""

import os as _os
import sys as _sys

try:
    import apex_tpu  # noqa: F401
except ModuleNotFoundError:  # running from a source checkout
    _sys.path.insert(0, _os.path.abspath(_os.path.join(
        _os.path.dirname(__file__), *[_os.pardir] * 2)))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp, runtime, training
from apex_tpu.models import Generator, Discriminator
from apex_tpu.optimizers import FusedAdam


def parse():
    p = argparse.ArgumentParser()
    p.add_argument("--batchSize", type=int, default=64)
    p.add_argument("--nz", type=int, default=100)
    p.add_argument("--ngf", type=int, default=64)
    p.add_argument("--ndf", type=int, default=64)
    p.add_argument("--niter", type=int, default=1)
    p.add_argument("--iters-per-epoch", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.0002)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--opt_level", type=str, default="O1")
    p.add_argument("--print-freq", type=int, default=1,
                   help="print losses every N iters (0 = only the final "
                   "iter); pipelined mode rounds the cadence to whole "
                   "windows and reads one dispatch behind, so a print "
                   "never drains the pipeline")
    p.add_argument("--data-pool", type=int, default=8,
                   help="pre-staged synthetic batches reused cyclically "
                   "(host->device upload happens before the timed loop, "
                   "like a prefetching input pipeline)")
    p.add_argument("--warmup", type=int, default=4,
                   help="iters excluded from the steady-state rate (the "
                   "first iterations compile; the SECOND call of each "
                   "program can retrace too — jit caches on input "
                   "shardings, and step outputs come back committed)")
    p.add_argument("--steps-per-call", type=int, default=8,
                   help="pipelined mode: chain N whole GAN iterations "
                   "(D phase + G phase + 3 scaler updates) into ONE "
                   "compiled program via apex_tpu.runtime.StepPipeline")
    p.add_argument("--imperative", action="store_true",
                   help="run the reference-parity imperative amp surface "
                   "(amp.initialize num_losses=3 + scale_loss loss_id + "
                   "FusedAdam.step) instead of the pipelined runtime")
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   metavar="DIR",
                   help="async sharded checkpointing of the full GAN "
                        "state (both parameter trees, both Adam states, "
                        "all three scalers) every --checkpoint-every "
                        "iters at window boundaries (pipelined mode)")
    p.add_argument("--checkpoint-every", type=int, default=100,
                   help="save cadence in iters (window-boundary floored)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest valid checkpoint under "
                        "--checkpoint-dir (pipelined mode)")
    p.add_argument("--drain", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="graceful SIGTERM/SIGINT drain (ON by default): "
                        "finish the window, write a final checkpoint, "
                        "flush the recorder; second signal hard-stops")
    p.add_argument("--telemetry", type=str, default=_os.environ.get(
                       "APEX_TPU_TELEMETRY") or None, metavar="PATH",
                   help="record the run-telemetry event stream (JSONL) "
                   "to PATH; analyze offline with "
                   "python -m apex_tpu.prof.timeline PATH.  Defaults "
                   "from APEX_TPU_TELEMETRY")
    p.add_argument("--metrics-port", type=int, metavar="PORT",
                   default=(int(_os.environ["APEX_TPU_METRICS_PORT"])
                            if _os.environ.get("APEX_TPU_METRICS_PORT")
                            else None),
                   help="serve live Prometheus metrics on "
                   "http://:PORT/metrics (0 = ephemeral; defaults from "
                   "APEX_TPU_METRICS_PORT)")
    p.add_argument("--metrics-textfile", metavar="PATH",
                   default=_os.environ.get("APEX_TPU_METRICS_TEXTFILE")
                   or None,
                   help="atomically-replaced Prometheus textfile for "
                   "node-exporter scraping (defaults from "
                   "APEX_TPU_METRICS_TEXTFILE)")
    p.add_argument("--watchdog", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="run-health rule engine over the telemetry "
                   "events (debounced alerts + a health: line at exit); "
                   "ON by default when --telemetry is set, "
                   "--no-watchdog disables")
    return p.parse_args()


def bce_with_logits(logits, target):
    z = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * target
                    + jnp.log1p(jnp.exp(-jnp.abs(z))))


def _build_models(opt, key):
    netG = Generator(ngf=opt.ngf, nc=3)
    netD = Discriminator(ndf=opt.ndf)
    z0 = jnp.ones((opt.batchSize, opt.nz))
    gv = netG.init(key, z0)
    img0 = netG.apply(gv, z0, train=False)
    dv = netD.init(jax.random.PRNGKey(1), img0)
    return netG, netD, gv, dv


def _synthetic_pool(opt):
    """Pre-staged synthetic batches, uploaded ONCE before the timed loop
    and cycled — the loop then measures the amp machinery, not host RNG
    + host->device streaming.  The
    reference gets the same effect from DALI/DataLoader prefetch.

    "Real" images come from the native counter-based generator
    (ISSUE 3: zero Python-RNG time on the producer side), normalized to
    roughly zero-mean; only the small [batch, nz] noise stays np.random
    (the generator consumes float gaussians)."""
    from apex_tpu.data import synthetic_imagenet

    rng = np.random.RandomState(0)
    imgs = [im for im, _ in synthetic_imagenet(
        opt.batchSize, 64, steps=max(1, opt.data_pool))]
    return [(jnp.asarray((im.astype(np.float32) / 255.0 - 0.5),
                         jnp.float32),
             jnp.asarray(rng.randn(opt.batchSize, opt.nz), jnp.float32))
            for im in imgs]


# -- pipelined mode: one program per K iterations -----------------------------

def main_pipelined(opt):
    """The runtime path: a pure ``step_fn(state, batch)`` carrying BOTH
    parameter trees, both Adam states, and all three dynamic loss-scale
    states; :class:`runtime.StepPipeline` scans it K iterations per host
    dispatch.  Semantics match the imperative path: each loss has its own
    scaler, the two D losses accumulate into one Adam step that skips if
    EITHER overflowed, and the G phase sees the UPDATED discriminator."""
    from apex_tpu.amp.loss_scaler import LossScaler

    if opt.opt_level not in ("O0", "O1"):
        raise SystemExit(f"pipelined dcgan supports O0/O1 (the reference "
                         f"example's levels); got {opt.opt_level} — use "
                         f"--imperative for the full opt-level surface")
    if opt.opt_level == "O1":
        amp.init()                      # O1 autocast inside the traced loss

    key = jax.random.PRNGKey(0)
    netG, netD, gv, dv = _build_models(opt, key)
    g_state = {k: v for k, v in gv.items() if k != "params"}
    d_state = {k: v for k, v in dv.items() if k != "params"}
    real_label, fake_label = 1.0, 0.0

    def d_loss_real(d_params, real):
        out, _ = netD.apply({"params": d_params, **d_state}, real,
                            train=True, mutable=["batch_stats"])
        return bce_with_logits(out, real_label)

    def d_loss_fake(d_params, fake):
        out, _ = netD.apply({"params": d_params, **d_state}, fake,
                            train=True, mutable=["batch_stats"])
        return bce_with_logits(out, fake_label)

    def g_loss(g_params, d_params, noise):
        fake, _ = netG.apply({"params": g_params, **g_state}, noise,
                             train=True, mutable=["batch_stats"])
        out, _ = netD.apply({"params": d_params, **d_state}, fake,
                            train=True, mutable=["batch_stats"])
        return bce_with_logits(out, real_label)

    # Three scalers, one per loss (the num_losses=3 contract), dynamic
    # under amp exactly like amp.initialize's default.
    dynamic = opt.opt_level != "O0"
    scalers = [LossScaler("dynamic" if dynamic else 1.0) for _ in range(3)]
    tx = training.adam(lr=opt.lr, beta1=opt.beta1, beta2=0.999)

    state = {
        "g": gv["params"], "d": dv["params"],
        "g_opt": tx.init(gv["params"]), "d_opt": tx.init(dv["params"]),
        "s0": scalers[0].init(), "s1": scalers[1].init(),
        "s2": scalers[2].init(),
    }

    def step_fn(state, batch):
        real, noise = batch
        # (1) D phase: G forward (detached) + BOTH D backwards, each loss
        # scaled by its own scaler; the two unscaled grads accumulate
        # into ONE Adam step that skips when EITHER loss overflowed
        # (apex semantics: backward-accumulate then step-or-skip).
        fake, _ = netG.apply({"params": state["g"], **g_state}, batch[1],
                             train=True, mutable=["batch_stats"])
        fake = jax.lax.stop_gradient(fake)
        errR, gR = jax.value_and_grad(
            lambda p: jnp.float32(d_loss_real(p, real))
            * state["s0"].loss_scale)(state["d"])
        errF, gF = jax.value_and_grad(
            lambda p: jnp.float32(d_loss_fake(p, fake))
            * state["s1"].loss_scale)(state["d"])
        gR, s0 = scalers[0].unscale(gR, state["s0"])
        gF, s1 = scalers[1].unscale(gF, state["s1"])
        mask_d = (jnp.logical_not(s0.overflow | s1.overflow)
                  if dynamic else None)
        g_d = jax.tree_util.tree_map(lambda a, b: a + b, gR, gF)
        d_new, d_opt = tx.update(g_d, state["d_opt"], state["d"],
                                 apply_mask=mask_d)
        # (2) G phase, loss_id=2, against the UPDATED discriminator —
        # same ordering as the imperative loop (optimizerD.step() runs
        # before g_phase reads optimizerD.params).
        errG, gG = jax.value_and_grad(
            lambda p: jnp.float32(g_loss(p, d_new, noise))
            * state["s2"].loss_scale)(state["g"])
        gG, s2 = scalers[2].unscale(gG, state["s2"])
        mask_g = jnp.logical_not(s2.overflow) if dynamic else None
        g_new, g_opt = tx.update(gG, state["g_opt"], state["g"],
                                 apply_mask=mask_g)
        metrics = {
            # unscaled for display (err* carry their loss's scale)
            "loss_d": (errR / state["s0"].loss_scale
                       + errF / state["s1"].loss_scale),
            "loss_g": errG / state["s2"].loss_scale,
            "scale": state["s2"].loss_scale,
        }
        new_state = {
            "g": g_new, "d": d_new, "g_opt": g_opt, "d_opt": d_opt,
            "s0": scalers[0].update_scale(s0),
            "s1": scalers[1].update_scale(s1),
            "s2": scalers[2].update_scale(s2),
        }
        return new_state, metrics

    # Elastic checkpoint/resume + preemption drain (ISSUE 9): the whole
    # functional carry — both parameter trees, both Adam states, all
    # three loss-scale machines — is one pytree, so the manager
    # checkpoints GAN training with the same code path as the others.
    mgr = None
    start_step = 0
    if opt.checkpoint_dir:
        from apex_tpu import checkpoint as apex_checkpoint
        mgr = apex_checkpoint.CheckpointManager(
            opt.checkpoint_dir,
            every_steps=max(1, opt.checkpoint_every))
        if opt.resume:
            restored = mgr.restore(like=state)
            if restored is not None:
                state = restored.state
                start_step = restored.step
                from apex_tpu import telemetry
                rec = telemetry.get_recorder()
                if rec is not None:
                    rec.run_id = mgr.run_id
                    rec.event("resume", run_id=mgr.run_id,
                              step=start_step)
                print(f"resumed at iter {start_step} "
                      f"(run {mgr.run_id}) from {opt.checkpoint_dir}")
    stop = runtime.GracefulShutdown().install() if opt.drain else None

    spc = max(1, opt.steps_per_call)
    total = opt.niter * opt.iters_per_epoch
    # Reused pool window: spc distinct pool batches stacked once — must
    # NOT be donated (streamed real data would stage fresh windows via
    # runtime.stage_windows and donate them).
    pool = _synthetic_pool(opt)
    window = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *(pool[i % len(pool)] for i in range(spc)))
    pipe = runtime.StepPipeline(step_fn, spc, donate_window=False)

    print_every = max(1, -(-opt.print_freq // spc)) \
        if opt.print_freq > 0 else 0       # cadence in WINDOWS

    t0 = time.perf_counter()
    t_steady = None
    warm_iters = 0
    reader = runtime.DeferredMetrics()
    ipe = opt.iters_per_epoch

    def emit(wm):
        """One window's loss lines from ONE stacked device->host
        transfer, one dispatch behind the loop."""
        vals = wm.fetch()
        last = wm.n_valid - 1
        it_done = wm.step + wm.n_valid
        print(f"[{(it_done - 1) // ipe}/{opt.niter}]"
              f"[{(it_done - 1) % ipe}/{ipe}] "
              f"Loss_D: {np.ravel(vals['loss_d'])[last]:.4f} "
              f"Loss_G: {np.ravel(vals['loss_g'])[last]:.4f}")

    ci = 0
    while start_step + reader.steps_pushed < total:
        n_valid = min(spc, total - start_step - reader.steps_pushed)
        state, metrics = pipe.step_window(state, window, n_valid)
        prev = reader.push(metrics, n_valid)
        if ci <= 1:
            # Calls 0 AND 1 both compile (call 1 re-specializes on the
            # committed output shardings); drain them synchronously so
            # the steady clock starts after both.
            reader.newest().fetch()
            t_steady = time.perf_counter()
            warm_iters = reader.steps_pushed
        if prev is not None and print_every \
                and (prev.step // spc) % print_every == 0:
            emit(prev)
        ci += 1
        gstep = start_step + reader.steps_pushed
        if stop is not None and stop.draining:
            if mgr is not None:
                mgr.save(gstep, state, block=True)
            print(f"drain: stopping at iter {gstep} ({stop.reason})")
            break
        if mgr is not None:
            mgr.maybe_save(gstep, state)
    if reader.newest() is not None:
        emit(reader.newest())             # doubles as the pipeline drain
    if mgr is not None:
        gstep = start_step + reader.steps_pushed
        if mgr.last_saved != gstep:
            mgr.save(gstep, state, block=True)
        mgr.close()
        print(f"checkpoint: iter {gstep} saved under "
              f"{opt.checkpoint_dir}")
    if stop is not None:
        stop.uninstall()
    t1 = time.perf_counter()
    # ACTUAL iterations dispatched (a drain break stops early — dividing
    # the planned total by the short wall would inflate the it/s lines
    # bench.py parses)
    n_done = reader.steps_pushed
    n_steady = n_done - warm_iters
    if t_steady is not None and n_steady > 0:
        print(f"steady {n_steady / (t1 - t_steady):.2f} it/s over "
              f"{n_steady} iters (excl first 2 calls)")

    # Best-of-3 windows under the repo's min-of-reps timing policy: one
    # steady window can eat a host stall; each timed
    # window is 2 calls (2*spc iters) fenced by one stacked metric fetch.
    if total >= spc and spc > 1:
        best = float("inf")
        for _ in range(3):
            tw = time.perf_counter()
            for _ in range(2):
                state, metrics = pipe.step_window(state, window, spc)
            runtime.WindowMetrics(0, spc, metrics).fetch()
            best = min(best, (time.perf_counter() - tw) / (2 * spc))
        print(f"best-of-3 windows: {1.0 / best:.2f} it/s "
              f"({best * 1e3:.1f} ms/iter over {2 * spc}-iter windows)")
    # Parsed by bench.py into loader_stall_pct: the pool is fully
    # pre-staged, so by construction the loop never waits on input.
    print("loader: stall 0.00% (pre-staged synthetic pool)")
    # HBM memory ledger (ISSUE 10): emits the `memory` event + the
    # peak_hbm_bytes gauge the exit health: line reads.
    try:
        mem = pipe.memory_stats()
        if mem is not None:
            print(f"memory: peak-hbm {mem['peak_bytes'] / 1e6:.1f}MB")
    except Exception as e:                       # pragma: no cover
        print(f"memory: ledger unavailable ({type(e).__name__}: {e})")
    print(f"done in {t1 - t0:.1f}s ({n_done / (t1 - t0):.2f} it/s)")


# -- imperative mode: the reference-parity amp surface ------------------------

def main_imperative(opt):
    key = jax.random.PRNGKey(0)
    netG, netD, gv, dv = _build_models(opt, key)

    optimizerG = FusedAdam(gv["params"], lr=opt.lr, betas=(opt.beta1, 0.999))
    optimizerD = FusedAdam(dv["params"], lr=opt.lr, betas=(opt.beta1, 0.999))

    # Multi-model / multi-optimizer / multi-loss init (reference
    # main_amp.py:214-215).
    [gp, dp], [optimizerG, optimizerD] = amp.initialize(
        [optimizerG.params, optimizerD.params], [optimizerG, optimizerD],
        opt_level=opt.opt_level, num_losses=3)

    g_state = {k: v for k, v in gv.items() if k != "params"}
    d_state = {k: v for k, v in dv.items() if k != "params"}
    real_label, fake_label = 1.0, 0.0

    def d_loss_real(d_params, real):
        out, _ = netD.apply({"params": d_params, **d_state}, real,
                            train=True, mutable=["batch_stats"])
        return bce_with_logits(out, real_label)

    def d_loss_fake(d_params, fake):
        out, _ = netD.apply({"params": d_params, **d_state}, fake,
                            train=True, mutable=["batch_stats"])
        return bce_with_logits(out, fake_label)

    def g_loss(g_params, d_params, noise):
        fake, _ = netG.apply({"params": g_params, **g_state}, noise,
                             train=True, mutable=["batch_stats"])
        out, _ = netD.apply({"params": d_params, **d_state}, fake,
                            train=True, mutable=["batch_stats"])
        return bce_with_logits(out, real_label)

    # TWO jitted programs per iteration phase pair (r5, VERDICT r4 next
    # #6): the whole D phase — G forward (detached) + BOTH D backwards —
    # is ONE compiled program instead of three; each dispatch costs a
    # fixed amount plus an amount per leaf-arg, so programs are the unit
    # of cost here.  Params AND loss scales enter as jit
    # ARGUMENTS (live values each call): closing over optimizer.params
    # inside an outer jit would freeze the weights at trace time — the
    # exact bug this file shipped with for four rounds.
    from apex_tpu.amp._amp_state import _amp_state

    def live_scale(i):
        return _amp_state.loss_scalers[i].state.loss_scale

    @jax.jit
    def d_phase(d_params, g_params, real, noise, s0, s1):
        fake, _ = netG.apply({"params": g_params, **g_state}, noise,
                             train=True, mutable=["batch_stats"])
        fake = jax.lax.stop_gradient(fake)
        err_r, g_r = jax.value_and_grad(
            lambda p: jnp.float32(d_loss_real(p, real)) * s0)(d_params)
        err_f, g_f = jax.value_and_grad(
            lambda p: jnp.float32(d_loss_fake(p, fake)) * s1)(d_params)
        return err_r, g_r, err_f, g_f

    @jax.jit
    def g_phase(g_params, d_params, noise, s2):
        return jax.value_and_grad(
            lambda p: jnp.float32(g_loss(p, d_params, noise)) * s2)(
                g_params)

    pool = _synthetic_pool(opt)

    def train_iter(idx):
        """One imperative iteration — shared by the main loop AND the
        best-of-3 timing windows so both measure the same computation.
        Returns the (scaled) losses and the scales used."""
        real, noise = pool[idx % len(pool)]
        # (1) D phase: ONE program — G fwd (detached) + D-real + D-fake
        # backwards; separate scalers per loss (loss_id=0/1).
        s0, s1 = live_scale(0), live_scale(1)
        errD_real, gD, errD_fake, gDf = d_phase(
            optimizerD.params, optimizerG.params, real, noise, s0, s1)
        with amp.scale_loss(errD_real, optimizerD, loss_id=0):
            optimizerD.backward(gD)
        with amp.scale_loss(errD_fake, optimizerD, loss_id=1):
            optimizerD.backward(gDf)
        optimizerD.step()
        # (2) G, loss_id=2 (grads w.r.t. G through D)
        s2 = live_scale(2)
        errG, gG = g_phase(optimizerG.params, optimizerD.params, noise, s2)
        with amp.scale_loss(errG, optimizerG, loss_id=2):
            optimizerG.backward(gG)
        optimizerG.step()
        return errD_real, errD_fake, errG, s0, s1, s2

    def drain():
        """Force the pipeline: one scalar fetch of the LAST update's
        output (in-order execution makes it drain everything before)."""
        float(jnp.ravel(jax.tree_util.tree_leaves(
            optimizerG.params)[-1])[0].astype(jnp.float32))

    t0 = time.perf_counter()
    total = opt.niter * opt.iters_per_epoch
    t_steady = t0 if opt.warmup <= 0 else None
    it = 0
    for epoch in range(opt.niter):
        for i in range(opt.iters_per_epoch):
            errD_real, errD_fake, errG, s0, s1, s2 = train_iter(it)
            it += 1
            if it == opt.warmup and it < total:
                # Warm the print path too before starting the steady
                # clock: the division/stack pack compiles on first use,
                # which would otherwise land inside the steady window at
                # the first print.
                # jaxlint: disable=J001 -- deliberate one-off warmup fetch: compiles the print path before the steady clock starts
                np.asarray(jnp.stack([errD_real / s0, errD_fake / s1,
                                      errG / s2]))
                t_steady = time.perf_counter()     # compiles are behind us
            if (opt.print_freq > 0 and it % opt.print_freq == 0) \
                    or it == total:
                # ONE stacked device->host transfer per print (each
                # separate float() is a full pipeline-drain round-trip);
                # losses are unscaled for display.
                # jaxlint: disable=J001 -- print-frequency-gated: one stacked transfer per print window, not per step
                packed = np.asarray(jnp.stack([
                    errD_real / s0, errD_fake / s1, errG / s2]))
                print(f"[{epoch}/{opt.niter}][{i}/{opt.iters_per_epoch}] "
                      f"Loss_D: {packed[0] + packed[1]:.4f} "
                      f"Loss_G: {packed[2]:.4f}")
    drain()
    t1 = time.perf_counter()
    if t_steady is not None and total > opt.warmup:
        n_steady = total - opt.warmup
        print(f"steady {n_steady / (t1 - t_steady):.2f} it/s over "
              f"{n_steady} iters (excl {opt.warmup} warmup)")

    # Best-of-3 windows under the repo's min-of-reps timing policy: the
    # single steady window above can eat a host stall, so the rate the
    # loop DEMONSTRABLY achieves is reported beside it.
    if total >= 8:         # skipped in tiny CPU smokes
        k = 8
        best = float("inf")
        for _ in range(3):
            drain()
            tp_ = time.perf_counter()
            for j in range(k):
                train_iter(it + j)
            drain()
            best = min(best, (time.perf_counter() - tp_) / k)
            it += k
        print(f"best-of-3 windows: {1.0 / best:.2f} it/s "
              f"({best * 1e3:.1f} ms/iter over {k}-iter windows)")

    # Dispatch budget: the imperative path pays a fixed cost per program
    # and a cost per INPUT leaf-arg (outputs ride the same transfers), so
    # print both counts next to the measured rate.  d_phase takes D+G
    # params + 2 batches + 2 scales; g_phase takes G+D params + noise +
    # scale; each step() program takes grads + adam (m, v) + params = 4
    # trees.  Also dispatched per iter: 6 TINY jitted scaler programs (3
    # unscale/axpby sweeps + 3 update_scale lanes).  What a program and a
    # leaf-arg cost on the current installation is not measured, so no
    # floor in ms is derived from the counts.
    n_d = len(jax.tree_util.tree_leaves(optimizerD.params))
    n_g = len(jax.tree_util.tree_leaves(optimizerG.params))
    n_leaves = ((n_d + n_g + 4)          # d_phase
                + (n_g + n_d + 2)        # g_phase
                + 4 * n_d + 4 * n_g)     # stepD + stepG
    print(f"dispatch budget: 4 heavy + 6 tiny jitted programs/iter, "
          f"~{n_leaves} leaf-args/iter")
    print("loader: stall 0.00% (pre-staged synthetic pool)")
    print(f"done in {t1 - t0:.1f}s ({total / (t1 - t0):.2f} it/s)")


def main():
    opt = parse()
    if opt.imperative and (opt.checkpoint_dir or opt.resume):
        raise SystemExit(
            "--checkpoint-dir/--resume need the pipelined default (the "
            "functional state carry is what the manager snapshots); "
            "drop --imperative")
    rec = None
    use_watchdog = (opt.watchdog if opt.watchdog is not None
                    else bool(opt.telemetry))
    if (opt.telemetry or use_watchdog or opt.metrics_port is not None
            or opt.metrics_textfile):
        # Active recorder installed before either mode builds its loop:
        # the pipelined path records window/gap/metrics events through
        # StepPipeline; the imperative path records the per-step
        # optimizer spans and deferred-overflow skip events.  The
        # watchdog (default-on under --telemetry) folds them online.
        from apex_tpu import telemetry
        rec = telemetry.start(
            opt.telemetry or _os.devnull, watchdog=use_watchdog,
            example="dcgan",
            export_port=opt.metrics_port,
            export_textfile=opt.metrics_textfile,
            mode="imperative" if opt.imperative else "pipelined",
            opt_level=opt.opt_level, steps_per_call=opt.steps_per_call)
        if rec.exporter is not None:
            print(f"metrics export: {rec.exporter.describe()}")
    try:
        if opt.imperative:
            main_imperative(opt)
        else:
            main_pipelined(opt)
    finally:
        if rec is not None:
            wd = rec.watchdog
            rec.close()
            if opt.telemetry:
                print(f"telemetry: {opt.telemetry} "
                      f"(python -m apex_tpu.prof.timeline to analyze)")
            if wd is not None:
                extras = ""
                peak = rec.metrics.gauge("peak_hbm_bytes").value
                if peak:
                    extras += f"  peak-hbm {peak / 1e6:.1f}MB"
                if rec.exporter is not None:
                    extras += f"  export {rec.exporter.describe()}"
                print(f"health: {wd.format_line()}{extras}")


if __name__ == "__main__":
    main()
