"""Causal-LM pretraining with amp — the long-context training example.

No reference counterpart (apex ships no LM example); this is the
framework's long-context showcase: GPT with Pallas flash attention, the
fused label-smoothing xentropy loss, FusedAdam with the BERT-style
no-decay-on-bias/LayerNorm parameter groups, and the fully-jitted amp
train step.  With ``--sp N`` the sequence is sharded over an ``sp`` mesh
axis and attention runs as ring attention (``--attention ring`` or
``ring_flash``).  ``--model granite-hybrid`` trains the Granite-4.0-H block
instead (``apex_tpu.models.GraniteHybrid``: Mamba-2 state-space layers
beside GQA attention layers in the published period of ten, SwiGLU, RMSNorm)
through the same loss, train step and pipeline, and ``--model lfm2-moe`` the
LFM2-MoE block (``apex_tpu.models.Lfm2Moe``: gated short convolutions beside
GQA attention with rotary positions in the published period of four, a dense
SwiGLU MLP in the first layer and routed experts after it, sigmoid top-4
without drops; the selection bias and the experts' load counts ride along as
model state), and ``--model nemotron-h`` the Nemotron-H block
(``apex_tpu.models.NemotronH``: layers that are each a Mamba-2 mixer, a GQA
attention without positions or a latent expert layer alone, by the published
string of letters; 16 ``relu ** 2`` experts in a latent space of hidden / 2,
4 a token, beside one shared expert).

The loop runs on :class:`apex_tpu.runtime.StepPipeline`:
``--steps-per-call K`` chains K steps into ONE compiled program
(BENCH r05: BERT runs 14.8 ms/step in a device loop vs 24.2 ms wall
jitted-per-step — pure dispatch), and the per-step loss lines print one
dispatch behind from the window's stacked metrics, so the hot loop
never blocks on a scalar.

    python main_amp.py --synthetic --steps 5 --seq-len 256 --opt-level O2
    python main_amp.py --synthetic --steps 32 --steps-per-call 8
    python main_amp.py --synthetic --steps 2 --sp 2 --attention ring
    python main_amp.py --synthetic --steps 5 --model granite-hybrid --layers 10
    python main_amp.py --synthetic --steps 5 --model lfm2-moe --layers 5
    python main_amp.py --synthetic --steps 5 --model nemotron-h --layers 11
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

from apex_tpu import runtime, training
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.models import (GPT, GraniteHybrid, Lfm2Moe, NemotronH,
                             granite_hybrid, lfm2_moe, nemotron_h)
from apex_tpu.training import make_train_step


def parse():
    p = argparse.ArgumentParser()
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--model", type=str, default="gpt",
                   choices=["gpt", "granite-hybrid", "lfm2-moe",
                            "nemotron-h"],
                   help="gpt: GPT-2 blocks; granite-hybrid: Mamba-2 and GQA "
                        "attention layers by the published period (five "
                        "mamba, attention, four mamba), heads of "
                        "hidden/heads for both kinds of layer; lfm2-moe: one "
                        "dense layer, then gated short convs and GQA+RoPE "
                        "attention by the published period (attention, three "
                        "convs), each followed by 8 routed experts of width "
                        "hidden, 4 a token; nemotron-h: the first --layers "
                        "letters of the published pattern (MEMEMEM*EME...: a "
                        "Mamba-2 mixer, a latent expert layer or an attention "
                        "alone), 16 latent experts, 4 a token, one shared")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("-b", "--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--vocab", type=int, default=8192)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--opt-level", type=str, default="O2")
    p.add_argument("--loss-scale", type=str, default=None)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--smoothing", type=float, default=0.0)
    p.add_argument("--fused-loss", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="contrib.xentropy fused softmax-cross-entropy on "
                        "the vocab-sized logits (the textbook case: one "
                        "pass, saves only max_log_sum_exp instead of "
                        "materialized log-probs).  --no-fused-loss keeps "
                        "the log_softmax+gather reference composition — "
                        "the smoke test asserts loss parity between the "
                        "two (ISSUE 7)")
    p.add_argument("--attention", type=str, default="flash",
                   choices=["full", "blockwise", "flash", "ring",
                            "ring_flash", "ulysses"])
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel ways (needs >= sp devices)")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA/MQA: kv heads shared across query heads "
                        "(must divide --heads; flash kernel shares KV "
                        "via index maps)")
    p.add_argument("--window", type=int, default=None,
                   help="sliding-window local attention (causal, "
                        "O(T*window) on the flash kernel)")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="chain N train steps into ONE compiled program "
                        "(apex_tpu.runtime.StepPipeline); host dispatch "
                        "and the metric fetch then cost once per N steps "
                        "— loss lines print one dispatch behind")
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   metavar="DIR",
                   help="async sharded checkpointing "
                        "(apex_tpu.checkpoint.CheckpointManager) every "
                        "--checkpoint-every steps at window boundaries")
    p.add_argument("--checkpoint-every", type=int, default=100,
                   help="save cadence in steps (window-boundary floored)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest valid checkpoint under "
                        "--checkpoint-dir: params/optimizer/scaler "
                        "state, step counter, and telemetry run-id "
                        "round-trip bit-identically")
    p.add_argument("--drain", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="graceful SIGTERM/SIGINT drain (ON by default): "
                        "finish the window, write a final checkpoint, "
                        "flush the recorder; second signal hard-stops")
    p.add_argument("--telemetry", type=str, default=_os.environ.get(
                       "APEX_TPU_TELEMETRY") or None, metavar="PATH",
                   help="record the run-telemetry event stream (JSONL) "
                        "to PATH; analyze offline with "
                        "python -m apex_tpu.prof.timeline PATH.  "
                        "Defaults from APEX_TPU_TELEMETRY")
    p.add_argument("--metrics-port", type=int, metavar="PORT",
                   default=(int(_os.environ["APEX_TPU_METRICS_PORT"])
                            if _os.environ.get("APEX_TPU_METRICS_PORT")
                            else None),
                   help="serve live Prometheus metrics on "
                        "http://:PORT/metrics (0 = ephemeral; defaults "
                        "from APEX_TPU_METRICS_PORT)")
    p.add_argument("--metrics-textfile", metavar="PATH",
                   default=_os.environ.get("APEX_TPU_METRICS_TEXTFILE")
                   or None,
                   help="atomically-replaced Prometheus textfile for "
                        "node-exporter scraping (defaults from "
                        "APEX_TPU_METRICS_TEXTFILE)")
    p.add_argument("--watchdog", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="run-health rule engine over the telemetry "
                        "events (debounced alerts + a health: line at "
                        "exit); ON by default when --telemetry is set, "
                        "--no-watchdog disables")
    return p.parse_args()


def main():
    args = parse()
    if not args.synthetic:
        raise SystemExit("only --synthetic data is implemented; pass "
                         "--synthetic (a real-data loader would plug in "
                         "here via apex_tpu.data)")
    rec = None
    use_watchdog = (args.watchdog if args.watchdog is not None
                    else bool(args.telemetry))
    if (args.telemetry or use_watchdog or args.metrics_port is not None
            or args.metrics_textfile):
        # Install the active recorder before the pipeline is built so
        # StepPipeline and the deferred metric reads pick it up.
        from apex_tpu import telemetry
        rec = telemetry.start(args.telemetry or _os.devnull,
                              watchdog=use_watchdog, example="lm",
                              export_port=args.metrics_port,
                              export_textfile=args.metrics_textfile,
                              opt_level=args.opt_level,
                              attention=args.attention,
                              steps_per_call=args.steps_per_call)
        if rec.exporter is not None:
            print(f"metrics export: {rec.exporter.describe()}")
    try:
        # close() in finally: a diverged/killed run still flushes its
        # stream, the summary event, and the watchdog's final alerts.
        _train(args)
    finally:
        if rec is not None:
            wd = rec.watchdog
            rec.close()
            if args.telemetry:
                print(f"telemetry: {args.telemetry} "
                      f"(python -m apex_tpu.prof.timeline to analyze)")
            if wd is not None:
                extras = ""
                peak = rec.metrics.gauge("peak_hbm_bytes").value
                if peak:
                    extras += f"  peak-hbm {peak / 1e6:.1f}MB"
                if rec.exporter is not None:
                    extras += f"  export {rec.exporter.describe()}"
                print(f"health: {wd.format_line()}{extras}")


def _train(args):
    loss_scale = args.loss_scale
    if loss_scale not in (None, "dynamic"):
        loss_scale = float(loss_scale)

    sp = args.sp
    if sp > 1 and args.attention not in ("ring", "ring_flash", "ulysses"):
        raise SystemExit(
            f"--sp {sp} shards the sequence; attention_impl="
            f"{args.attention!r} is shard-local and would silently attend "
            f"within shards only — use ring, ring_flash or ulysses")
    if args.window is not None and args.attention not in ("flash",):
        raise SystemExit("--window needs --attention flash")
    if args.kv_heads is not None and args.attention not in (
            "flash", "blockwise", "full"):
        raise SystemExit("--kv-heads needs --attention flash/blockwise/full "
                         "(GQA is shard-local; ring/ulysses paths are MHA)")
    hybrid, moe = args.model == "granite-hybrid", args.model == "lfm2-moe"
    latent = args.model == "nemotron-h"
    if (hybrid or moe or latent) and (sp > 1 or args.window is not None
                                      or args.attention != "flash"):
        raise SystemExit(f"--model {args.model} runs unsharded with "
                         f"--attention flash and no --window")
    if hybrid:
        period = granite_hybrid.PERIOD
        model = GraniteHybrid(
            vocab_size=args.vocab, hidden_size=args.hidden,
            layer_types=tuple(period[i % len(period)]
                              for i in range(args.layers)),
            num_heads=args.heads, num_kv_heads=args.kv_heads or args.heads,
            mlp_dim=4 * args.hidden, mamba_heads=2 * args.heads,
            mamba_head_dim=args.hidden // args.heads, dtype=jnp.bfloat16)
    elif moe:
        period = lfm2_moe.LAYER_TYPES[2:6]
        model = Lfm2Moe(
            vocab_size=args.vocab, hidden_size=args.hidden,
            layer_types=lfm2_moe.LAYER_TYPES[:1] + tuple(
                period[i % len(period)] for i in range(args.layers - 1)),
            num_dense_layers=1, num_heads=args.heads,
            num_kv_heads=args.kv_heads or args.heads,
            mlp_dim=4 * args.hidden, moe_dim=args.hidden, num_experts=8,
            experts_held=8, dtype=jnp.bfloat16)
    elif latent:
        model = NemotronH(
            vocab_size=args.vocab, hidden_size=args.hidden,
            pattern=nemotron_h.PATTERN[:args.layers],
            mamba_heads=2 * args.heads,
            mamba_head_dim=args.hidden // args.heads, mamba_groups=2,
            num_heads=args.heads, num_kv_heads=args.kv_heads or args.heads,
            head_dim=args.hidden // args.heads,
            latent_size=args.hidden // 2, moe_dim=args.hidden,
            shared_dim=2 * args.hidden, num_experts=16, experts_held=16,
            top_k=4, dtype=jnp.bfloat16)
    else:
        model = GPT(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    mlp_dim=4 * args.hidden, max_len=args.seq_len,
                    dtype=jnp.bfloat16, attention_impl=args.attention,
                    num_kv_heads=args.kv_heads, window=args.window,
                    sp_axis="sp" if sp > 1 else None)
    # Same architecture without the sp axis for (replicated) init.
    init_model = model if sp == 1 else model.clone(attention_impl="full",
                                                   sp_axis=None)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(1, args.vocab,
                                  (args.batch_size, args.seq_len)))
    # Next-token pairs are built GLOBALLY (before any sequence sharding,
    # so labels never cross shard boundaries); T' = seq_len - 1 tokens.
    x_tok, y_tok = ids[:, :-1], ids[:, 1:]
    t_train = args.seq_len - 1
    if sp > 1 and t_train % sp:
        raise SystemExit(f"--seq-len must be 1 + multiple of --sp "
                         f"(got {args.seq_len}, sp={sp})")
    variables = init_model.init(jax.random.PRNGKey(0), ids[:1, :8])
    # the expert layers' selection bias and load counts; None for the others
    params, model_state = variables["params"], variables.get("moe")
    n_params = sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(params))
    print(f"{type(model).__name__} {args.layers}L/{args.hidden}H  "
          f"{n_params/1e6:.1f}M params  "
          f"attention={args.attention}  opt_level = {args.opt_level}")

    def token_loss(logits, yb):
        flat = logits.reshape(-1, logits.shape[-1])
        labels = yb.reshape(-1)
        if args.fused_loss:
            losses = softmax_cross_entropy_loss(
                flat, labels, smoothing=args.smoothing)
        else:
            # Reference composition (materialized log-probs): the parity
            # oracle the smoke test pins the fused kernel against.  Same
            # padding contract as the fused default (padding_idx=0 —
            # synthetic ids are drawn from [1, vocab), so no row pads).
            logp = jax.nn.log_softmax(flat.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
            smooth = -jnp.mean(logp, axis=-1)
            losses = ((1.0 - args.smoothing) * nll
                      + args.smoothing * smooth)
            losses = jnp.where(labels == 0, 0.0, losses)
        return jnp.mean(losses)

    def loss_fn(p, batch):
        xb, yb = batch
        return token_loss(model.apply({"params": p}, xb), yb)

    def loss_fn_with_state(p, moe_state, batch):
        xb, yb = batch
        logits, new = model.apply({"params": p, "moe": moe_state}, xb,
                                  mutable=["moe"])
        return token_loss(logits, yb), new["moe"]

    # O2 keeps float32, beside the norms: the hybrid mixer's A_log, dt_bias
    # and D; the expert layers' router
    keep_fp32 = (granite_hybrid.keep_fp32 if hybrid
                 else lfm2_moe.keep_fp32 if moe
                 else nemotron_h.keep_fp32 if latent else None)
    moe = moe or latent         # both carry their expert layers' state
    init_fn, step_fn = make_train_step(
        loss_fn_with_state if moe else loss_fn,
        training.adam(args.lr, weight_decay=args.weight_decay),
        opt_level=args.opt_level, loss_scale=loss_scale,
        axis_name="sp" if sp > 1 else None, norm_predicate=keep_fp32,
        has_model_state=moe)
    state = init_fn(params, model_state)

    spc = max(1, args.steps_per_call)
    wrap = None
    if sp > 1:
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        devs = jax.devices()[:sp]
        mesh = Mesh(np.array(devs), ("sp",))
        # Sequence sharded over sp; params/batch-rows replicated.  The
        # window's leading K (step) axis stays unsharded; the tail mask
        # is replicated.
        wrap = lambda fn: shard_map(  # noqa: E731
            fn, mesh=mesh,
            in_specs=(P(), (P(None, None, "sp"), P(None, None, "sp")),
                      P()),
            out_specs=(P(), P()))

    # Synthetic data is ONE batch reused every step: pre-stack it into a
    # single [spc, B, T'] window and cycle it device-side — a reused pool
    # window must NOT be donated (streamed real data would stage fresh
    # windows through runtime.stage_windows and donate them).
    pipe = runtime.StepPipeline(step_fn, spc, wrap=wrap,
                                donate_window=False)
    window = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (spc,) + a.shape),
        (x_tok, y_tok))

    tok_per_step = args.batch_size * (args.seq_len - 1)
    tic = time.time()

    def emit(wm):
        """Print one loss line per REAL step of the window, from ONE
        stacked device->host transfer one dispatch behind the loop (the
        per-step float() reads this example used to do were each a full
        pipeline drain)."""
        nonlocal tic
        vals = wm.fetch()
        toc = time.time()
        tok_s = wm.n_valid * tok_per_step / max(toc - tic, 1e-9)
        loss_k = np.ravel(vals["loss"])
        scale_k = np.ravel(vals["loss_scale"])
        for j in range(wm.n_valid):
            print(f"step {wm.step + j}  loss {loss_k[j]:.4f}  "
                  f"loss_scale {scale_k[j]:.0f}  "
                  f"{tok_s:,.0f} tok/s")
        tic = toc
        return loss_k[wm.n_valid - 1]

    # Elastic checkpoint/resume + preemption drain (ISSUE 9).
    mgr = None
    start_step = 0
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
                from apex_tpu import telemetry as _tel
                rec = _tel.get_recorder()
                if rec is not None:
                    rec.run_id = mgr.run_id
                    rec.event("resume", run_id=mgr.run_id,
                              step=start_step)
                print(f"resumed at step {start_step} "
                      f"(run {mgr.run_id}) from {args.checkpoint_dir}")
    stop = runtime.GracefulShutdown().install() if args.drain else None

    loss = np.float32(np.nan)
    reader = runtime.DeferredMetrics()
    done = start_step
    while done < args.steps:
        n_valid = min(spc, args.steps - done)
        state, metrics = pipe.step_window(state, window, n_valid)
        done += n_valid
        prev = reader.push(metrics, n_valid)
        if prev is not None:
            loss = emit(prev)
        if stop is not None and stop.draining:
            if mgr is not None:
                mgr.save(done, state, block=True)
            print(f"drain: stopping at step {done} ({stop.reason})")
            break
        if mgr is not None:
            mgr.maybe_save(done, state)
    if reader.newest() is not None:
        loss = emit(reader.newest())       # doubles as the pipeline drain
    if mgr is not None:
        if mgr.last_saved != done:
            mgr.save(done, state, block=True)
        mgr.close()
        print(f"checkpoint: step {done} saved under "
              f"{args.checkpoint_dir}")
    if stop is not None:
        stop.uninstall()
    # Input-engine attribution line (bench.py parses loader_stall_pct):
    # the synthetic window is pre-staged on device, so the loop never
    # waits on input; a real-data loader would report its PrefetchLoader
    # stats here (see examples/imagenet).
    print("loader: stall 0.00% (pre-staged synthetic window)")
    # HBM memory ledger (ISSUE 10): one exit-time relower (disk-cached
    # under apex_tpu.cache) feeding the `memory` event, the
    # peak_hbm_bytes gauge, and the health: line's peak-hbm figure.
    try:
        mem = pipe.memory_stats()
        if mem is not None:
            print(f"memory: peak-hbm {mem['peak_bytes'] / 1e6:.1f}MB")
    except Exception as e:                       # pragma: no cover
        print(f"memory: ledger unavailable ({type(e).__name__}: {e})")
    assert np.isfinite(loss), "training diverged"


if __name__ == "__main__":
    main()
