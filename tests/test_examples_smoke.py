"""Smoke-run the example entry points (VERDICT r1 weak-#8: the flagship
"examples run unmodified" claim was never CI-verified).

Each example runs in-process via runpy with a tiny synthetic config
(`--synthetic --prof N`-style), mirroring how the reference's L1 harness
drives ``examples/imagenet/main_amp.py``.  The conftest pins the default
device to CPU, so these are fast correctness runs, not benchmarks.
"""

import os
import runpy
import sys

import numpy as np
import pytest

# The example entry points are exercised on-chip by bench.py every round;
# off the fast gate they cost ~5 min of CPU compiles.
pytestmark = pytest.mark.slow

import jax

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run_example(monkeypatch, rel_path, argv):
    path = os.path.join(_ROOT, rel_path)
    monkeypatch.setattr(sys, "argv", [path] + argv)
    monkeypatch.syspath_prepend(_ROOT)
    from apex_tpu.amp import autocast
    try:
        runpy.run_path(path, run_name="__main__")
    finally:
        autocast.shutdown()   # examples may enable O1 globally


@pytest.mark.parametrize("opt_level", ["O0", "O2"])
def test_imagenet_example(monkeypatch, opt_level, capsys):
    _run_example(monkeypatch, "examples/imagenet/main_amp.py", [
        "--synthetic", "--prof", "3", "-b", "8", "--image-size", "32",
        "-a", "resnet18", "--epochs", "1", "--steps-per-epoch", "3",
        "--opt-level", opt_level])
    out = capsys.readouterr().out
    assert "opt_level = " + opt_level in out


def test_imagenet_example_real_data_worker_pool(monkeypatch, tmp_path,
                                                capsys):
    """The real-data input path end to end (ISSUE 3): directory source
    (decode=False descriptors) -> 2-worker window assembly with the
    fused crop/flip/normalize augment -> async device staging -> train
    loop, plus the parseable loader-stall attribution line."""
    import re

    import numpy as np

    rng = np.random.RandomState(0)
    for cls in ("a", "b"):
        d = tmp_path / cls
        d.mkdir()
        for i in range(10):
            np.save(d / f"s{i}.npy",
                    rng.randint(0, 256, (64, 64, 3)).astype(np.uint8))
    _run_example(monkeypatch, "examples/imagenet/main_amp.py", [
        str(tmp_path), "--prof", "2", "-b", "8", "--image-size", "32",
        "-a", "resnet18", "--epochs", "1", "--opt-level", "O2",
        "--workers", "2", "--augment"])
    out = capsys.readouterr().out
    m = re.search(r"loader: stall ([\d.]+)%", out)   # bench._LOADER_RE
    assert m, f"no loader attribution line in:\n{out[-2000:]}"
    assert 0.0 <= float(m.group(1)) <= 100.0


def test_imagenet_example_telemetry_stream(monkeypatch, tmp_path, capsys):
    """ISSUE 5 acceptance shape: the imagenet CPU smoke run emits a
    telemetry stream; ``apex_tpu.prof.timeline`` analyzes it and its
    stall attribution agrees with the 'loader: stall' line the example
    printed (bench.py gates the same agreement every round)."""
    import json
    import re

    tel = str(tmp_path / "run.jsonl")
    _run_example(monkeypatch, "examples/imagenet/main_amp.py", [
        "--synthetic", "--prof", "4", "-b", "8", "--image-size", "32",
        "-a", "resnet18", "--epochs", "1", "--steps-per-epoch", "4",
        "--opt-level", "O2", "--loss-scale", "dynamic",
        "--steps-per-call", "2", "--telemetry", tel])
    out = capsys.readouterr().out
    m = re.search(r"loader: stall ([\d.]+)%", out)
    assert m, f"no loader line in:\n{out[-2000:]}"
    assert "telemetry:" in out
    # ISSUE 6: the watchdog is on by default under --telemetry and a
    # healthy smoke run prints the ok health line at exit
    assert "health: ok (0 alerts)" in out

    from apex_tpu.prof import timeline
    events = timeline.load_events(tel)
    a = timeline.analyze(events)
    assert a["steps"] == 4 and a["windows"] == 2
    # stall attribution agrees with the printed number (same snapshot;
    # the synthetic pool never waits on input, so both are 0.0)
    assert abs(a["attribution"]["loader_stall_pct"]
               - float(m.group(1))) <= 2.0
    # the stream is valid JSONL with a summary and a run header
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "run" and kinds[-1] == "summary"
    assert "window" in kinds and "metrics" in kinds
    # chrome export round-trips
    chrome = str(tmp_path / "trace.json")
    from apex_tpu import telemetry
    assert telemetry.to_chrome_trace(events, chrome) > 0
    json.load(open(chrome))


def test_imagenet_example_sync_bn(monkeypatch, capsys):
    _run_example(monkeypatch, "examples/imagenet/main_amp.py", [
        "--synthetic", "--prof", "2", "-b", "8", "--image-size", "32",
        "-a", "resnet18", "--epochs", "1", "--steps-per-epoch", "2",
        "--opt-level", "O2", "--sync_bn"])


def test_dcgan_example_multi_loss(monkeypatch):
    """The multi-model / multi-loss O1 path (3 loss scalers), default
    (step-pipelined) mode: the whole GAN iteration — both D backwards,
    the G phase, and all three scaler machines — runs through
    runtime.StepPipeline."""
    _run_example(monkeypatch, "examples/dcgan/main_amp.py", [
        "--batchSize", "8", "--ngf", "8", "--ndf", "8",
        "--iters-per-epoch", "2", "--niter", "1", "--steps-per-call", "2"])


def test_dcgan_example_multi_loss_imperative(monkeypatch):
    """The reference-parity imperative surface (amp.initialize with
    num_losses=3, scale_loss loss_id=0/1/2, FusedAdam.step — reference
    dcgan/main_amp.py:214-253)."""
    _run_example(monkeypatch, "examples/dcgan/main_amp.py", [
        "--batchSize", "8", "--ngf", "8", "--ndf", "8",
        "--iters-per-epoch", "2", "--niter", "1", "--imperative"])


def test_imagenet_example_steps_per_call(monkeypatch, capsys):
    """The K-step device loop through the example CLI (--prof rounds up
    to whole calls; the ragged-tail path is covered by
    tests/test_runtime.py on the stage_windows protocol)."""
    _run_example(monkeypatch, "examples/imagenet/main_amp.py", [
        "--synthetic", "--prof", "5", "-b", "8", "--image-size", "32",
        "-a", "resnet18", "--epochs", "1", "--steps-per-epoch", "6",
        "--opt-level", "O2", "--steps-per-call", "2", "--print-freq", "2"])
    out = capsys.readouterr().out
    assert "done" in out


def test_distributed_example(monkeypatch):
    """SPMD DDP example over a 4-device CPU mesh."""
    cpus = jax.devices("cpu")[:4]
    orig_devices = jax.devices
    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **kw: orig_devices(*a, **kw) if a or kw else cpus)
    _run_example(monkeypatch,
                 "examples/simple/distributed/distributed_data_parallel.py",
                 [])


@pytest.mark.parametrize("name", ["lenet", "user_annotation",
                                  "custom_func_module", "end_to_end",
                                  "jit_function", "apex_ops"])
def test_prof_examples(monkeypatch, name, tmp_path):
    """The pyprof-examples analog (reference apex/pyprof/examples/)."""
    argv = [str(tmp_path / "trace")] if name == "end_to_end" else []
    _run_example(monkeypatch, f"examples/prof/{name}.py", argv)


@pytest.mark.parametrize("name,argv", [
    ("imagenet", ["-m", "resnet18", "-b", "4", "--image-size", "32"]),
    ("operators", []),
])
def test_prof_examples_with_args(monkeypatch, name, argv, tmp_path):
    """Round-4 recipes: imagenet-scale profiling CLI (reference
    pyprof/examples/imagenet/) and the operator sweep + start/stop window
    (operators.py + simple.py)."""
    if name == "operators":
        argv = [str(tmp_path / "trace")]
    _run_example(monkeypatch, f"examples/prof/{name}.py", argv)


def test_lm_example(monkeypatch, capsys):
    """GPT causal-LM example (flash attention path, fully-jitted step)."""
    _run_example(monkeypatch, "examples/lm/main_amp.py", [
        "--synthetic", "--steps", "2", "-b", "2", "--seq-len", "33",
        "--hidden", "32", "--layers", "1", "--heads", "2",
        "--vocab", "128", "--opt-level", "O2"])
    out = capsys.readouterr().out
    assert "opt_level = O2" in out


def test_lm_example_granite_hybrid(monkeypatch, capsys):
    """The same loop over the Mamba-2/attention hybrid: eight layers are
    five mamba, one attention, two mamba."""
    _run_example(monkeypatch, "examples/lm/main_amp.py", [
        "--synthetic", "--steps", "2", "-b", "2", "--seq-len", "33",
        "--hidden", "32", "--layers", "8", "--heads", "2", "--kv-heads", "1",
        "--vocab", "128", "--opt-level", "O2", "--loss-scale", "dynamic",
        "--model", "granite-hybrid"])
    out = capsys.readouterr().out
    assert "GraniteHybrid 8L/32H" in out and "loss_scale 65536" in out
    with pytest.raises(SystemExit, match="granite-hybrid runs unsharded"):
        _run_example(monkeypatch, "examples/lm/main_amp.py", [
            "--synthetic", "--model", "granite-hybrid", "--window", "8"])


def test_lm_example_lfm2_moe(monkeypatch, capsys):
    """The same loop over the LFM2-MoE block: five layers are one dense conv
    layer, then attention and three convs with routed experts; the model's
    state (selection bias, load counts) rides through ``make_train_step``."""
    _run_example(monkeypatch, "examples/lm/main_amp.py", [
        "--synthetic", "--steps", "2", "-b", "2", "--seq-len", "33",
        "--hidden", "32", "--layers", "5", "--heads", "2", "--kv-heads", "1",
        "--vocab", "128", "--opt-level", "O2", "--loss-scale", "dynamic",
        "--model", "lfm2-moe"])
    out = capsys.readouterr().out
    assert "Lfm2Moe 5L/32H" in out and "loss_scale 65536" in out
    with pytest.raises(SystemExit, match="lfm2-moe runs unsharded"):
        _run_example(monkeypatch, "examples/lm/main_amp.py", [
            "--synthetic", "--model", "lfm2-moe", "--window", "8"])


def test_lm_example_nemotron_h(monkeypatch, capsys):
    """The same loop over the Nemotron-H block: eleven layers are five
    Mamba-2 mixers, five latent expert layers and one attention, each alone
    in its layer; the model's state (correction bias, load counts, rows
    computed) rides through ``make_train_step``."""
    _run_example(monkeypatch, "examples/lm/main_amp.py", [
        "--synthetic", "--steps", "2", "-b", "2", "--seq-len", "33",
        "--hidden", "32", "--layers", "11", "--heads", "2", "--kv-heads", "1",
        "--vocab", "128", "--opt-level", "O2", "--loss-scale", "dynamic",
        "--model", "nemotron-h"])
    out = capsys.readouterr().out
    assert "NemotronH 11L/32H" in out and "loss_scale 65536" in out
    with pytest.raises(SystemExit, match="nemotron-h runs unsharded"):
        _run_example(monkeypatch, "examples/lm/main_amp.py", [
            "--synthetic", "--model", "nemotron-h", "--window", "8"])


def test_lm_example_smallthinker(monkeypatch, capsys):
    """The same loop over the SmallThinker block: a global layer without
    positions, then three window layers with rotary positions, each with
    ReGLU experts whose softmax router reads the attention's normed input;
    the load counts ride through ``make_train_step`` as model state."""
    _run_example(monkeypatch, "examples/lm/main_amp.py", [
        "--synthetic", "--steps", "2", "-b", "2", "--seq-len", "33",
        "--hidden", "32", "--layers", "4", "--heads", "4", "--kv-heads", "2",
        "--vocab", "128", "--opt-level", "O2", "--loss-scale", "dynamic",
        "--model", "smallthinker"])
    out = capsys.readouterr().out
    assert "SmallThinker 4L/32H" in out and "loss_scale 65536" in out
    with pytest.raises(SystemExit, match="smallthinker runs unsharded"):
        _run_example(monkeypatch, "examples/lm/main_amp.py", [
            "--synthetic", "--model", "smallthinker", "--window", "8"])


def test_lm_example_sequence_parallel(monkeypatch):
    """GPT over a 2-way sp mesh with ring attention."""
    _run_example(monkeypatch, "examples/lm/main_amp.py", [
        "--synthetic", "--steps", "2", "-b", "2", "--seq-len", "33",
        "--hidden", "32", "--layers", "1", "--heads", "2",
        "--vocab", "128", "--sp", "2", "--attention", "ring"])


def test_lm_example_fused_loss_parity(monkeypatch, capsys):
    """ISSUE 7 satellite: the contrib fused softmax-xentropy (the lm
    example's default) must produce the SAME loss trajectory as the
    --no-fused-loss log_softmax reference composition — its vocab-sized
    logits are the kernel's textbook case, and a trajectory match over
    real update steps pins forward AND backward parity."""
    import re

    argv = ["--synthetic", "--steps", "2", "-b", "2", "--seq-len", "33",
            "--hidden", "32", "--layers", "1", "--heads", "2",
            "--vocab", "128", "--opt-level", "O2", "--smoothing", "0.1"]
    _run_example(monkeypatch, "examples/lm/main_amp.py", argv)
    fused = [float(v) for v in
             re.findall(r"loss ([\d.]+)", capsys.readouterr().out)]
    _run_example(monkeypatch, "examples/lm/main_amp.py",
                 argv + ["--no-fused-loss"])
    ref = [float(v) for v in
           re.findall(r"loss ([\d.]+)", capsys.readouterr().out)]
    assert fused and len(fused) == len(ref)
    np.testing.assert_allclose(fused, ref, atol=2e-3)


def test_imagenet_example_unfused_flags(monkeypatch, capsys):
    """--no-fused-loss/--no-aot-warmup keep the plain log_softmax +
    cold-compile surface alive."""
    _run_example(monkeypatch, "examples/imagenet/main_amp.py", [
        "--synthetic", "--prof", "2", "-b", "8", "--image-size", "32",
        "-a", "resnet18", "--epochs", "1", "--steps-per-epoch", "2",
        "--opt-level", "O2", "--no-fused-loss", "--no-aot-warmup"])
    out = capsys.readouterr().out
    assert "done" in out


@pytest.mark.parametrize("zero", [2, 3])
def test_mesh_example(monkeypatch, capsys, zero):
    """The mesh-frontend flagship: plan declaration, ZeRO sharding,
    AOT-warmed pipeline, state-bytes ledger (ISSUE 12)."""
    cpus = jax.devices("cpu")[:4]
    orig_devices = jax.devices
    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **kw: orig_devices(*a, **kw) if a or kw else cpus)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    _run_example(monkeypatch, "examples/simple/mesh/fsdp_train.py",
                 ["--zero", str(zero), "--steps", "8",
                  "--steps-per-call", "4", "--fsdp", "4", "--batch", "4"])
    out = capsys.readouterr().out
    assert "done" in out
    assert "ratio" in out
    if zero == 3:
        assert "0.25" in out          # params+state divided 4 ways
