"""The harness end to end on test-only cells at tiny widths, through the
Python entry with ``allow_cpu=True`` (the command has no such flag), and its
refusal to measure off the TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run

ROOT = run.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark whose manifest has two tiny cells: gpt_tiny
    widths on one device, ResNet-18 at 64x64 over the eight virtual ones."""
    root = str(tmp_path_factory.mktemp("tiny"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    gpt = json.load(open(os.path.join(bench, "configs", "gpt2_small_o2.json")))
    gpt["model"].update(vocab_size=1024, n_positions=64, n_embd=128,
                        n_layer=2, n_head=4, n_inner=256)
    resnet = json.load(open(os.path.join(bench, "configs", "resnet50_o2.json")))
    resnet["model"].update(arch="resnet18", image_size=64)
    # bf16 against float32 at these widths, BatchNorm over 4 x (2 x 2) values
    loose = dict(resnet["tolerance"], loss_rel=0.02, grad_cos_min=0.98,
                 grad_norm_ratio=[0.9, 1.1], leaf_rel=0.5, leaf_abs=0.01)
    files = {"configs/gpt_tiny.json": dict(gpt, tolerance=loose),
             "configs/resnet18_tiny.json": dict(resnet, tolerance=loose),
             "traffic/s32.json": {"batch_per_chip": 4, "seq": 32,
                                  "check_sample": 2},
             "traffic/b4.json": {"batch_per_chip": 4, "check_sample": 4}}
    for name, content in files.items():
        json.dump(content, open(os.path.join(bench, name), "w"))
    manifest["configs"] = [
        {"name": n, "source": "https://example.org", "reduced": [],
         "file": f"benchmark/configs/{n}.json", "why": "test"}
        for n in ("gpt_tiny", "resnet18_tiny")]
    manifest["workloads"] = [
        {"name": "gpt_tiny.s32", "config": "gpt_tiny", "traffic": "s32",
         "chips": 1, "why": "test"},
        {"name": "resnet18_tiny.b4", "config": "resnet18_tiny",
         "traffic": "b4", "chips": 8, "why": "test"}]
    for m in manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["resnet18_tiny.b4"]
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture(autouse=True)
def _cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


@pytest.mark.parametrize("cell,samples_per_step", [("gpt_tiny.s32", 4 * 32),
                                                   ("resnet18_tiny.b4", 32)])
def test_untraced_run_prints_the_contract(tiny_root, cell, samples_per_step):
    result = run.run_cell(cell, seed=3, seconds=1.0, trace=False,
                          allow_cpu=True, root=tiny_root)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(result["metrics"]) == {m["name"] for m in manifest["end_to_end"]}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    # a step of these cells takes milliseconds to seconds on the CPU
    per_s = result["metrics"]["samples_per_s"]["value"]
    assert samples_per_step / 60 < per_s < samples_per_step / 1e-4
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)


def test_a_stall_moves_stall_pct_and_not_samples_per_s():
    """One-chip machines stall for a second or two now and then: the
    throughput is a median over the window's dispatches, and what a median
    does not see is its own per-layer metric."""
    import types

    per_s = run._load(ROOT, "end_to_end", "samples_per_s").compute
    stall = run._load(ROOT, "layer_metrics", "stall_pct").compute
    steady = types.SimpleNamespace(k=1, samples_per_step=8192,
                                   intervals=[0.070] * 280)
    stalled = types.SimpleNamespace(k=1, samples_per_step=8192,
                                    intervals=[0.070] * 250 + [2.17])
    assert per_s(steady) == per_s(stalled) == pytest.approx(8192 / 0.070)
    assert stall(steady) == pytest.approx(0.0, abs=1e-9)
    assert stall(stalled) == pytest.approx(100 * 2.1 / (250 * 0.07 + 2.17))
    four = types.SimpleNamespace(k=4, samples_per_step=256, intervals=[2.0])
    assert per_s(four) == pytest.approx(4 * 256 / 2.0)


def test_same_seed_same_traffic_other_seed_other_traffic(tiny_root):
    plan = run.resolve("gpt_tiny.s32", tiny_root)
    import jax
    import numpy as np

    build = lambda seed: plan.family.build(plan.config, plan.traffic,
                                           jax.devices()[:1], seed)
    a, b, c = build(5), build(5), build(6)
    same = lambda x, y: all(np.array_equal(p, q) for p, q in zip(
        jax.tree_util.tree_leaves(x), jax.tree_util.tree_leaves(y)))
    assert same(a.window, b.window) and same(a.state.params, b.state.params)
    assert not same(a.window, c.window)
    assert not same(a.state.params, c.state.params)
    assert a.samples_per_step == 4 * 32 and a.k == 1


def test_traced_run_without_a_device_operation_is_refused(tiny_root):
    """On the CPU the profiler's trace has no device plane: the traced run
    must refuse, not report a host time under a device metric's name."""
    with pytest.raises(SystemExit, match="no device"):
        run.run_cell("gpt_tiny.s32", seed=3, seconds=0.5, trace=True,
                     allow_cpu=True, root=tiny_root)
    out = os.path.join(tiny_root, "benchmark", "out", "gpt_tiny.s32")
    events = [json.loads(line) for line in open(
        os.path.join(out, "telemetry.jsonl"))]
    assert sum(e["kind"] == "window" for e in events) >= 2


@pytest.mark.parametrize("how", ["python3 benchmark/run.py",
                                 "python3 -m benchmark.run"])
def test_command_refuses_to_measure_off_the_tpu(how):
    """No CPU fallback and no result line, whichever way it is started."""
    proc = subprocess.run(
        [sys.executable] + how.split()[1:] + [
            "--workload", "gpt2_small_o2.seq128", "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "platform='cpu'" in proc.stderr
    assert "{" not in proc.stdout


def test_command_fails_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    paths there is no program to measure: non-zero, no result line."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2_small_o2.seq128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and "{" not in proc.stdout


def test_unknown_workload_is_an_error():
    with pytest.raises(SystemExit, match="no workload"):
        run.run_cell("nope", 1, 1.0, False, allow_cpu=True)
