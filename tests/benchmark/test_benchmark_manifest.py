"""BENCHMARK.json against the files it names, and the harness's promise that
a new cell, configuration or metric is files and entries, never an edit."""

import json
import os
import re
import shutil

import pytest

from benchmark import run

ROOT = run.ROOT
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
#: the driver holds a layer's name to this (it refused "cache + build")
LAYER_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [c["name"] for c in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
#: program knobs a cell may never pass (they stay at the checkout's default)
KNOBS = ["steps-per-call", "steps_per_call", "fused-bn", "fused_bn",
         "pallas-conv", "pallas_conv", "fused-loss", "fused_loss",
         "aot-warmup", "aot_warmup", "bucketed", "attention_impl",
         "block_q", "block_k", "row_block"]


def _reported(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert len(MANIFEST["command"]) <= 32
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path)), path
    assert any(arg.startswith(MANIFEST["paths"][0] + "/")
               for arg in MANIFEST["command"])
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + list(E2E) + list(PER_LAYER))
    assert len(names) == len(set(names)), "a name is used twice"
    assert all(NAME.match(n) for n in names)
    assert 2 <= len(CELLS) <= 24 and 1 <= len(MANIFEST["configs"]) <= 24


def test_run_seconds_fits_the_check_with_24_cells():
    seconds = MANIFEST["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_config_is_used_and_under_paths():
    used = {c["config"] for c in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["name"] in used, f"{c['name']} has no cell"
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert c["source"].startswith("http")
        assert len(c["why"]) <= 200
        on_file = json.load(open(os.path.join(ROOT, c["file"])))
        assert on_file["name"] == c["name"]
        assert on_file["reduced"] == c["reduced"]
        assert on_file["tolerance"]["reason"], "a tolerance needs its reason"


def test_four_chip_cells_are_at_most_a_quarter():
    four = [c for c in MANIFEST["workloads"] if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in MANIFEST["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports_enough(cell):
    plan = run.resolve(cell)
    assert callable(plan.family.build)
    assert plan.traffic["batch_per_chip"] % plan.traffic["check_sample"] == 0
    entry = next(c for c in MANIFEST["workloads"] if c["name"] == cell)
    assert len(entry["why"]) <= 200
    e2e = [m["name"] for m in plan.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2 and plan.per_layer
    for m in plan.per_layer:
        assert m["moves"] in e2e, (
            f"{m['name']} is reported in {cell}, {m['moves']} is not")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_passes_no_program_knob(cell):
    plan = run.resolve(cell)
    texts = [json.dumps(plan.config), json.dumps(plan.traffic),
             open(plan.family.__file__).read()]
    for knob in KNOBS:
        assert not any(knob in t for t in texts), f"{cell} pins {knob}"


@pytest.mark.parametrize("name", sorted(E2E))
def test_end_to_end_metric(name):
    m = E2E[name]
    reader = run._load(ROOT, "end_to_end", name)
    assert (reader.UNIT, reader.BETTER) == (m["unit"], m["better"])
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert callable(reader.compute)
    assert all(w in CELLS for w in m.get("workloads", []))


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_per_layer_metric(name):
    m = PER_LAYER[name]
    reader = run._load(ROOT, "layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.MOVES) == (
        m["layer"], m["unit"], m["better"], m["moves"])
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert LAYER_NAME.match(m["layer"]), m["layer"]
    assert "bound" not in m and m["moves"] in E2E
    assert all(w in CELLS for w in m.get("workloads", []))
    assert any(_reported(m, c) and _reported(E2E[m["moves"]], c)
               for c in CELLS)


def test_setup_bound_and_peaks_table():
    assert E2E["setup_s"]["bound"] == 0.1
    peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))
    v5e = peaks["by_device_kind"]["TPU v5 lite"]
    assert peaks["source"] and v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["int8_ops_per_s"] == 393e12


def test_new_cell_config_and_metric_are_files_and_entries(tmp_path):
    """Drop a configuration, a traffic mix and a per-layer metric into a copy
    as new files; ``run.py`` finds all three by name, unedited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    manifest = json.loads(json.dumps(MANIFEST))
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "gpt2_small_o2.json")))
    config["name"] = "gpt2_medium_o2"
    config["model"].update(n_embd=1024, n_layer=24, n_head=16, n_inner=4096)
    new = os.path.join(root, "benchmark")
    json.dump(config, open(os.path.join(
        new, "configs", "gpt2_medium_o2.json"), "w"))
    json.dump({"batch_per_chip": 4, "seq": 512, "check_sample": 2},
              open(os.path.join(new, "traffic", "seq512.json"), "w"))
    with open(os.path.join(new, "layer_metrics", "loss_drop.py"), "w") as f:
        f.write('LAYER, UNIT, BETTER, MOVES = "train_step", "nats", '
                '"higher", "samples_per_s"\n\n\ndef compute(ctx):\n'
                '    loss = ctx.step_metrics["loss"]\n'
                '    return loss[0] - loss[-1]\n')
    manifest["configs"].append({
        "name": "gpt2_medium_o2", "source": "https://example.org",
        "file": "benchmark/configs/gpt2_medium_o2.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "gpt2_medium_o2.seq512", "config": "gpt2_medium_o2",
        "traffic": "seq512", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "loss_drop", "unit": "nats", "better": "higher",
        "source": "program_counter", "layer": "train_step",
        "moves": "samples_per_s", "workloads": ["gpt2_medium_o2.seq512"]})
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))

    plan = run.resolve("gpt2_medium_o2.seq512", root)
    assert plan.config["model"]["n_layer"] == 24 and plan.traffic["seq"] == 512
    assert "loss_drop" in [m["name"] for m in plan.per_layer]
    assert "collective_ops" not in [m["name"] for m in plan.per_layer]
    old = run.resolve("gpt2_small_o2.seq1024", root)
    assert "loss_drop" not in [m["name"] for m in old.per_layer]
    with pytest.raises(SystemExit):
        run.resolve("gpt2_medium_o2.seq999", root)
