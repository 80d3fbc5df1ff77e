"""``benchmark/phase_reduce.py``: from the compiled step's scopes and the
profiler's ``XLA Ops`` events to device time per phase of a training step."""

import collections
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import phase_reduce, run

DATA = os.path.join(run.ROOT, "benchmark", "testdata")
READERS = {"forward_ms_per_step": ["forward"],
           "backward_ms_per_step": ["backward"],
           "optimizer_ms_per_step": ["optimizer"],
           "amp_ms_per_step": ["cast", "scaler"],
           "unattributed_ms_per_step": ["other"],
           "allreduce_scope_ms_per_step": ["allreduce"]}


# as jax 0.9.0 spells them in ``compiled().as_text()``
@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/while/body/closed_call/jvp(apex.forward)/ln1/dot_general",
     "forward"),
    ("jit(step)/while/body/closed_call/transpose(jvp(apex.forward))/ln1/mul",
     "backward"),
    # the forward, rematerialised for the backward: what the backward costs
    ("jit(step)/transpose(jvp(apex.forward))/checkpoint/rematted_computation/"
     "tanh", "backward"),
    # the gradient all-reduce under shard_map's vma tracking is autodiff's
    ("jit(step)/shard_map/transpose(jvp(apex.forward))/psum_invariant",
     "backward"),
    ("jit(step)/jvp(apex.forward)/transpose", "forward"),
    ("jit(step)/jvp(apex.cast)/convert_element_type", "cast"),
    ("jit(step)/transpose(jvp(apex.cast))/convert_element_type", "cast"),
    ("jit(step)/shard_map/apex.allreduce/psum", "allreduce"),
    ("jit(step)/apex.scaler/jit(_unscale_fp32)/mul", "scaler"),
    ("jit(step)/apex.optimizer/sub", "optimizer"),
    ("jit(step)/shard_map/apex.metrics/psum_invariant", "other"),
    ("jit(step)/apex_tpu.ddp.allreduce/psum", "other"),
    ("state.params['w1']", "other"),
])
def test_phase_of_an_op_name(op_name, phase):
    hlo = (f'  %fusion.7 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fc, '
           f'metadata={{op_name="{op_name}" stack_frame_id=3}}\n')
    assert phase_reduce.scopes_of(hlo) == {"fusion.7": phase}


def test_scopes_of_a_compiled_step():
    """Every instruction of the CPU-compiled text of a tiny O2 step with a
    microbatch scan gets a phase, and every phase one device has is there."""
    from apex_tpu import training

    def loss_fn(p, batch):
        h = jnp.tanh(batch.astype(p["w1"].dtype) @ p["w1"])
        return jnp.mean((h @ p["w2"]).astype(jnp.float32) ** 2)

    init_fn, step_fn = training.make_train_step(
        loss_fn, training.adam(1e-3), opt_level="O2", loss_scale="dynamic",
        accum_steps=2)
    state = init_fn({"w1": jnp.ones((16, 32)), "w2": jnp.ones((32, 8))})
    hlo = jax.jit(step_fn).lower(state, jnp.ones((4, 16))).compile().as_text()
    scope_of = phase_reduce.scopes_of(hlo)
    instructions = [line.split(" = ")[0].split()[-1].lstrip("%")
                    for line in hlo.splitlines()
                    if " = " in line and line.startswith("  ")]
    assert len(instructions) > 100
    assert set(instructions) == set(scope_of)
    found = collections.Counter(scope_of.values())
    assert set(found) == {"forward", "backward", "optimizer", "cast",
                          "scaler", "other"}
    assert phase_reduce._SCOPED.search(hlo)
    # the metadata-less instructions and the computations' parameters
    assert scope_of["param_0"] == "other"


def test_attribute_counts_only_whole_runs_and_sums_to_their_busy_time():
    scope_of = {"fusion.1": "forward", "fusion.2": "backward",
                "all-reduce-start": "allreduce", "fusion.3": "optimizer"}
    step = [(0, 10, "%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop"),
            (10, 40, "%fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kOutput"),
            (40, 42, "%all-reduce-start = f32[8]{0} all-reduce-start(%x)"),
            (42, 47, "%copy-done = f32[8]{0} copy-done(%copy-start)"),
            (50, 60, "%fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop")]
    runs = [(off, off + 70) for off in (0, 100, 200, 300)]
    events = [(a + off, b + off, name) for off, _ in runs
              for a, b, name in step]
    # another program's operation between two runs: same name, not this module
    events.append((175, 195, "%fusion.1 = f32[2]{0} fusion(%q), kind=kLoop"))
    # an operation that runs over the end of its execution is cut, not whole
    events.append((265, 275, "%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop"))
    got = phase_reduce.attribute(events, runs, scope_of)
    assert got == pytest.approx({
        "forward": 20e-9, "backward": 60e-9, "allreduce": 4e-9,
        "optimizer": 20e-9, "other": 10e-9, "cast": 0, "scaler": 0})
    assert sum(got.values()) == pytest.approx(2 * 57e-9)
    assert set(got) == set(phase_reduce.PHASES)
    assert sum(phase_reduce.attribute(events, runs[:2], scope_of).values()) == 0


@pytest.fixture
def traced_cell(tmp_path, monkeypatch):
    """A ``ctx`` whose trace is the one-chip trace recorded on the v5e, laid
    out as ``run.py`` writes it, under a benchmark directory of its own."""
    monkeypatch.setattr(phase_reduce, "__file__",
                        str(tmp_path / "phase_reduce.py"))
    monkeypatch.setattr(phase_reduce, "_memo", {})
    trace = tmp_path / "out" / "cell" / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "tiny_1chip.xplane.pb"),
                trace / "host.xplane.pb")
    read = lambda ctx: {name: run._load(run.ROOT, "layer_metrics",
                                        name).compute(ctx) for name in READERS}
    return types.SimpleNamespace(workload="cell", k=1, hlo="", read=read,
                                 out=tmp_path / "out" / "cell")


def test_readers_split_the_recorded_trace_by_the_scopes_of_the_module(
        traced_cell, capsys):
    """The recorded step (``testdata/record.py``) is a prefetch (15,973 + 79
    ns over the six whole executions), two matmul-tanh fusions (158,231 ns)
    and a third with the tail (75,813 ns); the scopes are made up."""
    meta = 'metadata={op_name="jit(step)/%s/dot_general" stack_frame_id=1}'
    traced_cell.hlo = "\n".join([
        "ENTRY %main.1 (p: bf16[1024,1024]) -> bf16[1024,1024] {",
        "  %copy-start = (bf16[8]{0}, u32[]) copy-start(%p)",
        "  %copy-done = bf16[8]{0} copy-done(%copy-start)",
        "  %convolution_tanh_fusion.2 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % "jvp(apex.forward)",
        "  %convolution_tanh_fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, "
        + meta % "transpose(jvp(apex.forward))",
        "  ROOT %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, "
        + meta % "apex.optimizer", "}"])
    got = traced_cell.read(traced_cell)
    said = capsys.readouterr().out
    assert said.startswith("phases, ms per step: forward 0.01") and (
        said.count("\n") == 1), "one line, from one parse for six readers"
    assert got["allreduce_scope_ms_per_step"] == got["amp_ms_per_step"] == 0
    assert got["backward_ms_per_step"] == pytest.approx(
        got["forward_ms_per_step"], rel=0.3)
    assert got["forward_ms_per_step"] + got["backward_ms_per_step"] == (
        pytest.approx(158231e-6 / 6))
    assert got["optimizer_ms_per_step"] == pytest.approx(75813e-6 / 6)
    assert got["unattributed_ms_per_step"] == pytest.approx(16052e-6 / 6)
    assert sum(got.values()) == pytest.approx(250096e-6 / 6)
    detail = json.load(open(traced_cell.out / "phases.json"))
    assert set(detail) == set(phase_reduce.PHASES)
    assert detail["forward"]["ms_per_step"] == got["forward_ms_per_step"]
    assert detail["other"]["by_opcode"] == pytest.approx(
        {"copy-done": 15973e-6 / 6, "copy-start": 79e-6 / 6})
    assert detail["optimizer"]["by_opcode"] == {
        "fusion/output": got["optimizer_ms_per_step"]}
    label, ms = detail["backward"]["largest"][0]
    assert label.startswith("convolution_tanh_fusion fusion/output")
    assert ms == pytest.approx(got["backward_ms_per_step"])
    assert [label for label, _ in detail["other"]["largest"]] == [
        "copy-done -> bf16[1024,1024]",
        "copy-start -> (bf16[1024,1024], bf16[1024,1024], u32[])"]


def test_a_step_executable_without_scopes_reports_no_phase_and_says_why(
        traced_cell, capsys):
    """What a compile cache written by a checkout without the scopes hands
    back, and what the parent commit's program is: never a split."""
    traced_cell.hlo = (
        "  %convolution_tanh_fusion.2 = bf16[8]{0} fusion(%p), kind=kOutput, "
        'metadata={op_name="jit(step)/jvp(loss_fn)/dot_general"}\n')
    assert traced_cell.read(traced_cell) == dict.fromkeys(READERS)
    said = capsys.readouterr().out
    assert said.count("\n") == 1, "said once, not once per reader"
    assert "no phase is reported" in said and "compile cache" in said
    assert "JAX_COMPILATION_CACHE_DIR" in said
    assert not os.path.exists(traced_cell.out / "phases.json")
