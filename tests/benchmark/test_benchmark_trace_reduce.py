"""``benchmark/trace_reduce.py`` on small traces recorded on the chip
(``benchmark/testdata/record.py``), against numbers worked out by hand from
a dump of their events, and on instruction texts copied from real traces."""

import os

import pytest

from benchmark import run, trace_reduce

DATA = os.path.join(run.ROOT, "benchmark", "testdata")

#: instruction texts as ``XLA Ops`` events name them, cut in the middle
FUSION = ('%fusion.2290 = bf16[50257,768]{1,0:T(8,128)(2,1)S(1)} fusion(f32[8192'
          ',50257]{1,0:T(8,128)} %transpose_jvp___.2, bf16[8192,768]{1,0:T(8,128)'
          '(2,1)} %copy-done.102), kind=kOutput, calls=%fused_computation.1')
MOSAIC = ('%attention.106 = (bf16[8,12,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16['
          '8,12,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}) custom-call(bf16[8,12,1024,6'
          '4]{3,2,1,0:T(8,128)(2,1)} %x), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={bf16[8,12,1024,64]{3,2,1,0}}')
BITCAST = ('%custom-call.7 = f32[8192,1]{1,0:T(8,128)} custom-call(f32[2048,1]{1,'
           '0:T(8,128)S(1)} %slice-done.1580), custom_call_target="ConcatBitcast"')
PERMUTE = ('%collective-permute-start.3 = (f32[64]{0:T(128)}, f32[64]{0:T(128)}) '
           'collective-permute-start(f32[64]{0:T(128)} %x), channel_id=5, '
           'source_target_pairs={{0,1},{1,2},{2,3},{3,0}}')
ALLREDUCE = ('%all-reduce.277 = (bf16[7,7,3,64]{3,2,1,0:T(4,128)(2,1)S(1)}, bf16['
             '1,1000]{1,0:T(2,128)(2,1)S(1)}) all-reduce(bf16[7,7,3,64]{3,2,1,0} '
             '%a, bf16[1,1000]{1,0} %b), channel_id=1, to_apply=%add')
COPY_DONE = ('%copy-done.400 = s32[1,8,8,128]{3,2,1,0:T(8,128)S(1)} copy-done((s3'
             '2[1,8,8,128]{3,2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) %copy-start.400)')


@pytest.mark.parametrize("text,label,what,mosaic,collective", [
    (FUSION, "fusion/output -> bf16[50257,768]", "fusion/output", False, False),
    (MOSAIC, "attention custom-call/mosaic -> (bf16[8,12,1024,64], "
             "bf16[8,12,1024,64])", "custom-call/mosaic", True, False),
    (BITCAST, "custom-call -> f32[8192,1]", "custom-call", False, False),
    (PERMUTE, "collective-permute-start -> (f32[64], f32[64])",
     "collective-permute-start", False, True),
    (ALLREDUCE, "all-reduce -> (bf16[7,7,3,64], bf16[1,1000])", "all-reduce",
     False, True),
    (COPY_DONE, "copy-done -> s32[1,8,8,128]", "copy-done", False, False),
])
def test_describe(text, label, what, mosaic, collective):
    assert trace_reduce.describe(text) == (label, what, mosaic)
    assert trace_reduce._is_collective(what) == collective


def test_union_merges_overlaps_and_touching_intervals():
    total, merged = trace_reduce._union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)])
    assert total == 6 and merged == [[0, 4], [5, 7], [9, 9]]
    assert trace_reduce._clip([(0, 10), (20, 30), (40, 50)], 5, 25) == [
        (5, 10), (20, 25)]


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    jax.profiler.stop_trace()
    (path,) = [os.path.join(base, f) for base, _, files in os.walk(tmp_path)
               for f in files if f.endswith(".xplane.pb")]
    with pytest.raises(SystemExit, match="no device"):
        trace_reduce.reduce(path, k=1)
    with pytest.raises(SystemExit, match="no device"):
        trace_reduce.reduce(None, k=1)


@pytest.fixture(scope="module")
def tiny_1chip():
    return trace_reduce.reduce(os.path.join(DATA, "tiny_1chip.xplane.pb"), k=1)


def test_one_chip_trace_busy_and_window(tiny_1chip):
    """Eight executions of a three-matmul step, the two at the ends left
    out.  From the dump of the events: the six whole executions run from
    45,241,397 ns to 171,942,940 ns; the operations inside add up to
    264,259 ns (six steps of 39.1 to 42.4 us and five small fetch programs
    of 2.8 us)."""
    r = tiny_1chip
    assert r["devices"] == 1 and r["steps"] == 6
    assert r["window_s"] == pytest.approx(0.126701543, abs=2e-9)
    assert r["busy_s"] == pytest.approx(0.000264259, abs=2e-8)
    assert r["custom_call_s"] == 0 and r["collectives"] == 0
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    assert trace_reduce.reduce(os.path.join(DATA, "tiny_1chip.xplane.pb"),
                               k=2)["steps"] == 12


def test_one_chip_trace_operations(tiny_1chip):
    label, per_step, seconds = tiny_1chip["ops"][0]
    assert label == "convolution_tanh_fusion fusion/output -> bf16[1024,1024]"
    # 14,886 + 14,713 + 14,853 + 14,882 + 14,719 + 14,727 and 5 x 11,575 + 11,576
    assert per_step == 2 and seconds == pytest.approx(158231e-9, abs=1e-8)
    assert tiny_1chip["categories"]["fusion/output"] == pytest.approx(
        234044e-9, abs=1e-8)
    assert list(tiny_1chip["categories"]) == [
        "fusion/output", "copy-done", "dynamic-slice", "copy-start"]


def test_one_chip_trace_idle_goes_to_the_host_span_over_it(tiny_1chip):
    """The device idles almost all the time; the longest gap, 21,998,402 ns,
    lies inside the ``bench.fetch`` in which the recording slept 20 ms."""
    idle = tiny_1chip["idle"]
    assert sum(idle.values()) == pytest.approx(
        tiny_1chip["window_s"] - tiny_1chip["busy_s"], abs=1e-12)
    assert idle["bench.fetch"] > 0.021998402
    assert 0 < idle["bench.dispatch"] < 0.002
    assert idle[trace_reduce.IN_PROGRAM] < 1e-7
    assert tiny_1chip["between_programs_s"] == pytest.approx(
        sum(idle.values()) - idle[trace_reduce.IN_PROGRAM], abs=1e-12)


def test_breakdown_and_readers_on_the_one_chip_trace(tiny_1chip):
    import types

    b = trace_reduce.breakdown(tiny_1chip)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].endswith(" x2")
    assert b["idle_gaps"][0][0] == "bench.fetch"
    ctx = types.SimpleNamespace(
        trace=tiny_1chip, chips=1, flops_per_step=3 * 2 * 1024 ** 3,
        peaks={"bf16_flops_per_s": 197e12})
    read = lambda name: run._load(run.ROOT, "layer_metrics", name).compute(ctx)
    assert read("device_ms_per_step") == pytest.approx(264259e-6 / 6, rel=1e-4)
    assert read("device_idle_pct") == pytest.approx(99.7914, abs=1e-3)
    assert read("pallas_ms_per_step") == 0
    assert read("collective_ms_per_step") is None
    assert read("collective_exposed_ms_per_step") is None
    # 6.44 GFLOP in 44.0 us of device time: 74% of the peak while busy
    assert read("busy_mfu_pct") == pytest.approx(74.2, abs=0.5)
    assert read("mfu_pct") == pytest.approx(0.155, abs=0.01)
    assert read("host_gap_ms_per_step") == pytest.approx(126437e-3 / 6, rel=1e-3)


@pytest.fixture(scope="module")
def tiny_4chip():
    return trace_reduce.reduce(os.path.join(DATA, "tiny_4chip.xplane.pb"), k=1)


def test_four_chip_trace_collectives(tiny_4chip):
    """The same step under ``shard_map`` over four chips, with a ``psum`` (a
    synchronous all-reduce, 38.8 to 39.6 us) and a ``ppermute`` (start and
    done operations, 47.7 us from the one's start to the other's end).  On
    the first chip, third execution, from the dump: 39,562 + 1,245 + 46,528
    = 87,335 ns of collective operations, in flight for 87,336 ns; five of
    the six steps are followed by a fetch program with a 4 to 5 us scalar
    all-reduce of its own."""
    r = tiny_4chip
    assert r["devices"] == 4 and r["steps"] == 6
    assert r["collectives"] == pytest.approx(2 + 5 / 6)
    per_step = lambda key: 1e6 * r[key] / r["steps"]
    assert per_step("collective_exposed_s") == pytest.approx(87.3 + 3.6, abs=2.5)
    # nothing overlaps these collectives: in flight = held, but for the
    # nanosecond between a start operation and its done
    assert 0 <= r["collective_s"] - r["collective_exposed_s"] < 1e-7
    assert r["categories"]["all-reduce"] > 6 * 38.8e-6
    assert r["categories"]["collective-permute-done"] > 6 * 45e-6
    assert r["busy_s"] > r["collective_exposed_s"]
    assert r["idle"]["bench.fetch"] > 0.0219


def test_collective_readers_on_the_four_chip_trace(tiny_4chip):
    import types

    ctx = types.SimpleNamespace(trace=tiny_4chip, chips=4)
    read = lambda name: run._load(run.ROOT, "layer_metrics", name).compute(ctx)
    assert read("collective_exposed_ms_per_step") == pytest.approx(0.0892,
                                                                  abs=0.003)
    assert read("collective_ms_per_step") == pytest.approx(
        read("collective_exposed_ms_per_step"), abs=1e-4)
    ctx.hlo = (" %x = f32[2] all-reduce(%a), to_apply=%add\n"
               " %s = (f32[2], f32[2]) collective-permute-start(%x)\n"
               " %d = f32[2] collective-permute-done(%s)\n"
               " %g = f32[8] all-gather-start(%x)\n")
    assert read("collective_ops") == 3 and read("allreduce_ops") == 1
    ctx.hlo = " %f = f32[2] fusion(%a), kind=kLoop"
    assert read("collective_ops") is None and read("allreduce_ops") is None


def test_an_overlapped_collective_is_in_flight_longer_than_it_holds_the_core():
    """Synthetic ops line: a permute started at 10, compute from 12 to 90,
    done from 90 to 100.  In flight 90 ns, exposed 2 + 10 ns."""
    import types

    ev = lambda a, b, name: types.SimpleNamespace(start_ns=a, end_ns=b,
                                                  name=name)
    step = [ev(10, 12, PERMUTE), ev(12, 90, FUSION),
            ev(90, 100, PERMUTE.replace("-start", "-done"))]
    ops = [ev(e.start_ns + off, e.end_ns + off, e.name)
           for off in (0, 1000, 2000, 3000) for e in step]
    modules = [ev(off, off + 110, "jit_step(1)") for off in (0, 1000, 2000, 3000)]
    plane = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Modules", events=modules),
        types.SimpleNamespace(name="XLA Ops", events=ops)])
    r = trace_reduce._reduce_device(plane, spans=[], k=1)
    assert r["steps"] == 2 and r["collectives"] == 1
    assert r["window_s"] == pytest.approx(1110e-9)
    assert r["busy_s"] == pytest.approx(2 * 90e-9)
    assert r["collective_s"] == pytest.approx(2 * 90e-9)
    assert r["collective_exposed_s"] == pytest.approx(2 * 12e-9)
    assert r["idle"][trace_reduce.IN_PROGRAM] == pytest.approx(2 * 20e-9)
    assert r["idle"][trace_reduce.NO_SPAN] == pytest.approx(890e-9)
