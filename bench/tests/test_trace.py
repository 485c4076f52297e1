"""The reduction from a profiler trace to what the per-layer metrics
read: device busy intervals, time per jitted module and per Pallas
kernel, and idle gaps labelled by the harness's host spans."""
import pathlib

import pytest
from jax.profiler import ProfileData

from bench import harness, trace

# trace clock in ns; bench.mark opens at 500 ns, read as 100 s on the host
MARK_HOST = 100.0
SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 7500000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 9600000 duration_ps: 200000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 9600000 duration_ps: 200000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__unknown(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__unknown(8)" } }
  event_metadata { key: 6 value { id: 6 name: "jit__argmax(9)" } }
  event_metadata { key: 3 value { id: 3
    name: "%while.5 = (s32[], bf16[16,1,3584]) while(%tuple.1)" } }
  event_metadata { key: 4 value { id: 4
    name: "%paged_decode_attention.9 = bf16[16,4,7,128] custom-call(%a)" } }
  event_metadata { key: 5 value { id: 5
    name: "%paged_chunk_prefill_attention.7 = bf16[1,4,224,128] custom-call(%b)" } }
  event_metadata { key: 7 value { id: 7
    name: "%reduce.2.remat = s32[16] reduce(%c)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1000 }
    events { metadata_id: 2 offset_ps: 900000 duration_ps: 5200000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 6400000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 7400000 duration_ps: 2500000 }
    events { metadata_id: 5 offset_ps: 7500000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.mark" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.stamp" } }
  event_metadata { key: 4 value { id: 4 name: "bench.admit" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(decode_step)" } }
}
'''


def _host(ns):
    return MARK_HOST + (ns - 500) * 1e-9


@pytest.fixture(scope="module")
def summary():
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return trace.reduce(pd.planes, MARK_HOST, _host(10_500), MARK_HOST)


def test_busy_is_the_union_of_device_ops(summary):
    # the while op [1000, 5000) holds the kernel [2000, 5000); [8000,
    # 9000) and [9600, 9800) apart
    assert summary.busy_s == pytest.approx(5200e-9, rel=1e-6)
    assert summary.window_s == pytest.approx(10_000e-9, rel=1e-6)
    assert summary.busy == [pytest.approx([_host(1000), _host(5000)]),
                            pytest.approx([_host(8000), _host(9000)]),
                            pytest.approx([_host(9600), _host(9800)])]


def test_launches_are_named_by_the_kernel_they_run(summary):
    assert summary.launches("decode") == [pytest.approx(5000e-9)]
    assert summary.launches("prefill") == [pytest.approx(2000e-9)]
    assert summary.launches("jit__argmax") == [pytest.approx(200e-9)]
    assert summary.kernel_s("paged_decode") == pytest.approx(3000e-9)
    assert summary.kernel_s("paged_chunk_prefill") == pytest.approx(1000e-9)
    assert summary.kernel_s("qkv_matmul") == 0


def test_op_names_drop_the_instruction_and_numbering():
    assert trace.op_name("%copy.76.remat = bf16[4] copy(%x)") == "copy"
    assert trace.op_name("%fusion.89 = bf16[32] fusion(%y)") == "fusion"
    assert trace.op_name(
        "%paged_decode_attention.9 = bf16[16] custom-call(%z)") == \
        "paged_decode_attention"
    assert trace.op_name("%constant_dynamic-slice_fusion.12 = bf16[1]") == \
        "constant_dynamic-slice_fusion"


def test_idle_gaps_labelled_by_the_host_span(summary):
    gaps = summary.gaps()
    # [5000, 8000) under admit, [9800, 10500) past the last span,
    # [9000, 9600) under step 2, [500, 1000) before step 1
    assert [g[0] for g in gaps] == ["admit", "none", "step", "none"]
    assert [g[1] for g in gaps] == pytest.approx(
        [3000e-9, 700e-9, 600e-9, 500e-9])
    bd = summary.breakdown()
    assert bd["idle_gaps"][0] == ["admit", pytest.approx(3000e-9)]
    # self time: the while op keeps 1000 of its 4000 ns
    assert bd["device_ops"] == [
        ["decode/paged_decode_attention", pytest.approx(3000e-9)],
        ["decode/while", pytest.approx(1000e-9)],
        ["prefill/paged_chunk_prefill_attention", pytest.approx(1000e-9)],
        ["jit__argmax/reduce", pytest.approx(200e-9)]]


def test_step_host_time_is_the_step_span_no_device_op_covers(summary):
    run = harness.RunData(cell=None, t0=0, t1=1, recs=[], steps=[], dims={},
                          peak={}, setup_s=0, lags=[], admissions=[],
                          trace=summary)
    # step 1: [900, 6100) less busy [1000, 5000) = 1200 ns; step 2:
    # [7400, 9900) less [8000, 9000) and [9600, 9800) = 1300 ns
    got = harness.metric_reader("step_host_ms")(run)
    assert got == pytest.approx(1250e-9 * 1e3)
    idle = harness.metric_reader("device_idle_share")(run)
    assert idle == pytest.approx(48.0)


def test_a_trace_without_the_mark_or_the_chip_is_refused():
    no_mark = SYNTHETIC.replace('name: "bench.mark"', 'name: "other"')
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(no_mark))
    with pytest.raises(ValueError):
        trace.reduce(pd.planes, 0, 1, 0)
    no_tpu = SYNTHETIC.replace("/device:TPU:0", "/device:CPU:0")
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(no_tpu))
    with pytest.raises(ValueError):
        trace.reduce(pd.planes, 0, 1, 0)


# A 0.4 s trace of the qwen2-7b.chat cell recorded on a TPU v5e (three
# steps: a 32-token prefill chunk, a 16-slot decode, an argmax).  The
# expected numbers were read from the trace.json.gz the profiler wrote
# beside it, a second serialization of the same events, by a separate
# pass over its Chrome trace events.
# the two serializations round each event's time differently (to 1 ns
# or less): a sum over 168 events agrees to about 0.2 us
NS_SUM = 2e-7
CHIP_TRACE = pathlib.Path(__file__).parent / "data" / "qwen2-7b.chat.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip():
    return trace.reduce(trace.load(str(CHIP_TRACE)).planes, 0.0, 10.0, 0.0)


def test_chip_trace_busy_time(chip):
    assert chip.busy_s == pytest.approx(425_126.276e-6, abs=NS_SUM)


def test_chip_trace_kernels(chip):
    assert chip.kernel_s("paged_decode") == pytest.approx(237_725.292e-6,
                                                          abs=NS_SUM)
    assert chip.kernel_s("paged_chunk_prefill") == pytest.approx(
        22_472.249e-6, abs=NS_SUM)
    assert chip.kernel_s("qkv_matmul") == pytest.approx(5_049.518e-6,
                                                        abs=NS_SUM)
    assert sum(1 for o in chip.ops if o[2] == "paged_decode") == 84


def test_chip_trace_steps(chip):
    dec, pre = chip.launches("decode"), chip.launches("prefill")
    assert len(dec) == len(pre) == len(chip.launches("jit__argmax")) == 3
    # the six jit__unknown launches of the JSON twin: 425,067.206 us
    assert sum(dec) + sum(pre) == pytest.approx(425_067.206e-6, abs=NS_SUM)
    assert all(0.1 < d < 0.12 for d in dec)
    assert all(0.03 < d < 0.04 for d in pre)
    assert len(chip.host_spans("bench.step")) == 3
