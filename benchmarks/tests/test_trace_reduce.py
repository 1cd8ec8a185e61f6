"""The reduction from a trace to numbers, on a small recorded trace."""

import os

import pytest

from benchmarks.harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "small_trace.textproto")) as f:
        pd = ProfileData.from_text_proto(f.read())
    return [p for p in pd.planes
            if p.name.startswith(trace_reduce.DEVICE_PREFIX)]


def test_busy_is_the_union_of_nested_and_separate_events(planes):
    assert len(planes) == 1  # the host plane is not a device
    ops = trace_reduce.line_events(planes[0], trace_reduce.OPS_LINE)
    assert len(ops) == 5
    assert trace_reduce.union_ns(ops) == pytest.approx(12_000.0)
    trace = trace_reduce.reduce_planes(planes, window_s=20e-6)
    assert trace["busy_s"] == pytest.approx(12e-6)
    assert trace["module_runs"] == 1 and trace["events"] == 5


def test_self_time_by_category_does_not_count_a_loop_body_twice(planes):
    trace = trace_reduce.reduce_planes(planes, window_s=20e-6)
    cats = trace["by_category_s"]
    assert cats["convolution"] == pytest.approx(4e-6)
    assert cats["loop fusion"] == pytest.approx(2e-6)
    assert cats["tpu_custom_call"] == pytest.approx(2e-6)
    # the while's own time is what its body does not cover: 1 us
    assert cats["while"] == pytest.approx(1e-6)
    assert sum(cats.values()) == pytest.approx(trace["busy_s"])
    assert trace["top_ops"][0] == ["convolution.7", pytest.approx(4e-6)]
    # names that are HLO text give their own category and stem
    assert cats["fusion:kLoop"] == pytest.approx(3e-6)
    assert trace["by_stem_s"]["broadcast_maximum_fusion [fusion:kLoop]"] \
        == pytest.approx(3e-6)
    assert ["%custom-call.11 tpu_custom_call", pytest.approx(2e-6)] in \
        trace["top_ops"]


def test_the_metric_readers_on_the_small_trace(planes):
    from benchmarks.harness.spec import load_module

    trace = trace_reduce.reduce_planes(planes, window_s=20e-6)
    ctx = {"trace": trace, "window": {"steps": 2, "seconds": 20e-6}}
    assert load_module("metrics", "device_idle_share").read(ctx) == \
        pytest.approx(40.0)
    assert load_module("metrics", "step_device_ms").read(ctx) == \
        pytest.approx(6e-3)
    roofline = load_module("metrics", "kernels_roofline")
    assert roofline.kernel_seconds(trace) == pytest.approx(6e-6)  # conv + kernel
    # nothing to read: the reader returns nothing, never a 0
    empty = trace_reduce.reduce_planes([], window_s=1.0)
    ctx = {"trace": empty, "window": {"steps": 2, "seconds": 1.0}}
    assert load_module("metrics", "device_idle_share").read(ctx) is None
    assert load_module("metrics", "step_device_ms").read(ctx) is None
