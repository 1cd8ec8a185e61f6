"""The device's idle gaps by host activity, and the kernels by name,
on small recorded traces."""

import os

import pytest

from benchmarks.harness import host_spans, kernel_names, trace_reduce
from benchmarks.harness.spec import load_module

HERE = os.path.dirname(os.path.abspath(__file__))


def profile_of(name):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", name)) as f:
        return ProfileData.from_text_proto(f.read())


@pytest.fixture(scope="module")
def profile():
    return profile_of("host_spans_trace.textproto")


def test_each_gap_goes_to_the_innermost_span_that_covers_most_of_it(
        profile):
    found = host_spans.gaps(profile, min_gap_ms=0.001)
    assert [(a - 1000, b - 1000, label) for a, b, label in found] == [
        (0.0, 10000.0, "fit.feed_wait"),          # the lead, from fit's start
        (20000.0, 30000.0, "fit.stack"),
        (40000.0, 60000.0, "fit.feed_wait+prefetch.produce"),
        (70000.0, 100000.0, "(no span)"),        # fit() had returned
    ]


def test_idle_gaps_sums_by_label_longest_first(profile):
    assert host_spans.idle_gaps(profile, min_gap_ms=0.001) == [
        ["(no span)", pytest.approx(30e-6)],
        ["fit.feed_wait+prefetch.produce", pytest.approx(20e-6)],
        ["fit.feed_wait", pytest.approx(10e-6)],
        ["fit.stack", pytest.approx(10e-6)],
    ]
    # a threshold above every gap but the longest
    assert host_spans.idle_gaps(profile, min_gap_ms=0.025) == [
        ["(no span)", pytest.approx(30e-6)]]


def test_a_trace_without_spans_or_without_a_device_still_reads():
    small = profile_of("small_trace.textproto")
    # its host plane has one bare 'fit' event: the window starts there
    assert host_spans.idle_gaps(small, min_gap_ms=0.001) == [
        ["fit", pytest.approx(4e-6)]]
    from jax.profiler import ProfileData

    assert host_spans.idle_gaps(ProfileData.from_text_proto(""), 1.0) == []


def test_kernels_are_found_by_name_in_the_reduced_trace(profile):
    planes = [p for p in profile.planes
              if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    trace = trace_reduce.reduce_planes(planes, window_s=110e-6)
    stem = ("conv_block_fwd_bfloat16_8n_9h_9w_512c_512o_3kh_3kw_1s "
            "[tpu_custom_call]")
    assert trace["by_stem_s"][stem] == pytest.approx(20e-6)
    assert any(name.startswith("%conv_block_fwd_bfloat16_")
               and name.endswith(" tpu_custom_call")
               for name, _ in trace["top_ops"])
    ctx = {"trace": trace, "window": {"steps": 2}}
    assert load_module("metrics", "kernels_named_share").read(ctx) == 100.0
    assert load_module("metrics", "conv_block_fwd_ms").read(ctx) == \
        pytest.approx(10e-3)
    assert load_module("metrics", "conv_block_bwd_data_ms").read(ctx) is None
    assert load_module("metrics", "flash_attention_fwd_ms").read(ctx) is None


def test_an_unnamed_or_scope_wrapped_kernel_is_told_apart():
    trace = {"by_stem_s": {
        "transpose_jvp_conv_block_bwd_weights_bfloat16_8n_1s__ "
        "[tpu_custom_call]": 3.0,
        "jvp_conv_block_fwd_bfloat16_8n_1s__ [tpu_custom_call]": 2.0,
        "transpose_jvp_conv_block_fwd_recompute_bfloat16_8n_1s__ "
        "[tpu_custom_call]": 1.0,
        "transpose_jvp___ [tpu_custom_call]": 2.0,   # a kernel with no name
        "conv_block_fusion [fusion:kLoop]": 9.0,     # not a kernel
    }}
    ctx = {"trace": trace, "window": {"steps": 1}}
    assert load_module("metrics", "kernels_named_share").read(ctx) == 75.0
    assert kernel_names.ms_per_step(ctx, "conv_block_fwd") == 3000.0
    assert kernel_names.ms_per_step(ctx, "conv_block_bwd_weights") == 3000.0
    # the parent of the PR that named the kernels: no name anywhere
    old = {"by_stem_s": {"transpose_jvp___ [tpu_custom_call]": 3.0,
                         "jvp__ [tpu_custom_call]": 1.0}}
    ctx = {"trace": old, "window": {"steps": 1}}
    assert load_module("metrics", "kernels_named_share").read(ctx) == 0.0
    assert load_module("metrics", "conv_block_bwd_weights_ms").read(ctx) \
        is None
    # no TPU plane at all (a CPU rehearsal): nothing, not a 0
    ctx = {"trace": {"by_stem_s": {}}, "window": {"steps": 1}}
    assert load_module("metrics", "kernels_named_share").read(ctx) is None
