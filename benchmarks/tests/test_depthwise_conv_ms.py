"""The convolution kernels' time, read by name from a reduced trace;
nothing where the program's convolution is XLA's in both passes."""

import json
import os

import pytest

from benchmarks.harness.spec import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(by_stem_s, steps):
    return load_module("metrics", "depthwise_conv_ms").read(
        {"trace": {"by_stem_s": by_stem_s}, "window": {"steps": steps}})


def test_every_pass_of_the_kernel_is_read_together():
    got = read({
        "transpose_jvp_depthwise_conv_bwd_bfloat16_2b_4096t_4352c_4k__ "
        "[tpu_custom_call]": 0.464,
        "jvp_depthwise_conv_fwd_bfloat16_2b_4096t_4352c_4k_ "
        "[tpu_custom_call]": 0.08,
        "jvp_flash_attention_fwd_bfloat16_2b_32h_4096t_64d_ "
        "[tpu_custom_call]": 0.474,
        "multiply_convert_fusion [fusion:kLoop]": 1.09,
    }, steps=80)
    assert got == pytest.approx(6.8)


def test_a_program_whose_convolution_is_xla_reads_as_nothing():
    # the parent of the PR that brought the kernel, and a CPU rehearsal
    assert read({
        "jvp_flash_attention_fwd_bfloat16_2b_32h_4096t_64d_ "
        "[tpu_custom_call]": 0.474,
        "multiply_convert_fusion [fusion:kLoop]": 1.26,
    }, steps=80) is None
    assert read({}, steps=80) is None


def test_the_metric_is_declared_for_the_hybrid_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "depthwise_conv_ms"]
    assert entry == {
        "name": "depthwise_conv_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "fit_examples_per_s",
        "workloads": ["granite40hmicro.fit_4k"]}
    assert spec["per_layer"][-1] == entry
