"""The attention backward kernel's time, read by name from a reduced
trace; nothing where the program's attention backward is XLA's."""

import json
import os

import pytest

from benchmarks.harness.spec import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(by_stem_s, steps):
    ctx = {"trace": {"by_stem_s": by_stem_s}, "window": {"steps": steps}}
    return {name: load_module("metrics", name).read(ctx)
            for name in ("flash_attention_fwd_ms", "flash_attention_bwd_ms",
                         "kernels_named_share")}


def test_the_backward_kernel_is_read_apart_from_the_forward():
    got = read({
        "jvp_flash_attention_fwd_bfloat16_64b_8h_512t_64d_ "
        "[tpu_custom_call]": 0.96,
        "transpose_jvp_flash_attention_bwd_bfloat16_64b_8h_512t_64d__ "
        "[tpu_custom_call]": 1.92,
        "fusion [fusion:kOutput]": 4.0,
    }, steps=96)
    assert got["flash_attention_fwd_ms"] == pytest.approx(10.0)
    assert got["flash_attention_bwd_ms"] == pytest.approx(20.0)
    assert got["kernels_named_share"] == 100.0


def test_a_program_whose_backward_is_xla_reads_as_nothing():
    # the parent of the PR that brought the kernel, and a CPU rehearsal
    got = read({
        "jvp_flash_attention_fwd_bfloat16_64b_8h_512t_64d_ "
        "[tpu_custom_call]": 2.55,
        "divide_subtract_fusion [fusion:kOutput]": 1.43,
    }, steps=96)
    assert got["flash_attention_bwd_ms"] is None
    assert got["flash_attention_fwd_ms"] == pytest.approx(26.5625)
    assert read({}, steps=96)["flash_attention_bwd_ms"] is None


def test_the_metric_is_declared_for_the_transformer_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "flash_attention_bwd_ms"]
    assert entry == {
        "name": "flash_attention_bwd_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "fit_examples_per_s",
        "workloads": ["chartransformer12.fit"]}
