"""``lfm2moe24b_ep8share``'s operations counted from shapes, against
figures worked by hand from the configuration and against the built
model."""

import pytest

from benchmarks.harness.spec import Cell, load_json

CELL = "lfm2moe.fit_4k"


def test_parameters_are_833_million():
    """Per layer: the short-convolution mixer 16,783,360, the attention
    mixer 10,485,888 (with the q/k gains), an expert 9,437,184, the dense
    MLP 72,351,744, the router 131,072, two gains of 2,048; the tied
    table 16,777,216 and the final gain."""
    cell = Cell(CELL)
    assert cell.counts().parameters(cell.config) == 832_651_520
    assert cell.config["state"]["parameters"] == 832_651_520


def test_parameters_are_the_built_models_leaves():
    """``counts.parameters`` against the leaves of the zoo's model as the
    program builds it at the tiny sizes (shapes only, no weight made)."""
    import jax
    import numpy as np

    from benchmarks.harness.spec import load_module

    cell = Cell(CELL)
    driver = load_module("drivers", "fit_tokens")
    cfg = driver.sized(cell.config, True)
    net = driver.build_program(cfg, 0)

    def shapes():
        net.init()
        return net.params

    leaves = jax.tree.leaves(jax.eval_shape(shapes))
    assert sum(int(np.prod(a.shape)) for a in leaves) == (
        cell.counts().parameters(cfg))


def test_multiply_adds_a_token():
    cell = Cell(CELL)
    counts, cfg = cell.counts(), cell.config
    t = cfg["input"]["length"]
    assert counts.conv_layers(cfg) == 7 and counts.attention_layers(cfg) == 2
    assert counts.attention_macs_per_example(cfg) / t == 2048 * 32 * 128
    # 7 conv mixers 117.44M, the dense MLP 72.35M, attention projections
    # 20.97M and scores 16.78M, routers 1.05M, held experts at their
    # even share 37.75M, the head 16.78M
    assert counts.forward_macs_per_example(cfg) / t == pytest.approx(
        283.1e6, abs=0.1e6)
    conv = 7 * (2048 * 6144 + 2048 * 2048)
    assert conv / (counts.forward_macs_per_example(cfg) / t) == (
        pytest.approx(0.415, abs=0.001))


def test_roofline_time_is_at_least_the_compute_time():
    cell = Cell(CELL)
    peaks = load_json("harness", "peaks.json")["TPU v5 lite"]
    batch = cell.traffic["batch"]
    counts = cell.counts()
    least = counts.roofline_seconds_per_step(cell.config, batch, peaks)
    compute = counts.flops_per_example(cell.config) * batch / peaks[
        "flops_bf16"]
    assert compute <= least < 1.5 * compute


def test_short_conv_bytes():
    """Forward 4·n·d values a call, twice a layer under full remat;
    backward 7·n·d once; bfloat16; seven layers; n = 8,192."""
    cell = Cell(CELL)
    peaks = load_json("harness", "peaks.json")["TPU v5 lite"]
    counts, cfg = cell.counts(), cell.config
    nd = 8192 * 2048 * 2
    bw = peaks["hbm_bytes_per_s"]
    assert counts.short_conv_seconds_per_step(cfg, 2, peaks) == (
        pytest.approx(7 * (8 + 7) * nd / bw))
    assert counts.short_conv_seconds_per_step(
        cfg, 2, peaks, forward=False) == pytest.approx(7 * 7 * nd / bw)
    assert counts.short_conv_seconds_per_step(
        cfg, 2, peaks, backward=False) == pytest.approx(7 * 8 * nd / bw)
