"""``granite40hmicro_stage10``'s operations counted from shapes, against
ISSUE 37's hand-worked figures."""

import pytest

from benchmarks.harness.spec import Cell, load_json

CELL = "granite40hmicro.fit_4k"


def test_parameters_are_the_issues_772_million():
    cell = Cell(CELL)
    assert cell.counts().parameters(cell.config) == 772_160_448


def test_multiply_adds_a_token_match_the_issues_arithmetic():
    cell = Cell(CELL)
    counts, cfg = cell.counts(), cell.config
    t = cfg["input"]["length"]
    # 9 x (25.82M mixer products + 1.59M scan + 50.33M MLP)
    # + 10.49M + 8.39M + 50.33M for the attention block + 25.69M head
    assert counts.forward_macs_per_example(cfg) / t == pytest.approx(
        794.6e6, abs=0.05e6)
    assert counts.scan_macs_per_example(cfg) / t == (
        128 * 128 + 128 * 64 * 64 + 2 * 64 * 64 * 128)
    assert counts.attention_macs_per_example(cfg) / t == 2048 * 32 * 128
    assert counts.flops_per_example(cfg) == pytest.approx(19.53e12, rel=1e-3)
    batch = cell.traffic["batch"]
    assert batch * counts.flops_per_example(cfg) == pytest.approx(
        39.05e12, rel=1e-3)


def test_roofline_time_is_at_least_the_compute_time():
    cell = Cell(CELL)
    peaks = load_json("harness", "peaks.json")["TPU v5 lite"]
    batch = cell.traffic["batch"]
    least = cell.counts().roofline_seconds_per_step(
        cell.config, batch, peaks)
    compute = (cell.counts().flops_per_example(cell.config) * batch
               / peaks["flops_bf16"])
    assert compute <= least < 1.5 * compute


def test_a_chunk_shorter_than_the_configurations_counts_less_scan_work():
    """The intra-chunk products grow with the chunk; the states' do
    not."""
    cell = Cell(CELL)
    counts, cfg = cell.counts(), cell.config
    smaller = dict(cfg, mamba_chunk_size=128)
    t = cfg["input"]["length"]
    assert (counts.scan_macs_per_example(cfg)
            - counts.scan_macs_per_example(smaller)) / t == (
        64 * 128 + 64 * 64 * 64)
