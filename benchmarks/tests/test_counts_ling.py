"""``ling3flash_ep64share``'s operations counted from shapes, against
figures worked by hand from the configuration."""

import pytest

from benchmarks.harness.spec import Cell, load_json

CELL = "ling3flash.fit_4k"


def test_parameters_are_884_million():
    """Per layer: the delta-rule mixer 63,049,888, the latent-attention
    mixer 31,965,696 and its query-norm gain of 192 (``assumed``
    ``mla_q_norm``), the experts' part 54,394,880, the dense MLP
    47,185,920, two gains of 2,560; the embedding and head 100,600,320."""
    cell = Cell(CELL)
    assert cell.counts().parameters(cell.config) == 884_456_384 + 192


def test_multiply_adds_a_token():
    cell = Cell(CELL)
    counts, cfg = cell.counts(), cell.config
    t = cfg["input"]["length"]
    # a delta-rule chunk of 64 and a head of 128: 5 half squares of
    # products, the inverse at 64^3/6, three state products; 32 heads
    per_chunk = 5 * 2048 * 128 + 64 ** 3 // 6 + 3 * 64 * 128 * 128
    assert counts.kda_chunk_macs_per_example(cfg) == 64 * per_chunk * 32
    assert counts.attention_macs_per_example(cfg) / t == 2048 * 32 * 320
    assert counts.kda_layers(cfg) == 6 and counts.mla_layers(cfg) == 1
    assert counts.forward_macs_per_example(cfg) / t == pytest.approx(
        589.6e6, abs=0.1e6)


def test_roofline_time_is_at_least_the_compute_time():
    cell = Cell(CELL)
    peaks = load_json("harness", "peaks.json")["TPU v5 lite"]
    batch = cell.traffic["batch"]
    counts = cell.counts()
    least = counts.roofline_seconds_per_step(cell.config, batch, peaks)
    compute = counts.flops_per_example(cell.config) * batch / peaks[
        "flops_bf16"]
    assert compute <= least < 1.5 * compute
    # the chunk is bound by its bytes at these shapes, forward alone and
    # with its backward
    fwd = counts.kda_chunk_seconds_per_step(cell.config, batch, peaks,
                                            backward=False)
    assert fwd == pytest.approx(
        6 * 8192 * (4096 * 12 + 32 * 4) / peaks["hbm_bytes_per_s"])
    both = counts.kda_chunk_seconds_per_step(cell.config, batch, peaks)
    assert both == pytest.approx(
        6 * 8192 * (4096 * 34 + 32 * 12) / peaks["hbm_bytes_per_s"])
