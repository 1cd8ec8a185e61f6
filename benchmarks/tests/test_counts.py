"""Operations counted from shapes, against hand-worked figures."""

import pytest

from benchmarks.harness.spec import Cell, load_json


def test_resnet50_forward_macs():
    cell = Cell("resnet50.fit")
    counts = cell.counts()
    rows = {r[0]: r for r in counts.layers(cell.config["model"])}
    # stem: 64 x 3 x 7 x 7 kernel over 112 x 112 outputs
    assert rows["stem"][1] == 64 * 3 * 49 * 112 * 112 == 118_013_952
    # the widest 3x3: 512 x 512 x 9 over 7 x 7
    assert rows["s3b1_c2"][1] == 512 * 512 * 9 * 49
    assert rows["out"][1] == 2048 * 1000
    assert len(rows) == 53 + 1  # 53 convolutions and the classifier
    # about 4.1 G multiply-adds forward at 224 x 224 (v1.5 stride)
    assert counts.forward_macs_per_example(cell.config) == pytest.approx(
        4.1e9, rel=0.02)
    # backward is twice forward, less the stem's input gradient
    assert counts.flops_per_example(cell.config) == pytest.approx(
        6 * 4.1e9, rel=0.03)


def test_chartransformer12_flops_per_character():
    cell = Cell("chartransformer12.fit")
    counts = cell.counts()
    per_char = counts.flops_per_example(cell.config) / 512
    # 12 x (4 d^2 + 2 d ff) + causal attention + embedding and head,
    # forward and backward: about a quarter of a GFLOP a character
    d, ff = 512, 2048
    by_hand = 2 * 3 * (12 * (4 * d * d + 2 * d * ff + 512 * d)
                       + 256 * d) + 2 * 2 * 256 * d
    assert per_char == pytest.approx(by_hand, rel=1e-9)
    assert per_char == pytest.approx(0.25e9, rel=0.05)


@pytest.mark.parametrize("workload", ["resnet50.fit",
                                      "chartransformer12.fit"])
def test_roofline_time_is_at_least_the_compute_time(workload):
    cell = Cell(workload)
    peaks = load_json("harness", "peaks.json")["TPU v5 lite"]
    batch = cell.traffic["batch"]
    least = cell.counts().roofline_seconds_per_step(
        cell.config, batch, peaks)
    compute = (cell.counts().flops_per_example(cell.config) * batch
               / peaks["flops_bf16"])
    assert compute <= least < 3 * compute
