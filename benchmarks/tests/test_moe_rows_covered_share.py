"""The share of token-slots the held experts' data path covered, read
from what ``publish_routing_metrics`` returns; nothing where the
program counts no rungs."""

import json
import os

import pytest

from benchmarks.harness.spec import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(layers):
    return load_module("metrics", "moe_rows_covered_share").read(
        {"routing": {"layers": layers}})


def test_rows_covered_over_token_slots_of_every_expert_layer():
    slots = [512] * 64          # 32,768 a call, one call each
    got = read({
        "2": {"slots": slots, "held": (0, 7), "dropped": 0,
              "rows_covered": 8192},
        "3": {"slots": slots, "held": (0, 7), "dropped": 0,
              "rows_covered": 16384},
    })
    assert got == pytest.approx(100.0 * (8192 + 16384) / (2 * 32768))


def test_a_program_that_counts_no_rungs_reads_as_nothing():
    # the parent of the PR that brought the ladder, and no routing at all
    assert read({"2": {"slots": [512] * 64, "held": (0, 7),
                       "dropped": 0}}) is None
    assert read({}) is None
    assert load_module("metrics", "moe_rows_covered_share").read({}) is None


def test_the_metric_is_declared_for_the_expert_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "moe_rows_covered_share"]
    assert entry == {
        "name": "moe_rows_covered_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "expert routing",
        "moves": "fit_examples_per_s",
        "workloads": ["glm47flash.fit_4k"]}


def test_the_traced_rehearsal_reports_it_between_the_first_rung_and_all(
        capsys):
    # 2 of 8 experts held at the tiny sizes: the first rung is half of
    # the token-slots, the last all of them
    from benchmarks.tests.helpers import rehearse

    rc, result, _, _ = rehearse(capsys, "glm47flash.fit_4k", trace=1)
    assert rc == 0 and result["correct"] is True
    assert 50 <= result["metrics"]["moe_rows_covered_share"]["value"] <= 100
