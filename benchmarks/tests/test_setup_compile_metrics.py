"""The set-up's compile phases (PR 39): the four readers over the
program's ``compile_spans()`` records that ended before the traced
window's ``fit`` root; nothing where the program keeps no records or
there is no root."""

import json
import os
import types

import pytest

from benchmarks.harness import fit_spans
from benchmarks.harness.spec import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = ("setup_trace_s", "setup_lower_s", "setup_compile_or_load_s",
         "setup_cache_misses")
WINDOW = 100.0  # the fit root's start


def rec(name, start, end, **attrs):
    return {"kind": "span", "name": name, "trace_id": "t", "span_id": "s",
            "parent_id": None, "start": start, "end": end,
            "attrs": attrs, "events": [], "status": "ok"}


RECORDS = [
    # set-up: an outer trace with two traces nested inside it
    rec("compile.trace", 10.0, 14.0, fun="multi_step"),
    rec("compile.trace", 11.0, 12.0, fun="inner"),
    rec("compile.trace", 12.5, 13.0, fun="inner2"),
    rec("compile.lower", 14.0, 20.0, fun="jit(multi_step)"),
    rec("compile.backend", 20.0, 50.0, fun="jit(multi_step)",
        outcome="miss"),
    rec("compile.trace", 60.0, 61.0, fun="make_weights"),
    rec("compile.lower", 61.0, 62.0, fun="jit(make_weights)"),
    rec("compile.backend", 62.0, 62.5, fun="jit(make_weights)",
        outcome="hit", retrieval_s=0.4),
    rec("compile.backend", 63.0, 63.5, fun="jit(convert)",
        outcome="uncached"),
    # ends exactly at the window's start: still the set-up's
    rec("compile.backend", 99.0, WINDOW, fun="jit(stack)",
        outcome="miss"),
    # the reference, after the window
    rec("compile.trace", 130.0, 140.0, fun="ref_step"),
    rec("compile.lower", 140.0, 150.0, fun="jit(ref_step)"),
    rec("compile.backend", 150.0, 190.0, fun="jit(ref_step)",
        outcome="miss"),
    # started in the set-up, ended inside the window: not the set-up's
    rec("compile.backend", 99.5, 101.0, fun="jit(late)", outcome="miss"),
]


@pytest.fixture
def program(monkeypatch):
    """The program's records and a fit root at ``WINDOW``."""
    from deeplearning4j_tpu.compile import persistent

    monkeypatch.setattr(persistent, "compile_spans",
                        lambda: [dict(r) for r in RECORDS],
                        raising=False)
    tree = types.SimpleNamespace(root={"start": WINDOW})
    monkeypatch.setattr(fit_spans, "of_window", lambda: tree)
    return persistent


def read(name):
    return load_module("metrics", name).read({})


def test_each_phase_is_the_union_of_its_records_before_the_window(program):
    # 10..14 holds both nested traces: 4 s, not 5.5; then 60..61
    assert read("setup_trace_s") == pytest.approx(5.0)
    assert read("setup_lower_s") == pytest.approx(7.0)
    assert read("setup_compile_or_load_s") == pytest.approx(
        30.0 + 0.5 + 0.5 + 1.0)
    assert read("setup_cache_misses") == 2


def test_a_cell_that_started_warm_reads_no_miss(program, monkeypatch):
    warm = [dict(r, attrs=dict(r["attrs"], outcome="hit"))
            if r["attrs"].get("outcome") == "miss" else r
            for r in RECORDS]
    monkeypatch.setattr(program, "compile_spans", lambda: warm)
    assert read("setup_cache_misses") == 0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_phase_records_reads_as_nothing(
        monkeypatch, name):
    from deeplearning4j_tpu.compile import persistent

    monkeypatch.delattr(persistent, "compile_spans", raising=False)
    tree = types.SimpleNamespace(root={"start": WINDOW})
    monkeypatch.setattr(fit_spans, "of_window", lambda: tree)
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_no_fit_root_reads_as_nothing(program, monkeypatch, name):
    def no_root():
        raise LookupError("no 'fit' root span")

    monkeypatch.setattr(fit_spans, "of_window", no_root)
    assert read(name) is None
    monkeypatch.setattr(fit_spans, "of_window", lambda: None)
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_ring_that_let_records_go_reads_as_nothing(
        program, monkeypatch, name):
    # the set-up's records are the oldest: they would read low
    monkeypatch.setattr(program, "cache_stats",
                        lambda: {"compile_spans_dropped": 3})
    assert read(name) is None


def test_where_the_phase_time_went_by_function(program):
    from benchmarks.harness import compile_spans

    setup = compile_spans.of_setup()
    assert setup.by_fun("compile.backend")[0] == (
        "jit(multi_step)", 1, 30.0)
    assert setup.count("compile.backend", "uncached") == 1
    assert setup.count("compile.backend", "hit") == 1
    assert "jit(late)" not in [r["attrs"]["fun"] for r in setup.records]


def test_the_four_metrics_are_declared_for_the_four_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]][:4]
    got = {m["name"]: m for m in spec["per_layer"] if m["name"] in NAMES}
    assert set(got) == set(NAMES)
    for name, m in got.items():
        assert m == {
            "name": name,
            "unit": "count" if name == "setup_cache_misses" else "s",
            "better": "lower", "source": "program_span",
            "layer": "compile", "moves": "setup_s", "workloads": cells}


def test_the_traced_rehearsal_reports_all_four(capsys):
    from benchmarks.tests.helpers import rehearse

    rc, result, _, _ = rehearse(capsys, "resnet50.fit", trace=1)
    assert rc == 0 and result["correct"] is True
    got = {n: result["metrics"][n]["value"] for n in NAMES}
    assert isinstance(got["setup_cache_misses"], int)
    assert got["setup_cache_misses"] >= 0
    for n in NAMES[:3]:  # the warm-up compiled the scan program
        assert got[n] > 0
    # nested traces folded: the set-up is tens of records, not thousands
    from deeplearning4j_tpu.compile import persistent

    assert len(persistent.compile_spans()) < 400
    assert persistent.cache_stats()["compile_spans_dropped"] == 0
