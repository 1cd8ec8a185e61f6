"""The readers of the fit drivers' spans, on a hand-built span tree
and on rehearsed traced runs."""

import pytest

from benchmarks.harness import fit_spans
from benchmarks.harness.spec import load_module
from benchmarks.tests.helpers import CELLS, rehearse

SHARES = ["fit_feed_wait_share", "fit_stack_share",
          "fit_dispatch_call_share", "fit_host_other_share"]


def span(name, start, end, span_id, parent_id=None, trace_id="t1",
         **attrs):
    return {"name": name, "trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id, "start": start, "end": end,
            "status": "ok", "attrs": attrs}


def tree_of_two_chunks(trace_id="t1", t0=0.0):
    """fit 0..10 s: one epoch 0.5..9.5 of two chunks of 2 batches;
    feed waits 1 + 0.5 + 1 + 0.5 + 0.25 (the last one empty-handed),
    stacks 0.5 + 0.5, dispatches 0.25 + 0.25 starting at 2.5 and 5.5,
    listeners 0.5."""
    s = [span("fit", 0, 10, "r", epochs=1, path="scan")]
    s.append(span("fit.epoch", .5, 9.5, "e", "r", epoch=0, batches=4))
    rows = [("fit.feed_wait", .5, 1.5, dict(batches=1)),
            ("fit.feed_wait", 1.5, 2.0, dict(batches=1)),
            ("fit.stack", 2.0, 2.5, dict(batches=2, bytes=64)),
            ("fit.dispatch", 2.5, 2.75, dict(steps=2, rows=16,
                                             first_step=1)),
            ("fit.listeners", 2.75, 3.25, dict(steps=2)),
            ("fit.feed_wait", 3.5, 4.5, dict(batches=1)),
            ("fit.feed_wait", 4.5, 5.0, dict(batches=1)),
            ("fit.stack", 5.0, 5.5, dict(batches=2, bytes=64)),
            ("fit.dispatch", 5.5, 5.75, dict(steps=2, rows=16,
                                             first_step=3)),
            ("fit.feed_wait", 9.0, 9.25, dict(batches=0))]
    for i, (name, a, b, attrs) in enumerate(rows):
        s.append(span(name, a, b, f"c{i}", "e", **attrs))
    return [dict(x, trace_id=trace_id, start=x["start"] + t0,
                 end=x["end"] + t0) for x in s]


def test_totals_shares_and_self_times_of_a_hand_built_tree():
    tree = fit_spans.FitTree(tree_of_two_chunks())
    assert tree.seconds == 10
    assert tree.total("fit.feed_wait") == pytest.approx(3.25)
    assert tree.share("fit.stack") == pytest.approx(10.0)
    assert tree.share("fit.dispatch") == pytest.approx(5.0)
    # everything else: fit's self time 1, the epoch's 3.75, listeners .5
    assert tree.other() == pytest.approx(5.25)
    assert tree.self_time(tree.root) == pytest.approx(1.0)
    (epoch,) = tree.named("fit.epoch")
    assert tree.self_time(epoch) == pytest.approx(9.0 - 5.25)
    assert tree.steps_per_dispatch() == 2
    assert tree.first_dispatch_s() == pytest.approx(2.5)
    table = {name: (n, total, own)
             for name, n, total, own in tree.table()}
    assert tree.table()[0][0] == "fit"
    assert table["fit.feed_wait"] == (5, pytest.approx(3.25),
                                      pytest.approx(3.25))
    assert table["fit.epoch"][2] == pytest.approx(3.75)


def test_the_newest_fit_root_is_the_windows_and_others_are_left_out():
    older = tree_of_two_chunks("t0", t0=-100.0)
    worker = [span("prefetch.produce", 1, 2, "w1", trace_id="t9",
                   bytes=8)]
    tree = fit_spans.FitTree(older + worker + tree_of_two_chunks())
    assert tree.root["trace_id"] == "t1" and tree.root["start"] == 0
    assert {s["trace_id"] for s in tree.spans} == {"t1"}
    assert len(tree.spans) == 11


def test_no_root_or_a_tree_the_ring_cut_short_raises():
    spans = tree_of_two_chunks()
    with pytest.raises(LookupError, match="no 'fit' root"):
        fit_spans.FitTree(spans[1:])
    with pytest.raises(LookupError, match="overflowed"):
        fit_spans.FitTree([s for s in spans if s["span_id"] != "c0"])


def test_span_objects_of_the_programs_tracer_are_read_alike():
    from deeplearning4j_tpu.observability.trace import Tracer

    ticks = iter(range(100))
    tracer = Tracer(seed=1, clock=lambda: float(next(ticks)))
    with tracer.start_span("fit", attrs={"epochs": 1}) as root:
        with tracer.start_span("fit.epoch", parent=root) as epoch:
            tracer.start_span("fit.feed_wait", parent=epoch,
                              attrs={"batches": 1}).end()
            tracer.start_span("fit.dispatch", parent=epoch,
                              attrs={"steps": 1}).end()
            epoch.set_attr("batches", 1)
    tree = fit_spans.FitTree(tracer.finished_spans())
    assert tree.seconds == 7 and tree.total("fit.feed_wait") == 1
    assert tree.steps_per_dispatch() == 1


def test_a_program_from_before_the_spans_reads_as_nothing(monkeypatch):
    """The parent commit of the PR that brought the spans: the readers
    return nothing and do not raise."""
    monkeypatch.setattr(fit_spans, "program_records_fit_spans",
                        lambda: False)
    for name in SHARES + ["fit_steps_per_dispatch",
                          "fit_first_dispatch_ms"]:
        assert load_module("metrics", name).read({}) is None


@pytest.mark.parametrize("workload", CELLS)
def test_traced_rehearsal_reports_the_span_metrics(capsys, workload):
    rc, result, earlier, _ = rehearse(capsys, workload, trace=1)
    assert rc == 0 and result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # the tiny sizes keep the scan chunk of 16
    assert metrics["fit_steps_per_dispatch"] == 16
    assert sum(metrics[n] for n in SHARES) == pytest.approx(100.0)
    assert all(0 <= metrics[n] <= 100 for n in SHARES)
    assert metrics["fit_first_dispatch_ms"] > 0
    # no TPU plane on the CPU: the device-trace readers find nothing
    for name in ("kernels_named_share", "conv_block_fwd_ms",
                 "conv_block_bwd_data_ms", "conv_block_bwd_weights_ms",
                 "flash_attention_fwd_ms"):
        assert name not in metrics


def test_untraced_rehearsal_leaves_the_tracers_ring_empty(capsys):
    from deeplearning4j_tpu.observability.trace import get_tracer

    get_tracer().clear()
    rc, result, _, _ = rehearse(capsys, CELLS[1], trace=0)
    assert rc == 0 and get_tracer().finished_spans() == []
