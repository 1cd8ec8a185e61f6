"""UI component DSL + profiler listener tests (reference analog:
``deeplearning4j-ui-components`` bean->JSON round-trip tests; §5
tracing hook)."""

import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.ui.components import (
    ChartHistogram,
    ChartLine,
    ChartScatter,
    ComponentDiv,
    ComponentTable,
    ComponentText,
    component_from_json,
    render_page,
)


def test_chart_line_json_round_trip():
    c = ChartLine(title="score").add_series("s", [0, 1, 2], [3, 2, 1])
    back = component_from_json(c.to_json())
    assert isinstance(back, ChartLine)
    assert back.title == "score"
    assert back.x == [[0.0, 1.0, 2.0]]
    assert back.y == [[3.0, 2.0, 1.0]]
    svg = back.render_html()
    assert svg.startswith("<svg") and "polyline" in svg


def test_chart_scatter_and_histogram_render():
    s = ChartScatter(title="pts").add_series("a", [0, 1], [1, 0])
    assert s.render_html().count("<circle") == 2
    h = ChartHistogram(title="h")
    h.add_bin(0.0, 1.0, 5.0).add_bin(1.0, 2.0, 2.0)
    out = h.render_html()
    assert out.count("<rect") == 2
    back = component_from_json(h.to_json())
    assert back.values == [5.0, 2.0]


def test_component_div_nesting_and_escaping():
    div = ComponentDiv(children=[
        ComponentText(text="<b>bold?</b>", color="#111"),
        ComponentTable(header=["k", "v"],
                       content=[["a", "<script>"], ["b", "2"]]),
    ], style="margin:1em")
    html_out = div.render_html()
    assert "&lt;b&gt;bold?&lt;/b&gt;" in html_out     # escaped
    assert "&lt;script&gt;" in html_out               # escaped
    assert "<script>" not in html_out
    back = component_from_json(div.to_json())
    assert isinstance(back.children[0], ComponentText)
    assert isinstance(back.children[1], ComponentTable)
    page = render_page(div)
    assert page.startswith("<!DOCTYPE html>")


def test_profiler_listener_produces_trace(tmp_path):
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize import ProfilerListener

    conf = (
        NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
        .layer(OutputLayer(n_out=2))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    log_dir = str(tmp_path / "trace")
    listener = ProfilerListener(log_dir, start_iteration=2,
                                num_iterations=2)
    net.listeners.append(listener)
    rng = np.random.RandomState(0)
    ds = DataSet(features=rng.rand(8, 4).astype(np.float32),
                 labels=np.eye(2, dtype=np.float32)[
                     rng.randint(0, 2, 8)])
    for _ in range(6):
        net.fit(ds)
    listener.close()
    assert listener.trace_dir is not None
    # a plugins/profile/<ts>/ directory with trace artifacts appears
    found = []
    for root, _, files in os.walk(log_dir):
        found += files
    assert found, "profiler produced no trace files"


def test_profiler_listener_keeps_the_fused_scan_path(tmp_path):
    """Attached to a scan-eligible net it traces the program the user
    runs: the scan-of-k step is built, the per-step one is not, and
    the session brackets whole chunks."""
    from deeplearning4j_tpu.datasets.api import (
        DataSet,
        ListDataSetIterator,
    )
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize import ProfilerListener

    conf = (
        NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
        .list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
        .layer(OutputLayer(n_out=2))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    log_dir = str(tmp_path / "trace")
    listener = ProfilerListener(log_dir, start_iteration=2,
                                num_iterations=20)
    net.set_listeners(listener)
    rng = np.random.RandomState(0)
    batches = [DataSet(features=rng.rand(8, 4).astype(np.float32),
                       labels=np.eye(2, dtype=np.float32)[
                           rng.randint(0, 2, 8)])
               for _ in range(48)]
    net.fit(ListDataSetIterator(batches), epochs=1)
    assert net._jit_multi_step is not None
    assert net._jit_step is None
    # started in the first chunk's callbacks (iteration 2), stopped in
    # the second's (iteration 22): one session, never restarted
    assert listener.trace_dir == log_dir and not listener._active
    found = [f for _, _, files in os.walk(log_dir) for f in files]
    assert len([f for f in found if f.endswith(".xplane.pb")]) == 1


def test_profiler_annotate_context():
    from deeplearning4j_tpu.optimize import annotate

    with annotate("data-load"):
        x = np.ones(4).sum()
    assert x == 4.0
