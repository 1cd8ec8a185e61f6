"""How ``fit()``'s scan driver builds a chunk (``nn/core.py``): each
batch goes to the device as it arrives, the chunk is stacked in device
memory, and the host enqueues at most one chunk behind the one that
runs. The trajectory is the one the host-stacking driver gave, which
is kept here as the reference."""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.datasets.api import MultiDataSet, PlacedDataSet
from deeplearning4j_tpu.nn import core, multilayer
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    DenseLayer,
    GravesLSTM,
    OutputLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.trace import (
    Tracer,
    set_global_tracer,
)

ROWS, CHUNK = 8, 16


def _builder():
    return NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)


def _mln(n_in=4):
    conf = (_builder().list()
            .layer(DenseLayer(n_in=n_in, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2)).build())
    return MultiLayerNetwork(conf).init()


def _rnn():
    conf = (_builder().list()
            .layer(GravesLSTM(n_in=3, n_out=5))
            .layer(RnnOutputLayer(n_out=2)).build())
    return MultiLayerNetwork(conf).init()


def _graph(n_in=4):
    conf = (_builder().graph_builder().add_inputs("in")
            .add_layer("h", DenseLayer(n_in=n_in, n_out=8,
                                       activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=2), "h")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _two_input_graph():
    from deeplearning4j_tpu.nn.conf.graph_conf import MergeVertex

    conf = (_builder().graph_builder().add_inputs("a", "b")
            .add_layer("ha", DenseLayer(n_in=4, n_out=6,
                                        activation="tanh"), "a")
            .add_layer("hb", DenseLayer(n_in=3, n_out=6,
                                        activation="tanh"), "b")
            .add_vertex("m", MergeVertex(), "ha", "hb")
            .add_layer("out", OutputLayer(n_in=12, n_out=2), "m")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _labels(rng, rows):
    return np.eye(2, dtype=np.float32)[rng.randint(0, 2, rows)]


def _dense_batches(n, rows=ROWS, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)

    def features():
        if dtype == np.uint8:
            return rng.randint(0, 256, (rows, 4)).astype(np.uint8)
        return rng.rand(rows, 4).astype(dtype)

    return [DataSet(features=features(), labels=_labels(rng, rows))
            for _ in range(n)]


def _masked_sequences(n, t=6):
    rng = np.random.RandomState(1)
    out = []
    for _ in range(n):
        mask = np.ones((ROWS, t), np.float32)
        mask[:, rng.randint(2, t):] = 0.0
        ids = rng.randint(0, 2, (ROWS, t))
        out.append(DataSet(
            features=rng.rand(ROWS, 3, t).astype(np.float32),
            labels=np.eye(2, dtype=np.float32)[ids].transpose(0, 2, 1),
            features_mask=mask, labels_mask=mask))
    return out


def _masked_rows(n):
    """Per-row label masks, as the DAG engine takes them."""
    rng = np.random.RandomState(2)
    return [MultiDataSet(
        features=[rng.rand(ROWS, 4).astype(np.float32)],
        labels=[_labels(rng, ROWS)],
        labels_masks=[(rng.rand(ROWS, 1) > 0.3).astype(np.float32)])
        for _ in range(n)]


def _two_inputs(n):
    rng = np.random.RandomState(4)
    return [MultiDataSet(
        features=[rng.rand(ROWS, 4).astype(np.float32),
                  rng.rand(ROWS, 3).astype(np.float32)],
        labels=[_labels(rng, ROWS)]) for _ in range(n)]


def _placed(batches):
    return [PlacedDataSet(features=jax.numpy.asarray(b.features),
                          labels=jax.numpy.asarray(b.labels))
            for b in batches]


# case -> (network factory, batches): 20 batches are one chunk of 16
# and a last one of 4; a change of shape ends a chunk early
CASES = {
    "mln-float32": (_mln, lambda: _dense_batches(20)),
    "mln-float64-cast-on-the-host": (
        _mln, lambda: _dense_batches(20, dtype=np.float64)),
    "mln-uint8": (_mln, lambda: _dense_batches(20, dtype=np.uint8)),
    "mln-masks": (_rnn, lambda: _masked_sequences(20)),
    "mln-shape-changes": (
        _mln, lambda: _dense_batches(10) + _dense_batches(10, rows=4,
                                                          seed=5)),
    "mln-one-batch-left-over": (_mln, lambda: _dense_batches(17)),
    "graph-float32": (_graph, lambda: _dense_batches(20)),
    "graph-uint8": (_graph, lambda: _dense_batches(20, dtype=np.uint8)),
    "graph-label-masks": (_graph, lambda: _masked_rows(20)),
    "graph-two-inputs": (_two_input_graph, lambda: _two_inputs(20)),
    "graph-one-batch-left-over": (_graph, lambda: _dense_batches(17)),
    "graph-shape-changes": (
        _graph, lambda: _dense_batches(10) + _dense_batches(10, rows=4,
                                                            seed=5)),
}


def _host_stacking_driver(monkeypatch):
    """The scan driver as it was: the chunk's batches stay on the host
    until the flush, which stacks them there (``np.stack``) and copies
    the chunk over in one transfer."""

    def stack_on_device(arrs, dtype):
        if all(isinstance(a, jax.Array) for a in arrs):
            return core.cast_stacked(jax.numpy.stack(arrs), dtype)
        return core.to_device(
            np.stack([np.asarray(a) for a in arrs]), dtype)

    monkeypatch.setattr(core, "place_batch", lambda model, ds: ds)
    monkeypatch.setattr(core, "stack_on_device", stack_on_device)
    monkeypatch.setattr(multilayer, "_stack_on_device", stack_on_device)


def _leaves(net):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(
        (net.params, net.updater_state, net.state))]


def _chunks_seen(monkeypatch):
    """[(k, dtype of the first stacked feature array)] per dispatch."""
    seen = []
    real = core.run_scan_chunk

    def spy(model, stacked):
        seen.append((stacked[4],
                     jax.tree_util.tree_leaves(stacked[0])[0].dtype))
        return real(model, stacked)

    monkeypatch.setattr(core, "run_scan_chunk", spy)
    return seen


@pytest.mark.parametrize("case", list(CASES))
def test_trajectory_is_the_host_stacking_drivers_bitwise(monkeypatch,
                                                         case):
    make_net, make_batches = CASES[case]
    net, batches = make_net(), make_batches()
    seen = _chunks_seen(monkeypatch)
    net.fit(ListDataSetIterator(batches), epochs=2)
    with monkeypatch.context() as before:
        _host_stacking_driver(before)
        ref = make_net()
        ref.fit(ListDataSetIterator(make_batches()), epochs=2)
    assert net.iteration_count == ref.iteration_count == 2 * len(batches)
    for got, want in zip(_leaves(net), _leaves(ref), strict=True):
        assert got.dtype == want.dtype and np.array_equal(got, want)
    assert float(net.score_value) == float(ref.score_value)
    half = len(seen) // 2
    assert seen[:half] == seen[half:]  # both drivers, the same chunks
    if "shape-changes" in case:
        assert [k for k, _ in seen[:half]] == [10, 10] * 2
    elif "left-over" in case:
        assert [k for k, _ in seen[:half]] == [16] * 2
    else:
        assert [k for k, _ in seen[:half]] == [16, 4] * 2
    if "uint8" in case:  # still narrow on the device: the step casts
        assert {d for _, d in seen} == {np.dtype(np.uint8)}


@pytest.mark.parametrize("make_net", [_mln, _graph])
def test_device_resident_batches_end_where_host_batches_end(make_net):
    batches = _dense_batches(20)
    host, placed = make_net(), make_net()
    host.fit(ListDataSetIterator(batches), epochs=1)
    placed.fit(ListDataSetIterator(_placed(batches)), epochs=1)
    for got, want in zip(_leaves(placed), _leaves(host), strict=True):
        assert np.array_equal(got, want)


def test_a_placed_batch_is_handed_on_as_it_is():
    net = _mln()
    (ds,) = _placed(_dense_batches(1))
    out = core.place_batch(net, ds)
    assert out.features is ds.features and out.labels is ds.labels
    assert core.host_bytes(ds) == 0
    assert core.host_bytes(_dense_batches(1)[0]) == ROWS * (4 + 2) * 4


@pytest.mark.parametrize("make_net,make_batches", [
    (_mln, lambda: _dense_batches(36)),
    (_graph, lambda: _dense_batches(36)),
    (_two_input_graph, lambda: _two_inputs(36)),
])
def test_no_chunk_is_built_on_the_host(monkeypatch, make_net,
                                       make_batches):
    """``np.stack`` of a chunk's batches, and any array of a chunk's
    size made on the host, are gone from the scan path."""
    net, batches = make_net(), make_batches()
    biggest = max(np.asarray(a).nbytes for b in batches
                  for part in (b.features, b.labels)
                  for a in (part if isinstance(part, list) else [part]))
    moved = []
    real = core.to_device

    def to_device(a, dtype):
        if not isinstance(a, jax.Array):
            moved.append(np.asarray(a).nbytes)
        return real(a, dtype)

    def no_stack(*a, **k):
        raise AssertionError("np.stack on fit()'s scan path")

    monkeypatch.setattr(core, "to_device", to_device)
    monkeypatch.setattr(np, "stack", no_stack)
    net.fit(ListDataSetIterator(batches), epochs=1)
    assert net.iteration_count == 36
    assert moved and max(moved) <= biggest


@pytest.mark.parametrize("make_net,make_batches", [
    (_mln, lambda: _dense_batches(CHUNK)),
    (_two_input_graph, lambda: _two_inputs(CHUNK)),
])
def test_one_chunk_is_sixteen_place_spans_and_one_stack_span(
        make_net, make_batches):
    from benchmarks.harness.fit_spans import FitTree
    from deeplearning4j_tpu.datasets.api import payload_bytes

    tracer = Tracer(seed=5)
    prev = set_global_tracer(tracer)
    try:
        batches = make_batches()
        make_net().fit(ListDataSetIterator(batches), epochs=1)
    finally:
        set_global_tracer(prev)
    tree = FitTree(tracer.finished_spans())
    stacks = tree.named("fit.stack")
    assert [(s["attrs"]["part"], s["attrs"]["batches"])
            for s in stacks] == [("place", 1)] * CHUNK + [("stack", CHUNK)]
    assert sum(s["attrs"]["bytes"] for s in stacks) == sum(
        payload_bytes(b) for b in batches)
    assert stacks[-1]["attrs"]["bytes"] == 0
    (dispatch,) = tree.named("fit.dispatch")
    assert dispatch["attrs"]["steps"] == CHUNK
    assert tree.steps_per_dispatch() == CHUNK
    # the place spans lie between the feed's waits, the stack span just
    # before the dispatch; the harness's four shares are the whole span
    assert all(a["end"] <= b["start"] for a, b in zip(stacks, stacks[1:]))
    assert stacks[-1]["end"] <= dispatch["start"]
    shares = [tree.share("fit.feed_wait"), tree.share("fit.stack"),
              tree.share("fit.dispatch"),
              100.0 * tree.other() / tree.seconds]
    assert all(s >= 0 for s in shares)
    assert sum(shares) == pytest.approx(100.0)


class _Scores:
    """What a chunk's program hands back, on a device that finishes a
    chunk only when somebody waits for it."""

    def __init__(self, device):
        self.device = device
        self.done = False

    def __getitem__(self, i):
        return self

    def block_until_ready(self):
        if not self.done:
            self.done = True
            self.device.finished += 1
        return self


class _CountingDevice:
    def __init__(self):
        self.enqueued = 0
        self.finished = 0
        self.most_unfinished = 0

    def program(self, params, updater_state, state, *rest):
        self.enqueued += 1
        self.most_unfinished = max(self.most_unfinished,
                                   self.enqueued - self.finished)
        return params, updater_state, state, _Scores(self), rest[5]


@pytest.mark.parametrize("make_net", [_mln, _graph])
def test_run_ahead_is_one_chunk_running_and_one_queued(make_net):
    """An iterator that never blocks, on a device that never finishes
    by itself: before chunk k+2 is enqueued the driver waits for chunk
    k, and for nothing while fewer are in flight."""
    net, device = make_net(), _CountingDevice()
    net._jit_multi_step = device.program
    tracer = Tracer(seed=7)
    prev = set_global_tracer(tracer)
    try:
        net.fit(ListDataSetIterator(_dense_batches(6 * CHUNK)), epochs=1)
    finally:
        set_global_tracer(prev)
    assert device.enqueued == 6
    assert device.most_unfinished == core.SCAN_CHUNKS_AHEAD
    # chunks 3 to 6 each waited for the chunk two before them
    assert device.finished == 4
    waits = [s for s in tracer.finished_spans()
             if s.name == "fit.backpressure"]
    assert len(waits) == 4
    assert len(net._scan_inflight) == core.SCAN_CHUNKS_AHEAD


def test_prestacked_chunks_wait_for_their_slot_too():
    from deeplearning4j_tpu.datasets.api import ChunkedDataSet

    net, device = _mln(), _CountingDevice()
    net._jit_multi_step = device.program
    batches = _dense_batches(4)
    chunk = ChunkedDataSet(
        features=np.stack([b.features for b in batches]),
        labels=np.stack([b.labels for b in batches]))
    net.fit(ListDataSetIterator([chunk] * 5), epochs=1)
    assert device.enqueued == 5 and device.finished == 3
    assert device.most_unfinished == core.SCAN_CHUNKS_AHEAD
