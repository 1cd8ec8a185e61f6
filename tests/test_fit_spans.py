"""The spans inside the fit drivers (``nn/core.py``): recorded by the
global tracer whenever a JAX profiler session runs (or the tracer was
enabled explicitly), silent otherwise; one ``fit`` tree per call on
every path; the same intervals in the session's ``.xplane.pb``."""

import glob
import os
import sys

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.datasets.prefetch import PrefetchIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import trace as trace_mod
from deeplearning4j_tpu.observability.trace import (
    Tracer,
    get_tracer,
    set_global_tracer,
)

N_BATCHES, ROWS = 20, 8
DRIVER = ("fit.feed_wait", "fit.stack", "fit.dispatch", "fit.listeners")


@pytest.fixture(autouse=True)
def default_global_tracer():
    """The process's default tracer, empty, whatever ran before."""
    prev = set_global_tracer(Tracer(enabled=False, follow_profiler=True))
    yield
    set_global_tracer(prev)


def _batches(n=N_BATCHES):
    rng = np.random.RandomState(0)
    return [DataSet(features=rng.rand(ROWS, 4).astype(np.float32),
                    labels=np.eye(2, dtype=np.float32)[
                        rng.randint(0, 2, ROWS)])
            for _ in range(n)]


def _mln():
    conf = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph():
    conf = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
            .graph_builder().add_inputs("in")
            .add_layer("h", DenseLayer(n_in=4, n_out=8,
                                       activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=2), "h")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


class _PerStepListener:
    """No ``supports_batched_iterations``: ``fit()`` takes one step a
    batch."""

    def iteration_done(self, model, iteration):
        pass


def _net(path):
    if path == "graph":
        return _graph()
    net = _mln()
    if path == "step":
        net.set_listeners(_PerStepListener())
    elif path == "megastep":
        net.set_transforms(megastep=4)
    return net


PATHS = {"scan": "scan", "step": "step", "megastep": "megastep",
         "graph": "scan"}


def _traced_fit(tmp_path, net, data, **kw):
    with jax.profiler.trace(str(tmp_path / "trace")):
        net.fit(data, **kw)
    return get_tracer().finished_spans()


def _tree(spans):
    """(root, its descendants) of the one ``fit`` tree."""
    roots = [s for s in spans if s.name == "fit"]
    assert len(roots) == 1
    tree = [s for s in spans
            if s.trace_id == roots[0].trace_id and s is not roots[0]]
    return roots[0], tree


def _dur(s):
    return s.end_time - s.start_time


def test_no_session_no_span_is_made(monkeypatch):
    made = []
    init = trace_mod.Span.__init__
    monkeypatch.setattr(
        trace_mod.Span, "__init__",
        lambda self, *a, **k: (made.append(a[1]), init(self, *a, **k))[1])
    net = _mln()
    net.fit(PrefetchIterator(ListDataSetIterator(_batches())), epochs=1)
    assert made == [] and get_tracer().finished_spans() == []
    assert net._fit_span is None


@pytest.mark.parametrize("path", list(PATHS))
def test_one_fit_root_and_dispatch_steps_sum_to_the_batches(tmp_path,
                                                            path):
    spans = _traced_fit(tmp_path, _net(path),
                        ListDataSetIterator(_batches()), epochs=1)
    root, tree = _tree(spans)
    assert root.attrs == {"epochs": 1, "path": PATHS[path]}
    assert root.parent_id is None and root.status == "ok"
    dispatches = [s for s in tree if s.name == "fit.dispatch"]
    assert sum(s.attrs["steps"] for s in dispatches) == N_BATCHES
    assert sum(s.attrs["rows"] for s in dispatches) == N_BATCHES * ROWS
    assert [s.attrs["first_step"] for s in dispatches] == sorted(
        s.attrs["first_step"] for s in dispatches)
    if path in ("scan", "graph"):  # 16 fused, then the remainder of 4
        assert [s.attrs["steps"] for s in dispatches] == [16, 4]
        # each batch copied over as it arrives, each chunk stacked in
        # device memory: nothing left for the stack to copy
        stacks = [s for s in tree if s.name == "fit.stack"]
        assert [(s.attrs["part"], s.attrs["batches"]) for s in stacks] \
            == [("place", 1)] * 16 + [("stack", 16)] \
            + [("place", 1)] * 4 + [("stack", 4)]
        assert [s.attrs["bytes"] for s in stacks] == \
            ([ROWS * (4 + 2) * 4] * 16 + [0]
             + [ROWS * (4 + 2) * 4] * 4 + [0])
    if path == "megastep":
        assert [s.attrs["steps"] for s in dispatches] == [4] * 5
    if path == "step":
        assert len(dispatches) == N_BATCHES
        listeners = [s for s in tree if s.name == "fit.listeners"]
        assert sum(s.attrs["steps"] for s in listeners) == N_BATCHES
    (epoch,) = [s for s in tree if s.name == "fit.epoch"]
    assert epoch.attrs == {"epoch": 0, "batches": N_BATCHES}
    feeds = [s for s in tree if s.name == "fit.feed_wait"]
    assert sum(s.attrs["batches"] for s in feeds) == N_BATCHES
    assert [s.status for s in feeds] == ["ok"] * N_BATCHES + ["exhausted"]


@pytest.mark.parametrize("path", list(PATHS))
def test_children_lie_inside_parents_and_shares_sum_to_the_root(
        tmp_path, path):
    spans = _traced_fit(tmp_path, _net(path),
                        ListDataSetIterator(_batches()), epochs=2)
    root, tree = _tree(spans)
    by_id = {s.span_id: s for s in [root] + tree}
    for s in tree:
        parent = by_id[s.parent_id]
        assert parent.start_time <= s.start_time <= s.end_time \
            <= parent.end_time, (s.name, parent.name)
    epochs = [s for s in tree if s.name == "fit.epoch"]
    assert len(epochs) == 2
    assert all(by_id[s.parent_id] in epochs
               for s in tree if s.name in DRIVER)
    # the drivers' spans are laid end to end on one thread: the four
    # shares (feed wait, stack, dispatch, everything else) make up the
    # root's duration exactly, everything else being self time
    total = {n: sum(_dur(s) for s in tree if s.name == n) for n in DRIVER}
    self_root = _dur(root) - sum(_dur(e) for e in epochs)
    self_epochs = sum(_dur(e) for e in epochs) - sum(total.values())
    assert self_root >= 0 and self_epochs >= 0
    other = self_root + self_epochs + total["fit.listeners"]
    assert (total["fit.feed_wait"] + total["fit.stack"]
            + total["fit.dispatch"] + other) == pytest.approx(_dur(root))


def test_device_cached_path_hangs_its_dispatches_on_the_root(tmp_path):
    spans = _traced_fit(tmp_path, _mln(), _batches(), epochs=2)
    root, tree = _tree(spans)
    assert root.attrs["path"] == "device_cached"
    dispatches = [s for s in tree if s.name == "fit.dispatch"]
    assert sum(s.attrs["steps"] for s in dispatches) == 2 * N_BATCHES
    assert {s.parent_id for s in dispatches} == {root.span_id}


def test_prefetch_produce_spans_come_from_the_worker(tmp_path):
    batches = _batches()
    spans = _traced_fit(tmp_path, _mln(),
                        PrefetchIterator(ListDataSetIterator(batches)),
                        epochs=1)
    root, _ = _tree(spans)
    produced = [s for s in spans if s.name == "prefetch.produce"]
    assert [s.status for s in produced] == \
        ["ok"] * N_BATCHES + ["exhausted"]
    item_bytes = batches[0].features.nbytes + batches[0].labels.nbytes
    assert all(s.attrs["bytes"] == item_bytes for s in produced[:-1])
    # roots of their own: the worker runs ahead of fit()
    assert all(s.parent_id is None and s.trace_id != root.trace_id
               for s in produced)
    # and on the profiler's timeline they sit on another thread's line
    lines = _host_lines(tmp_path)
    fit_lines = {i for i, names in lines.items() if "fit" in names}
    produce_lines = {i for i, names in lines.items()
                     if "prefetch.produce" in names}
    assert len(fit_lines) == 1 and produce_lines
    assert not fit_lines & produce_lines


def _host_events(tmp_path):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(tmp_path / "trace"), "plugins", "profile", "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("fit", "prefetch.")):
                    yield i, e


def _host_lines(tmp_path):
    lines = {}
    for i, e in _host_events(tmp_path):
        lines.setdefault(i, set()).add(e.name)
    return lines


def test_spans_are_in_the_written_xplane_with_their_attrs(tmp_path):
    spans = _traced_fit(tmp_path, _mln(),
                        ListDataSetIterator(_batches()), epochs=1)
    root, tree = _tree(spans)
    events = [e for _, e in _host_events(tmp_path)]
    names = [e.name for e in events]
    assert names.count("fit") == 1 and names.count("fit.epoch") == 1
    assert names.count("fit.feed_wait") == N_BATCHES + 1
    stats = {e.name: dict(e.stats) for e in events}
    assert stats["fit"] == {"epochs": 1, "path": "scan"}
    assert stats["fit.epoch"] == {"epoch": 0, "batches": N_BATCHES}
    dispatch = [dict(e.stats) for e in events if e.name == "fit.dispatch"]
    assert [d["steps"] for d in dispatch] == [16, 4]
    assert dispatch[0] == {"steps": 16, "rows": 16 * ROWS,
                           "first_step": 1}
    # one clock: an event lasts what its span lasted, and sits where
    # the span sat inside the root
    fit_ev = next(e for e in events if e.name == "fit")
    assert fit_ev.duration_ns * 1e-9 == pytest.approx(_dur(root),
                                                      rel=0.05, abs=2e-4)
    d0 = next(s for s in tree if s.name == "fit.dispatch")
    e0 = next(e for e in events if e.name == "fit.dispatch")
    assert (e0.start_ns - fit_ev.start_ns) * 1e-9 == pytest.approx(
        d0.start_time - root.start_time, abs=2e-4)


def test_explicitly_disabled_tracer_stays_silent_in_a_session(tmp_path):
    silent = Tracer(enabled=False)
    set_global_tracer(silent)
    spans = _traced_fit(tmp_path, _mln(),
                        ListDataSetIterator(_batches()), epochs=1)
    assert spans == [] and silent.finished_spans() == []
    assert not list(_host_events(tmp_path))


def test_explicitly_enabled_tracer_records_without_a_session():
    tracer = Tracer(seed=3)
    set_global_tracer(tracer)
    _mln().fit(ListDataSetIterator(_batches()), epochs=1)
    root, tree = _tree(tracer.finished_spans())
    assert root.attrs["path"] == "scan"
    assert sum(s.attrs["steps"] for s in tree
               if s.name == "fit.dispatch") == N_BATCHES


def test_exception_in_fit_ends_the_open_spans_with_error(tmp_path):
    class Broken(ListDataSetIterator):
        def next(self):
            if self._pos == 3:
                raise RuntimeError("bad shard")
            return super().next()

    net = _mln()
    with jax.profiler.trace(str(tmp_path / "trace")):
        with pytest.raises(RuntimeError, match="bad shard"):
            net.fit(Broken(_batches()), epochs=1)
    spans = get_tracer().finished_spans()
    root, tree = _tree(spans)
    (epoch,) = [s for s in tree if s.name == "fit.epoch"]
    feeds = [s for s in tree if s.name == "fit.feed_wait"]
    assert root.status == epoch.status == "error"
    assert root.attrs["error_type"] == "RuntimeError"
    assert [s.status for s in feeds] == ["ok"] * 3 + ["error"]
    assert all(s.end_time is not None for s in [root] + tree)
    assert net._fit_span is None
    # nothing was left open on the profiler's timeline either
    names = [e.name for _, e in _host_events(tmp_path)]
    assert names.count("fit") == 1 and names.count("fit.epoch") == 1


def test_fit_minibatch_outside_fit_is_a_root_of_its_own(tmp_path):
    net = _mln()
    with jax.profiler.trace(str(tmp_path / "trace")):
        net.fit_minibatch(_batches(1)[0])
    # the step's first call compiles inside the session: where the
    # process keeps compile phase records they join it as compile.*
    (span,) = [s for s in get_tracer().finished_spans()
               if not s.name.startswith("compile.")]
    assert span.name == "fit.dispatch" and span.parent_id is None
    assert span.attrs == {"steps": 1, "rows": ROWS, "first_step": 1}


def test_session_started_mid_fit_records_from_there(tmp_path):
    """``ProfilerListener`` starts its session inside ``fit()``: the
    drivers' spans after that point record, as roots (their ``fit``
    began untraced)."""
    from deeplearning4j_tpu.optimize import ProfilerListener

    net = _mln()
    listener = ProfilerListener(str(tmp_path / "trace"),
                                start_iteration=1, num_iterations=100)
    net.set_listeners(listener)
    net.fit(ListDataSetIterator(_batches(48)), epochs=1)
    spans = get_tracer().finished_spans()
    assert not [s for s in spans if s.name == "fit"]
    dispatches = [s for s in spans if s.name == "fit.dispatch"]
    # the session opened in the first chunk's callbacks
    assert [s.attrs["first_step"] for s in dispatches] == [17, 33]
    assert not trace_mod.profiler_session_active()  # closed at epoch end


@pytest.mark.skipif(sys.version_info[:2] != (3, 12),
                    reason="frame sizes are the interpreter's: "
                           "re-measure on another CPython")
def test_scan_chain_keeps_its_data_stack_footprint():
    """The frames between ``fit()`` and the jitted scan program are on
    the stack while JAX traces and lowers it. CPython 3.12 keeps frames
    on 16 KiB data-stack chunks and unmaps a chunk the moment its first
    frame pops, so where the chain ends decides whether the lowering's
    hot calls map and unmap a chunk each: with 46 slots more in this
    chain the same MLIR conversion took 21.4 s against 14.7 s on the
    chip (and 0.7 s at a lucky offset: PERF.md, PR 28). A change to
    these functions is a change to ``setup_s``: measure it on the chip
    (``benchmarks/run.py``, both cells) and then move this number."""
    from deeplearning4j_tpu.nn import core

    def footprint(fn):
        code = fn.__code__
        cells = [c for c in code.co_cellvars
                 if c not in code.co_varnames]
        return (len(code.co_varnames) + len(cells)
                + len(code.co_freevars) + code.co_stacksize)

    chain = (core.fit_batches, core.fit_epoch_scan,
             core.flush_scan_chunk, core.run_scan_chunk)
    assert sum(footprint(fn) for fn in chain) == 82
