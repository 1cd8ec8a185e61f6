"""The window's feed: whole chunks only, through the scan path."""

import time

import numpy as np
import pytest


def _net_and_batches(n=4):
    from benchmarks.drivers.fit import build_program, sized
    from benchmarks.harness import data
    from benchmarks.harness.spec import Cell

    cell = Cell("chartransformer12.fit")
    cfg = sized(cell.config, True)
    net = build_program(cfg, 1).init()
    return net, data.make_batches(cfg["input"], 4, n, 1)


def test_deadline_feed_ends_on_a_chunk_boundary():
    from benchmarks.harness.feed import DeadlineFeed

    _, batches = _net_and_batches()
    feed = DeadlineFeed(batches, 16, deadline=time.perf_counter() + 0.05)
    n = 0
    while feed.has_next():
        feed.next()
        n += 1
        time.sleep(0.002)
    assert n and n % 16 == 0
    feed.reset()  # fit() resets at the epoch's end: no second epoch
    assert not feed.has_next()
    fixed = DeadlineFeed(batches, 16, n_batches=32)
    assert sum(1 for _ in fixed) == 32
    with pytest.raises(ValueError):
        DeadlineFeed(batches, 16, n_batches=20)


def test_fit_takes_the_scan_path_one_dispatch_per_chunk(monkeypatch):
    from benchmarks.drivers.fit import fit_window
    from deeplearning4j_tpu.nn import core

    net, batches = _net_and_batches()
    chunks, singles = [], []
    real = core.run_scan_chunk
    monkeypatch.setattr(core, "run_scan_chunk", lambda m, st: (
        chunks.append(st[4]), real(m, st))[1])
    monkeypatch.setattr(type(net), "fit_minibatch",
                        lambda self, ds: singles.append(ds))
    seconds, taken, wait_s, paced_s = fit_window(
        net, batches, net.scan_chunk, 2, n_batches=48)
    assert net.scan_chunk == 16  # the default a user gets
    assert taken == 48 and chunks == [16, 16, 16] and not singles
    assert net.iteration_count == 48
    assert 0 <= wait_s < seconds and 0 <= paced_s < seconds
    assert np.isfinite(float(net.score_value))
