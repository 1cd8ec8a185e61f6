"""The window's input feed and the clock on it.

``DeadlineFeed`` cycles over a fixed set of host batches and stops at a
chunk boundary, so that a window holds whole scan chunks (one compiled
program, no remainder). ``TimedIterator`` wraps the outermost iterator
handed to ``fit()`` and sums the time ``fit()`` spends waiting inside
it. Both are ``DataSetIterator``s of the program's SPI.

``ChunkPacer`` is the feed's back-pressure. ``fit()``'s scan path
dispatches a chunk and goes straight on to stack the next: nothing in it
waits for the device, so a feed that is never empty lets the host run
any number of chunks ahead, each with its stacked inputs already on the
device, and a deadline on the host's clock would say nothing about the
device's progress. The pacer lets ``fit()`` have the first batch of a
new chunk only once the chunk before the one just dispatched has
finished: one chunk runs, one is queued, the host prepares the third.
"""

import collections
import functools
import time

from deeplearning4j_tpu.datasets.api import DataSetIterator


class DeadlineFeed(DataSetIterator):
    """Hands out ``batches`` round-robin. Stops at the first multiple of
    ``chunk`` at which ``deadline`` (a ``time.perf_counter`` value) has
    passed, or after ``n_batches`` (a multiple of ``chunk``) where that
    is given. One use: a ``reset()`` does not re-arm it, because
    ``fit()`` resets its iterator again when the epoch ends."""

    def __init__(self, batches, chunk, deadline=None, n_batches=None):
        if (deadline is None) == (n_batches is None):
            raise ValueError("give a deadline or a number of batches")
        if n_batches is not None and n_batches % chunk:
            raise ValueError(
                f"n_batches {n_batches} is not a multiple of the scan "
                f"chunk {chunk}: the window would compile a remainder"
            )
        self._batches = list(batches)
        self._chunk = int(chunk)
        self._deadline = deadline
        self._n = n_batches
        self.handed = 0
        self._done = False

    def has_next(self):
        if self._done:
            return False
        if self.handed and self.handed % self._chunk == 0:
            if self._n is not None:
                self._done = self.handed >= self._n
            else:
                self._done = time.perf_counter() >= self._deadline
        return not self._done

    def next(self):
        ds = self._batches[self.handed % len(self._batches)]
        self.handed += 1
        return ds

    def reset(self):
        pass

    def batch(self):
        return self._batches[0].num_examples()


class TimedIterator(DataSetIterator):
    """Delegates to ``inner`` and adds up the seconds the consumer
    spends inside ``has_next()`` and ``next()``: the time ``fit()``
    waits for its input."""

    def __init__(self, inner, chunk=None, pace=None):
        self.inner = inner
        self.chunk = chunk
        self.pace = pace
        self.wait_s = 0.0
        self.paced_s = 0.0
        self.taken = 0

    def has_next(self):
        t0 = time.perf_counter()
        try:
            return self.inner.has_next()
        finally:
            self.wait_s += time.perf_counter() - t0

    def next(self):
        t0 = time.perf_counter()
        if self.pace and self.taken and self.taken % self.chunk == 0:
            self.pace()
            self.paced_s += time.perf_counter() - t0
            t0 = time.perf_counter()
        try:
            ds = self.inner.next()
        finally:
            self.wait_s += time.perf_counter() - t0
        self.taken += 1
        return ds

    def reset(self):
        self.inner.reset()

    def shutdown(self, *args, **kwargs):
        return self.inner.shutdown(*args, **kwargs)

    def batch(self):
        return self.inner.batch()


@functools.cache
def _first_element():
    """One jitted read for the whole process, so that only the warm-up
    compiles it."""
    import jax

    return jax.jit(lambda a: a.reshape(-1)[0])


class ChunkPacer:
    """Called when ``fit()`` asks for the first batch of a new chunk,
    that is right after it dispatched one. Takes a marker that depends
    on the dispatched chunk's result (one element of the smallest
    parameter leaf, through a jitted read that is compiled during the
    warm-up) and waits for the marker taken one chunk earlier."""

    def __init__(self, net):
        self._net = net
        leaves = [(layer, name) for layer, lp in net.params.items()
                  for name in lp]
        self._where = min(
            leaves, key=lambda ln: net.params[ln[0]][ln[1]].size)
        self._markers = collections.deque()

    def __call__(self):
        layer, name = self._where
        self._markers.append(
            _first_element()(self._net.params[layer][name]))
        if len(self._markers) > 1:
            self._markers.popleft().block_until_ready()
