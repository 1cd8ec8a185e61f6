"""The fit drivers' span tree of the traced window, taken from the
program's global tracer after the window.

The program (``deeplearning4j_tpu/nn/core.py``) records one tree per
``fit()`` call whenever a JAX profiler session runs:

    fit                       the whole call (attrs: epochs, path)
      fit.epoch               one epoch (epoch, batches)
        fit.feed_wait         each next() of the iterator handed in
                              (batches: 1, or k of a pre-stacked chunk)
        fit.stack             host stacking + the copy's enqueue
                              (batches, bytes)
        fit.dispatch          the enqueue of one program run
                              (steps, rows, first_step)
        fit.listeners         listener callbacks (steps)

Under ``--trace 1`` the warm-up ``fit()`` ran before the session and
the reference never calls the program, so the tracer's ring holds one
``fit`` tree: the window's. The shares the readers give are of the
``fit`` span, which ends when ``fit()`` returns; the driver's window
goes on until the parameters are ready, one to two chunks longer, and
that tail is the device's, not the fit drivers'.

A span's self time is its duration less what its children cover.
"""

DRIVER_SPANS = ("fit.feed_wait", "fit.stack", "fit.dispatch",
                "fit.listeners")


def program_records_fit_spans():
    """Whether the program under test has spans inside its fit drivers
    at all. A commit from before them has none to read: the readers
    then return nothing (and raise nothing), as the harness asks of a
    metric the program cannot feed."""
    from deeplearning4j_tpu.observability import trace

    return hasattr(trace, "profiler_session_active")


def _as_dict(span):
    return span if isinstance(span, dict) else span.to_dict()


def covered(intervals):
    """Length of the union of ``[(start, end)]``."""
    total, edge = 0.0, None
    for s, e in sorted(intervals):
        if edge is None or s > edge:
            total += e - s
            edge = e
        elif e > edge:
            total += e - edge
            edge = e
    return total


class FitTree:
    """The newest ``fit`` root among ``spans`` (``Span`` objects or
    their ``to_dict()`` form) and its descendants. Raises where there
    is no such root, or where the ring dropped part of the tree: the
    epochs' ``batches`` then disagree with the batches the
    ``fit.feed_wait`` spans handed over."""

    def __init__(self, spans):
        spans = [_as_dict(s) for s in spans]
        roots = [s for s in spans
                 if s["name"] == "fit" and s["parent_id"] is None]
        if not roots:
            raise LookupError(
                "no 'fit' root span among the tracer's "
                f"{len(spans)} finished spans: the window's fit() "
                "recorded nothing, or the ring dropped its root")
        self.root = max(roots, key=lambda s: s["start"])
        self.spans = [s for s in spans
                      if s["trace_id"] == self.root["trace_id"]
                      and s is not self.root]
        fed = sum(s["attrs"].get("batches", 0)
                  for s in self.named("fit.feed_wait"))
        counted = sum(s["attrs"].get("batches", 0)
                      for s in self.named("fit.epoch"))
        if fed != counted or not counted:
            raise LookupError(
                f"the fit.epoch spans count {counted} batches but the "
                f"fit.feed_wait spans found handed over {fed}: the "
                "tracer's ring overflowed (Tracer(max_finished=...))")

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    @property
    def seconds(self):
        return self.root["end"] - self.root["start"]

    def total(self, name):
        return sum(s["end"] - s["start"] for s in self.named(name))

    def share(self, name):
        """Percent of the ``fit`` span inside spans called ``name``."""
        return 100.0 * self.total(name) / self.seconds

    def other(self):
        """The ``fit`` span less feed wait, stacking and dispatch: the
        self time of ``fit`` and ``fit.epoch`` plus the listeners."""
        return self.seconds - sum(
            self.total(n) for n in DRIVER_SPANS[:3])

    def self_time(self, span):
        kids = [(s["start"], s["end"]) for s in self.spans
                if s["parent_id"] == span["span_id"]]
        return (span["end"] - span["start"]) - covered(kids)

    def steps_per_dispatch(self):
        runs = self.named("fit.dispatch")
        return sum(s["attrs"]["steps"] for s in runs) / len(runs)

    def first_dispatch_s(self):
        """From the start of ``fit`` to the start of its first
        ``fit.dispatch``: the ramp before the device has any work."""
        return min(s["start"] for s in self.named("fit.dispatch")) \
            - self.root["start"]

    def table(self):
        """[(name, count, total seconds, self seconds)], the root
        first, then by total."""
        rows = {}
        for s in [self.root] + self.spans:
            n, t, own = rows.get(s["name"], (0, 0.0, 0.0))
            rows[s["name"]] = (n + 1, t + s["end"] - s["start"],
                               own + self.self_time(s))
        return sorted(((k, *v) for k, v in rows.items()),
                      key=lambda r: (r[0] != "fit", -r[2]))


def of_window():
    """The window's tree from the program's global tracer; ``None``
    where the program predates the spans."""
    if not program_records_fit_spans():
        return None
    from deeplearning4j_tpu.observability.trace import get_tracer

    return FitTree(get_tracer().finished_spans())
