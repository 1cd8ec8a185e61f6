"""The device's idle gaps, put down to what the host was doing.

While a JAX profiler session runs, every span of the program's tracer
is also an event on the ``/host:CPU`` plane of the session's
``.xplane.pb`` (one line per Python thread), on the time axis of the
``/device:TPU:<n>`` planes. So a gap between the device's operations
can be laid against the fit drivers' spans (``fit``, ``fit.*`` on the
thread that called ``fit()``; ``prefetch.*`` on the feed's worker).

A gap goes to the innermost span that covers most of it: each span's
claim on a gap is the part of the gap inside the span and outside its
children (nesting is by containment on one thread's line), and the
part under no span at all claims for ``(no span)``. Where the
worker's ``prefetch.produce`` spans cover more than half of a gap the
label says so too (``fit.feed_wait+prefetch.produce``: ``fit()``
waited because the worker was still making the batch).

The window is from the start of the ``fit`` event (else the first
device operation) to the end of the last device operation: the lead
before the first dispatch counts, the time after the device's last
operation does not.
"""

from collections import defaultdict

from benchmarks.harness.trace_reduce import (
    DEVICE_PREFIX,
    OPS_LINE,
    line_events,
)

HOST_PLANE = "/host:CPU"
NO_SPAN = "(no span)"
WORKER_PREFIX = "prefetch."


def _is_span(name):
    return name == "fit" or name.startswith(("fit.", WORKER_PREFIX))


def busy_intervals(plane):
    """The union of the ``XLA Ops`` intervals, as merged [start, end]."""
    merged = []
    for _, s, e, _ in sorted(line_events(plane, OPS_LINE),
                             key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def host_lines(profile):
    """[[(name, start_ns, end_ns)]]: the program's spans on each line
    of the host plane, outermost first."""
    lines = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events if _is_span(e.name)]
            if events:
                lines.append(sorted(events,
                                    key=lambda ev: (ev[1], -ev[2])))
    return lines


def _claims(events, a, b):
    """{name: ns of [a, b] inside a span of that name and outside its
    children}, for one line's events."""
    out = defaultdict(float)
    stack = []  # (name, end) of the open enclosing spans
    for name, s, e in events:
        if e <= a or s >= b:
            continue
        lo, hi = max(s, a), min(e, b)
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= hi - lo
        out[name] += hi - lo
        stack.append((name, e))
    return out


def label_of(lines, a, b):
    """The label of the gap [a, b] (see the module's docstring)."""
    consumer, worker = defaultdict(float), 0.0
    for events in lines:
        for name, ns in _claims(events, a, b).items():
            if name.startswith(WORKER_PREFIX):
                worker += ns
            else:
                consumer[name] += ns
    best = max(consumer, key=consumer.get, default=None)
    uncovered = (b - a) - sum(consumer.values())
    label = best if best and consumer[best] > uncovered else None
    if worker > 0.5 * (b - a):
        label = (label + "+" if label else "") + WORKER_PREFIX + "produce"
    return label or NO_SPAN


def gaps(profile, min_gap_ms=1.0):
    """[(start_ns, end_ns, label)] of the first device plane's idle
    gaps longer than ``min_gap_ms`` inside the window."""
    planes = [p for p in profile.planes
              if p.name.startswith(DEVICE_PREFIX)]
    if not planes:
        return []
    busy = busy_intervals(planes[0])
    if not busy:
        return []
    lines = host_lines(profile)
    fits = [s for events in lines for n, s, _ in events if n == "fit"]
    start = min(fits) if fits else busy[0][0]
    edges = [start] + [t for iv in busy for t in iv]
    out = []
    for a, b in zip(edges[0::2], edges[1::2]):  # idle: end -> next start
        if b - a > min_gap_ms * 1e6:
            out.append((a, b, label_of(lines, a, b)))
    return out


def idle_gaps(profile, min_gap_ms=1.0):
    """[[label, seconds], ...], longest first: the device's idle time
    in gaps longer than ``min_gap_ms``, summed by what the host was
    doing (the shape of a result's ``breakdown.idle_gaps``)."""
    by_label = defaultdict(float)
    for a, b, label in gaps(profile, min_gap_ms):
        by_label[label] += (b - a) * 1e-9
    return [[k, v] for k, v in sorted(by_label.items(),
                                      key=lambda kv: -kv[1])]
