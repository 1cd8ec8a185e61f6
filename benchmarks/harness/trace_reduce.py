"""From the profiler's ``.xplane.pb`` to numbers, with nothing but
``jax.profiler.ProfileData``.

A TPU's plane is ``/device:TPU:<n>``. Its ``XLA Ops`` line holds one
event per executed HLO operation, an enclosing operation (a ``while``
around a scan's body, a fusion's call) spanning its children; its
``XLA Modules`` line one event per program run. From the operations:

    busy_s     the union of their intervals (nesting and overlap counted
               once), averaged over the device planes
    self time  an event's duration less that of the events nested
               directly inside it, so that a ``while`` does not count its
               body twice; summed by operation name and by category
    category   read from the event's name, which on the TPU plane is the
               operation's HLO text: its opcode (``convolution``,
               ``copy``), ``fusion:<kind>`` for a fusion, the target for
               a custom call (``tpu_custom_call`` is a Mosaic kernel);
               an ``hlo_category`` stat wins where the profiler gives
               one; anything else is ``uncategorized``
    stem       the operation's own name without its number
               (``%broadcast_maximum_fusion.34`` -> the stem
               ``broadcast_maximum_fusion``): XLA names a fusion after
               what is in it
"""

import functools
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


# the same operation runs in every step, so a trace has few distinct
# names among its hundreds of thousands of events
@functools.lru_cache(maxsize=None)
def short_name(name):
    """``%fusion.12 fusion:kLoop`` from an operation's HLO text."""
    head = name.split(" = ", 1)[0]
    cat = category_of(name)
    return head if cat == "uncategorized" else f"{head} {cat}"


@functools.lru_cache(maxsize=None)
def stem_of(name):
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


@functools.lru_cache(maxsize=None)
def category_of(name):
    if " = " not in name:
        return "uncategorized"
    rest = name.split(" = ", 1)[1]
    m = _OPCODE.search(rest)
    if not m:
        return "uncategorized"
    op = m.group(1)
    if op == "fusion":
        kind = _KIND.search(rest)
        return f"fusion:{kind.group(1)}" if kind else "fusion"
    if op == "custom-call":
        target = _TARGET.search(rest)
        return target.group(1) if target else "custom-call"
    return op


def _category(event):
    for name, value in event.stats:
        if name == "hlo_category":
            return str(value)
    return category_of(event.name)


def line_events(plane, line_name):
    """[(name, start_ns, end_ns, category)] of one line of a plane."""
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for e in line.events:
            start = float(e.start_ns)
            out.append((e.name, start, start + float(e.duration_ns),
                        _category(e)))
    return out


def union_ns(events):
    """Total length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, s, e, _ in sorted(events, key=lambda ev: ev[1]):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events):
    """[(name, self_ns, category)]: each event's duration less the
    events nested directly inside it."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = [[n, e - s, c] for n, s, e, c in order]
    stack = []  # indices of open enclosing events
    for i, (_, s, e, _) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            out[stack[-1]][1] -= e - s
        stack.append(i)
    return [(n, max(t, 0.0), c) for n, t, c in out]


def reduce_planes(planes, window_s):
    """The reduction of the device planes of one traced window."""
    busy, modules = [], 0
    by_name, by_cat = defaultdict(float), defaultdict(float)
    by_stem = defaultdict(float)
    n_events = 0
    for plane in planes:
        ops = line_events(plane, OPS_LINE)
        n_events += len(ops)
        busy.append(union_ns(ops) * 1e-9)
        modules += len(line_events(plane, MODULES_LINE))
        for name, t, cat in self_times(ops):
            by_name[short_name(name)] += t * 1e-9
            by_cat[cat] += t * 1e-9
            by_stem[f"{stem_of(name)} [{cat}]"] += t * 1e-9
    n = max(len(planes), 1)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "planes": len(planes), "events": n_events,
        "module_runs": modules, "window_s": window_s,
        "busy_s": sum(busy) / n if busy else 0.0,
        "by_category_s": {k: v / n for k, v in by_cat.items()},
        "by_stem_s": {k: v / n for k, v in by_stem.items()},
        "top_ops": [[k, v / n] for k, v in top[:40]],
    }


def reduce_dir(trace_dir, window_s):
    pd = load(find_xplane(trace_dir))
    planes = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    return reduce_planes(planes, window_s)


def summary(trace):
    return {
        "planes": trace["planes"], "events": trace["events"],
        "module_runs": trace["module_runs"],
        "busy_s": round(trace["busy_s"], 4),
        "window_s": round(trace["window_s"], 4),
        "by_category_s": {k: round(v, 4) for k, v in sorted(
            trace["by_category_s"].items(), key=lambda kv: -kv[1])},
        "by_stem_s": {k: round(v, 4) for k, v in sorted(
            trace["by_stem_s"].items(), key=lambda kv: -kv[1])[:25]},
        "top_ops": [[k, round(v, 4)] for k, v in trace["top_ops"][:15]],
    }


def describe(path, limit=6):
    """What is in a trace, for reading one by hand: planes, lines,
    event counts, and the first events of each line with their stats."""
    lines = []
    for plane in load(path).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name}: {len(events)} events")
            for e in events[:limit]:
                stats = {str(k): str(v)[:60] for k, v in e.stats}
                lines.append(
                    f"    {e.name[:70]} start={e.start_ns:.0f} "
                    f"dur={e.duration_ns:.0f} {stats}")
    return "\n".join(lines)
