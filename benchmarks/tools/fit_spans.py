#!/usr/bin/env python3
"""What the fit drivers' spans cost and what they show: windows of a
fixed number of chunks in one process, turn about

    off        no profiler session, the program's default tracer: the
               spans are no-ops (the way every timed run goes)
    memory     a global ``Tracer()`` enabled by hand: spans kept in
               memory only
    profiler   inside ``jax.profiler.trace``: spans kept and written
               into the session's file beside the device's timeline

One JSON line per window with its examples/s; from every recording
window the span table (count, total and self seconds per name) and the
check that the ``fit.feed_wait`` spans add up to what the harness's
own clock on the feed read (``feed_wait_s + paced_s``); from every
profiler window the device's idle gaps by host activity
(``harness/host_spans.py``).

    python3 benchmarks/tools/fit_spans.py --workload resnet50.fit \
        --seed 7 --chunks 4
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

ORDER = ("off", "memory", "profiler", "profiler", "memory", "off")


def main(argv=None):
    import jax

    from benchmarks.drivers.fit import FitRun
    from benchmarks.harness import host_spans, trace_reduce
    from benchmarks.harness.fit_spans import FitTree
    from benchmarks.harness.spec import Cell
    from deeplearning4j_tpu.observability.trace import (
        Tracer,
        get_tracer,
        set_global_tracer,
    )

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--min-gap-ms", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    fr = FitRun(Cell(args.workload), args)
    fr.start(args.seed)
    n = args.chunks * fr.chunk
    for mode in ORDER:
        tracer = Tracer() if mode == "memory" else get_tracer()
        tracer.clear()
        prev = set_global_tracer(tracer)
        profile = None
        try:
            if mode == "profiler":
                with tempfile.TemporaryDirectory(
                        prefix="fit_spans_") as tdir:
                    with jax.profiler.trace(tdir):
                        clock = fr.window(n_batches=n)
                    profile = trace_reduce.load(
                        trace_reduce.find_xplane(tdir))
            else:
                clock = fr.window(n_batches=n)
        finally:
            set_global_tracer(prev)
        seconds, taken, wait_s, paced_s = clock
        spans = tracer.finished_spans()
        line = {"mode": mode, "chunks": taken // fr.chunk,
                "seconds": seconds,
                "examples_per_s": taken * fr.batch / seconds,
                "feed_wait_s": wait_s, "paced_s": paced_s,
                "spans_recorded": len(spans)}
        if mode != "off":
            tree = FitTree(spans)
            fed = tree.total("fit.feed_wait")
            line.update(
                fit_s=tree.seconds, path=tree.root["attrs"].get("path"),
                steps_per_dispatch=tree.steps_per_dispatch(),
                first_dispatch_ms=1e3 * tree.first_dispatch_s(),
                feed_wait_spans_s=fed,
                feed_wait_spans_over_clock=fed / (wait_s + paced_s),
                other_s=tree.other(),
                spans=[{"name": name, "count": count,
                        "total_s": round(total, 6),
                        "self_s": round(own, 6)}
                       for name, count, total, own in tree.table()],
                produce=_produce(spans))
        if profile is not None:
            found = host_spans.gaps(profile, args.min_gap_ms)
            line["idle_gaps"] = host_spans.idle_gaps(
                profile, args.min_gap_ms)
            line["longest_gaps_ms"] = [
                [label, round((b - a) * 1e-6, 3)]
                for a, b, label in sorted(
                    found, key=lambda g: g[0] - g[1])[:8]]
            line["device"] = _device_line(profile)
        print(json.dumps(line), flush=True)
    return 0


def _produce(spans):
    """The feed worker's ``prefetch.produce`` spans of the window."""
    made = [s for s in spans
            if s.name == "prefetch.produce" and s.status == "ok"]
    return {"count": len(made),
            "total_s": round(sum(s.end_time - s.start_time
                                 for s in made), 6),
            "bytes": sum(s.attrs.get("bytes", 0) for s in made)}


def _device_line(profile):
    """Busy seconds and the kernels by pass, from the same file."""
    from benchmarks.harness import kernel_names, trace_reduce

    planes = [p for p in profile.planes
              if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    trace = trace_reduce.reduce_planes(planes, 0.0)
    named, every = kernel_names.seconds(trace, kernel_names.KERNELS)
    passes = ("conv_block_fwd", "conv_block_bwd_data",
              "conv_block_bwd_weights", "matmul_block_fwd",
              "flash_attention_fwd")
    return {"busy_s": round(trace["busy_s"], 4),
            "tpu_custom_call_s": round(every, 4),
            "named_s": round(named, 4),
            "by_pass_s": {p: round(kernel_names.seconds(
                trace, (p,))[0], 4) for p in passes},
            "top_ops": [[k, round(v, 4)]
                        for k, v in trace["top_ops"][:12]]}


if __name__ == "__main__":
    sys.exit(main())
