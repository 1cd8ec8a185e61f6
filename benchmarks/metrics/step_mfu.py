"""The whole step's share of the chip's peak: operations of forward and
backward per example, counted from shapes (``counts/<config>.py``; no
updater, nothing recomputed), times the examples per second of the
traced window, over the published bf16 peak. Nothing is read on a
device without a table entry."""

from benchmarks.harness.device import peaks_of


def read(ctx):
    if ctx["device"].platform != "tpu":
        return None
    w = ctx["window"]
    flops = ctx["counts"].flops_per_example(ctx["cfg"])
    return (100.0 * flops * w["examples"] / w["seconds"]
            / peaks_of(ctx["device"])["flops_bf16"])
