"""The whole step's share of the chip's peak in a language-model cell
with routed experts: operations of forward and backward per example
from shapes (``counts/<config>.py``; no updater, nothing recomputed),
the held experts' at the share of token-slots the program's routing
counters report, times the examples per second of the traced window,
over the published bf16 peak."""

from benchmarks.harness.device import peaks_of
from benchmarks.harness.spec import load_module


def read(ctx):
    if ctx["device"].platform != "tpu":
        return None
    share = load_module("metrics", "moe_held_slot_share").read(ctx)
    if share is None:
        return None
    w = ctx["window"]
    flops = ctx["counts"].flops_per_example(ctx["cfg"], share / 100.0)
    return (100.0 * flops * w["examples"] / w["seconds"]
            / peaks_of(ctx["device"])["flops_bf16"])
