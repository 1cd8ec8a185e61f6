"""The matrix work's share of its roofline. Numerator: the least time
the chip could take for one step's convolutions, products and
attention, forward and backward, from shapes
(``counts/<config>.py``: per layer the larger of operations over peak
FLOP/s and bytes over peak bytes/s) - the same work whatever implements
it. Denominator: the device time per step of the events that implement
it, by the categories listed in ``kernels_roofline.json``."""

from benchmarks.harness.device import peaks_of
from benchmarks.harness.spec import load_json


def kernel_seconds(trace):
    cats = set(load_json("metrics", "kernels_roofline.json")["categories"])
    return sum(v for k, v in trace["by_category_s"].items() if k in cats)


def read(ctx):
    trace, w = ctx["trace"], ctx["window"]
    if ctx["device"].platform != "tpu" or not w["steps"]:
        return None
    spent = kernel_seconds(trace) / w["steps"]
    if not spent:
        return None
    least = ctx["counts"].roofline_seconds_per_step(
        ctx["cfg"], w["batch"], peaks_of(ctx["device"]))
    return 100.0 * least / spent
