"""The matrix work's share of its roofline in a language-model cell
with routed experts. Numerator: the least time the chip could take for
one step's latent-attention products, attention, dense, shared and
held-expert products (at the share of token-slots the routing counters
report) and the two heads, forward and backward, from shapes
(``counts/<config>.py``). Denominator: the device time per step of the
events that implement them, by the categories ``kernels_roofline.json``
lists: the grouped products of ``jax.lax.ragged_dot`` are Mosaic
kernels of XLA's own (``tpu_custom_call``, stem ``ragged-dot-*``), as
the flash pair is, so no category is added."""

from benchmarks.harness.device import peaks_of
from benchmarks.harness.spec import load_module


def read(ctx):
    trace, w = ctx["trace"], ctx["window"]
    if ctx["device"].platform != "tpu" or not w["steps"]:
        return None
    share = load_module("metrics", "moe_held_slot_share").read(ctx)
    spent = load_module("metrics", "kernels_roofline").kernel_seconds(
        trace) / w["steps"]
    if share is None or not spent:
        return None
    least = ctx["counts"].roofline_seconds_per_step(
        ctx["cfg"], w["batch"], peaks_of(ctx["device"]), share / 100.0)
    return 100.0 * least / spent
