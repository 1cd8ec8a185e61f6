"""The gated short convolution's kernels as a share of their roofline:
the least time a step of them needs (``counts/<config>.py``
``short_conv_seconds_per_step``: their bytes over peak bytes/s, the
forward kernel twice a layer under full remat where the trace holds a
``short_conv_fwd`` kernel, the backward once where it holds a
``short_conv_bwd``), over the device time of the ``short_conv_*``
kernels a step; nothing where the trace has no such kernel or the
counts have no such function."""

from benchmarks.harness import kernel_names
from benchmarks.harness.device import peaks_of


def read(ctx):
    least = getattr(ctx["counts"], "short_conv_seconds_per_step", None)
    spent = kernel_names.ms_per_step(ctx, "short_conv_")
    if least is None or spent is None or ctx["device"].platform != "tpu":
        return None
    held = [kernel_names.seconds(ctx["trace"], (name,))[0] > 0
            for name in ("short_conv_fwd", "short_conv_bwd")]
    seconds = least(ctx["cfg"], ctx["window"]["batch"],
                    peaks_of(ctx["device"]), forward=held[0],
                    backward=held[1])
    return 100.0 * seconds / (spent / 1e3)
