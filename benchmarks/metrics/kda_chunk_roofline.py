"""Kimi Delta Attention's chunk kernels as a share of their roofline:
the least time a step of the chunk needs, once a delta-rule layer
(``counts/<config>.py`` ``kda_chunk_seconds_per_step``: its products
against its bytes, forward and backward where the trace holds a
``kda_chunk_bwd`` kernel, the forward alone where it does not), over the
device time of the ``kda_chunk_*`` kernels a step. Full remat runs the
forward kernel twice, so a perfect program reads about 3/4; nothing
where the trace has no such kernel or the counts have no such
function."""

from benchmarks.harness import kernel_names
from benchmarks.harness.device import peaks_of


def read(ctx):
    least = getattr(ctx["counts"], "kda_chunk_seconds_per_step", None)
    spent = kernel_names.ms_per_step(ctx, "kda_chunk_")
    if least is None or spent is None or ctx["device"].platform != "tpu":
        return None
    backward = kernel_names.seconds(ctx["trace"], ("kda_chunk_bwd",))[0] > 0
    seconds = least(ctx["cfg"], ctx["window"]["batch"],
                    peaks_of(ctx["device"]), backward=backward)
    return 100.0 * seconds / (spent / 1e3)
