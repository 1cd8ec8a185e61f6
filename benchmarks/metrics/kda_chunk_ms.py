"""Device self time per optimizer step of Kimi Delta Attention's chunk
kernels (``kda_chunk_*``: the forward kernel, run for the primal pass
and again for full remat's recomputation, and the backward kernel, one
call a delta-rule layer each); nothing where the trace has no such
kernel, as in a program whose chunk is XLA's."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "kda_chunk_")
