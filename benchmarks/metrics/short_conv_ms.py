"""Device self time per optimizer step of the gated short convolution's
kernels (``short_conv_*``: the forward kernel, run for the primal pass
and again for full remat's recomputation, and the backward kernel, one
call a convolution layer each); nothing where the trace has no such
kernel, as in a program whose short convolution is XLA's."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "short_conv_")
