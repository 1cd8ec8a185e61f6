"""Device self time per optimizer step of the Mamba-2 convolution's
Pallas kernels (``depthwise_conv_*``: since PR 38 the backward kernel
alone, under full remat one call a state-space layer); nothing where
the trace has no such kernel, as in a program whose convolution is
XLA's in both passes."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "depthwise_conv_")
