"""Device self time per optimizer step of the convolution kernel's
backward-weights pass (``conv_block_bwd_weights_*``)."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "conv_block_bwd_weights")
