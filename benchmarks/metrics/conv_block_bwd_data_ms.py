"""Device self time per optimizer step of the convolution kernel's
backward-data pass (``conv_block_bwd_data_*``: the forward kernel on
the dilated gradient and the flipped weights, float32)."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "conv_block_bwd_data")
