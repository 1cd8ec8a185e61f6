"""Device self time per optimizer step of the convolution kernel's
forward pass (``conv_block_fwd_*``), the backward pass's recompute of
the pre-epilogue accumulator included (``conv_block_fwd_recompute_*``):
both run the forward kernel."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "conv_block_fwd")
