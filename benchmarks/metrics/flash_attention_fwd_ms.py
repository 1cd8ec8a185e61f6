"""Device self time per optimizer step of the attention forward
kernel, resident or streamed (``flash_attention_fwd_*``)."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "flash_attention_fwd")
