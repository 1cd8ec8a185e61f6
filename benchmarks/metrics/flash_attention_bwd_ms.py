"""Device self time per optimizer step of the attention backward
kernel (``flash_attention_bwd_*``); nothing where the trace has no
such kernel, as in a program whose attention backward is XLA's."""

from benchmarks.harness import kernel_names


def read(ctx):
    return kernel_names.ms_per_step(ctx, "flash_attention_bwd")
