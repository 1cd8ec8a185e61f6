"""Share of the Mosaic kernels' device time (category
``tpu_custom_call``) spent in kernels that carry a name of the
program's kernel library (``harness/kernel_names.py``). Reads 100; a
kernel added without a name, which XLA then calls after its enclosing
scope, shows as less."""

from benchmarks.harness import kernel_names


def read(ctx):
    named, every = kernel_names.seconds(ctx["trace"], kernel_names.KERNELS)
    return 100.0 * named / every if every else None
