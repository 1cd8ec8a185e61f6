"""Seconds of the set-up inside jax's tracing of functions to jaxprs:
the union of the program's ``compile.trace`` records that ended before
the traced window's ``fit`` span began (``harness/compile_spans.py``)."""

from benchmarks.harness import compile_spans


def read(ctx):
    setup = compile_spans.of_setup()
    return None if setup is None else setup.seconds("compile.trace")
