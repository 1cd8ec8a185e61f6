"""Seconds of the set-up inside the conversion of jaxprs to MLIR
modules: the union of the program's ``compile.lower`` records that
ended before the traced window's ``fit`` span began
(``harness/compile_spans.py``). PR 28 found this phase swing between
0.7 and 14.7 s with the frames of the fit drivers."""

from benchmarks.harness import compile_spans


def read(ctx):
    setup = compile_spans.of_setup()
    return None if setup is None else setup.seconds("compile.lower")
